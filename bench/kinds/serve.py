"""Cells of the scheduling service: ``SchedServer.serve_stream`` under
closed-loop FL-job traffic (``bench/loadgen.py``).

Set-up builds the server from the configuration file (its step and admit
programs come from the compile cache after a cell's first run), joins the
mix's jobs, compiles every batch size of the autosize ladder that the
stream may pick, and runs the jobs for the mix's ``warmup_s``.  The
window follows without a break.  Afterwards the plain reference replays a
sample of the jobs, drawn from the seed with the busiest job in it, and
every answer those jobs got is compared with the reference's.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import tempfile
import time

import numpy as np

from bench import harness
from bench.loadgen import ClosedLoop

def build_server(cfg):
    from repro.core.bandits import GLRCUCB
    from repro.sim import SchedServer
    s, v = cfg["scheduler"], cfg["server"]
    sched = GLRCUCB(s["n_channels"], s["n_clients"], delta=s["delta"],
                    gamma=s["gamma"], alpha=s["alpha"], history=s["history"],
                    detector_stride=s["detector_stride"],
                    min_samples=s["min_samples"], split_grid=s["split_grid"])
    server = SchedServer(sched, capacity=v["capacity"], slots=v["slots"],
                         use_matching=v["use_matching"],
                         matcher_beta=s["matcher_beta"])
    server.warm()
    return server


def _request(tenant, rewards, key, contrib, aoi):
    from repro.sim import ServeRequest
    return ServeRequest(tenant, rewards, key, contrib=contrib, aoi=aoi)


def drive(cfg, mix, seed, seconds, trace_dir=None, log=None):
    """Set up, warm up and run one window; returns the finished loop, the
    server and the set-up split."""
    import jax
    from repro.sim.sweep import sweep_cache_stats

    split = {}
    t = time.perf_counter()
    server = build_server(cfg)
    split["server_s"] = time.perf_counter() - t
    s = cfg["scheduler"]
    if int(mix["jobs"]) > cfg["server"]["capacity"]:
        raise ValueError("the mix has more jobs than the server has capacity")
    span = None
    if trace_dir is not None:
        span = jax.profiler.TraceAnnotation
    t = time.perf_counter()
    loop = ClosedLoop(server, mix, seed, s["n_channels"], s["n_clients"],
                      _request, span=span)
    split["join_s"] = time.perf_counter() - t
    counter = harness.CompileCounter()
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    misses0 = sweep_cache_stats()["misses"]
    loop.on_window = lambda on: setattr(counter, "active", on)
    try:
        loop.run(float(mix["warmup_s"]), seconds)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    split["warmup_s"] = float(mix["warmup_s"])
    split["compiles_in_window"] = counter.count
    split["cache_misses_after_setup"] = sweep_cache_stats()["misses"] - misses0
    if log:
        log(f"set-up split {split}")
    return loop, server, split


def sample_jobs(loop, seed, k):
    """``k`` jobs drawn from the seed, the busiest among them."""
    jobs = [j for j in loop.jobs.values() if j.answers]
    busiest = max(jobs, key=lambda j: len(j.answers))
    rest = [j for j in jobs if j is not busiest]
    rng = np.random.default_rng([seed, 0x5A3])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [busiest] + [rest[i] for i in sorted(pick)]


def mismatch_share(reference, jobs, scheduler, dtype=None, against=None):
    """Share of the jobs' answers that differ from the reference's.

    With ``against`` (a list of per-job decision arrays), compares the
    reference at ``dtype`` with those instead of with the service."""
    import jax.numpy as jnp
    hist = [j.history() for j in jobs]
    mine = reference.decide(hist, scheduler, dtype or jnp.float32)
    other = against if against is not None else [h["served"] for h in hist]
    bad = sum(int(np.any(a != b, axis=1).sum()) for a, b in zip(mine, other))
    total = sum(len(h["served"]) for h in hist)
    return bad / max(total, 1), total, mine


def run(ctx):
    """One run of a serving cell; see ``bench/run.py`` for ``ctx``."""
    cfg, mix = ctx.cfg, ctx.mix
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if ctx.trace else None
    try:
        loop, server, split = drive(cfg, mix, ctx.seed, ctx.seconds,
                                    trace_dir=trace_dir, log=ctx.log)
        setup_s = loop.w0 - ctx.t_start
        obs = {"cfg": cfg, "loop": loop, "bench": ctx.bench,
               "device_kind": ctx.devices[0].device_kind,
               "counters": loop.window_marks}
        if trace_dir is not None:
            from bench.tracing import Trace
            import glob
            path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            obs["trace"] = Trace.from_file(path)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = harness.device_record(ctx.devices)
    lat = np.asarray(loop.lat)
    ctx.log(f"window: {loop.window_requests} requests due, "
            f"{loop.answered_in_window} answered inside it, "
            f"{loop.sent} sent in all, {loop.left_jobs} jobs left and were "
            f"replaced; stats {server.stats()}")
    e2e = {"setup_s": setup_s,
           "decisions_per_s": loop.answered_in_window / ctx.seconds}
    if lat.size:
        e2e["decision_p99_ms"] = float(np.percentile(lat, 99)) * 1e3
        q = {p: float(np.percentile(lat, p)) * 1e3 for p in (50, 90, 95, 99, 99.9)}
        ctx.log(f"latency ms {q} over {lat.size} requests "
                f"({int(lat.size * 0.01)} beyond p99)")
    # the service's state is freed before the reference runs on the chip
    loop.server = None
    del server
    gc.collect()
    reference = ctx.bench.reference(cfg["name"])
    jobs = sample_jobs(loop, ctx.seed, int(cfg["check"]["sample_jobs"]))
    share, checked, _ = mismatch_share(reference, jobs, cfg["scheduler"])
    ctx.log(f"reference: {checked} answers of {len(jobs)} jobs checked")
    unanswered = loop.sent - loop.answered
    checks = {
        "mismatch_share": {"value": share,
                           "limit": cfg["check"]["mismatch_share_limit"]},
        "unanswered": {"value": unanswered, "limit": 0},
    }
    return {"e2e": e2e, "obs": obs, "checks": checks, "device": device,
            "attempted": loop.window_requests, "failed": unanswered}


def control(ctx, seeds):
    """Readings for the limit on ``mismatch_share``, one line per seed:
    the service against the reference (sound runs: the lower reading) and
    the reference computed in bfloat16, a precision below the
    configuration's float32, against the float32 reference (the control:
    the upper reading), on the same jobs at the cell's own size."""
    import json
    import jax.numpy as jnp
    reference = ctx.bench.reference(ctx.cfg["name"])
    rows = []
    for seed in seeds:
        loop, server, _ = drive(ctx.cfg, ctx.mix, seed, ctx.seconds)
        loop.server = None
        del server
        gc.collect()
        jobs = sample_jobs(loop, seed, int(ctx.cfg["check"]["sample_jobs"]))
        share, checked, ref = mismatch_share(reference, jobs,
                                             ctx.cfg["scheduler"])
        ctl, _, _ = mismatch_share(reference, jobs, ctx.cfg["scheduler"],
                                   jnp.bfloat16, against=ref)
        rows.append({"seed": seed, "service": share, "control_bf16": ctl,
                     "answers_checked": checked})
        ctx.log(json.dumps(rows[-1]))
    return rows


# Faults a serving cell can have, planted in the program underneath the
# timed path (for the tests).  Each builds its serve steps anew: the
# process keeps compiled steps, and a broken one must not come from there.
@contextlib.contextmanager
def _broken_step(wrap):
    from unittest import mock
    import repro.sim.serve as serve
    from repro.sim.sweep import clear_sweep_cache
    make = serve.make_serve_step
    clear_sweep_cache()
    try:
        with mock.patch.object(serve, "make_serve_step",
                               lambda *a, **k: wrap(make(*a, **k))):
            yield
    finally:
        clear_sweep_cache()


def fault_state_unchanged():
    """The step returns the tenants' state it was given."""
    def wrap(step):
        def frozen(state, *args):
            _, assignment, mstate = step(state, *args)
            return state, assignment, mstate
        return frozen
    return _broken_step(wrap)


def fault_half_batch():
    """The second half of each step's request rows is left out."""
    def wrap(step):
        def half(state, slots, rewards, keys, contrib, aoi, aoi_set, mask):
            b = mask.shape[0]
            return step(state, slots, rewards, keys, contrib, aoi, aoi_set,
                        mask.at[b - b // 2:].set(False))
        return half
    return _broken_step(wrap)


def fault_altered_answer():
    """One channel of each step's first answer is altered where the step
    produces it."""
    def wrap(step):
        def altered(state, *args):
            state, assignment, mstate = step(state, *args)
            return state, assignment.at[0, 0].add(1), mstate
        return altered
    return _broken_step(wrap)


FAULTS = {"state_unchanged": fault_state_unchanged,
          "half_batch": fault_half_batch,
          "altered_answer": fault_altered_answer}
