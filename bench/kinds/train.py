"""Cells of the population trainer: ``SparseAsyncFLTrainer.run`` rounds.

Set-up makes the clients' data, the server's proxy set and the model's
weights on the device from the seed (one jitted call each), builds the
trainer with the mix's aggregator, and drives it from the seed through
its first call of ``rounds_per_call`` rounds: the window's own compiled
call, on the window's own data.  The same state goes on into the window,
which calls ``run`` back to back, never more than two calls in flight,
and blocks on the state at the end.  Afterwards the plain reference
follows those first rounds from the same seed, and the program's rounds
are compared with it: each round's mean local loss, and, by the worst
parameter leaf, the clients' updates held after the rounds and the
parameters' change over them.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import glob
import os
import shutil
import tempfile
import time

import numpy as np

from bench import harness


def _key(seed):
    """A raw PRNG key from any non-negative seed (64 bits used)."""
    import jax.numpy as jnp
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def make_inputs(cfg, seed, reference):
    """Clients' data, proxy set, channels and weights, on the device."""
    import jax
    import jax.numpy as jnp
    pop, mdl, rnd, chan = (cfg["population"], cfg["model"], cfg["round"],
                           cfg["channels"])
    n, n_ex, s = pop["n_clients"], pop["examples_per_client"], mdl["image"]

    @jax.jit
    def data(key):
        kx, ky = jax.random.split(key)
        return (jax.random.bits(kx, (n, n_ex, s * s), jnp.uint8),
                jax.random.randint(ky, (n, n_ex), 0, mdl["classes"], jnp.int32))

    @jax.jit
    def proxy(key):
        kx, ky = jax.random.split(key)
        return (jax.random.bits(kx, (rnd["proxy_examples"], s * s), jnp.uint8),
                jax.random.randint(ky, (rnd["proxy_examples"],), 0,
                                   mdl["classes"], jnp.int32))

    key = _key(seed)
    client_x, client_y = data(jax.random.fold_in(key, 1))
    proxy_x, proxy_y = proxy(_key(cfg["proxy_seed"]))
    params = jax.jit(functools.partial(reference.init, model=mdl))(
        jax.random.fold_in(key, 2))
    rng = np.random.default_rng([seed, 0xC4A])
    means = rng.uniform(chan["mean_low"], chan["mean_high"],
                        (chan["segments"], cfg["scheduler"]["n_channels"]))
    breaks = np.arange(1, chan["segments"]) * chan["segment_rounds"]
    return (client_x, client_y, proxy_x, proxy_y,
            jnp.asarray(means, jnp.float32), jnp.asarray(breaks, jnp.int32),
            params, jax.random.fold_in(key, 3))


def build_trainer(cfg, mix, inputs, reference):
    import jax
    from repro.core.aggregation import make_aggregator
    from repro.core.availability import MarkovChurn
    from repro.core.bandits import GLRCUCB
    from repro.core.channels import segment_env
    from repro.fl.sparse import SparseAsyncFLTrainer, SparseFLConfig
    _, _, proxy_x, proxy_y, means, breaks, params, _ = inputs
    s, rnd, av = cfg["scheduler"], cfg["round"], cfg["availability"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    sizes = [int(np.prod(x.shape)) for x in leaves]
    offsets = np.cumsum([0] + sizes)

    def unflatten(flat):
        return jax.tree_util.tree_unflatten(tree, [
            flat[offsets[i]:offsets[i + 1]].reshape(x.shape)
            for i, x in enumerate(leaves)])

    def proxy_loss(flat):
        return reference.loss(unflatten(flat), proxy_x, proxy_y)

    sched = GLRCUCB(s["n_channels"], s["n_clients"], delta=s["delta"],
                    gamma=s["gamma"], alpha=s["alpha"], history=s["history"],
                    detector_stride=s["detector_stride"],
                    min_samples=s["min_samples"], split_grid=s["split_grid"])
    agg = None if mix["aggregator"] == "mean" else make_aggregator(mix["aggregator"])
    return SparseAsyncFLTrainer(
        cfg=SparseFLConfig(
            n_clients=cfg["population"]["n_clients"], n_sched=rnd["n_sched"],
            n_channels=s["n_channels"], batch_size=rnd["batch_size"],
            local_epochs=rnd["local_steps"], client_lr=rnd["client_lr"],
            server_lr=rnd["server_lr"], matcher_beta=s["matcher_beta"]),
        scheduler=sched, env=segment_env(means, breaks),
        loss_fn=reference.loss, proxy_loss_fn=proxy_loss,
        availability=MarkovChurn(p_drop=av["p_drop"], p_rejoin=av["p_rejoin"]),
        aggregator=agg)


def round_keys(key, calls, per_call):
    import jax
    return np.asarray(jax.random.split(key, calls * per_call)).reshape(
        calls, per_call, 2)


class Rounds:
    """The trainer, its state and the rounds it has run."""

    def __init__(self, cfg, mix, seed, reference, log=None):
        import jax
        t = time.perf_counter()
        self.inputs = make_inputs(cfg, seed, reference)
        jax.block_until_ready(self.inputs)
        self.split = {"inputs_s": time.perf_counter() - t}
        self.trainer = build_trainer(cfg, mix, self.inputs, reference)
        self.per_call = cfg["round"]["rounds_per_call"]
        self.keys = self.inputs[-1]
        self.params0 = jax.tree_util.tree_map(np.asarray, self.inputs[6])
        self.state = self.trainer.init(self.inputs[6], jax.random.fold_in(
            self.keys, 0))
        self.calls = 0
        self._keys = round_keys(jax.random.fold_in(self.keys, 1), 1024,
                                self.per_call)
        t = time.perf_counter()
        self.first_metrics = self.call()
        jax.block_until_ready(self.state)
        self.split["first_call_s"] = time.perf_counter() - t
        self.first = {
            "local_loss": np.asarray(self.first_metrics["local_loss"]),
            "n_success": np.asarray(self.first_metrics["n_success"]),
            "params": jax.tree_util.tree_map(np.asarray, self.state.params),
            "buffers": _buffer_leaves(np.asarray(self.state.buffers),
                                      self.params0)}
        if log:
            log(f"set-up split {self.split}")

    def call(self):
        c = self.calls
        if c >= len(self._keys):     # more rounds than drawn: draw more
            import jax
            self._keys = np.concatenate([self._keys, round_keys(
                jax.random.fold_in(self.keys, 2 + c), 1024, self.per_call)])
        cx, cy = self.inputs[0], self.inputs[1]
        self.state, metrics = self.trainer.run(self.state, cx, cy,
                                               self._keys[c])
        self.calls += 1
        return metrics

    def window(self, seconds, on_window=None):
        """Calls back to back for ``seconds``; returns rounds and seconds
        from the window's start to the last round's completion."""
        import jax
        t0 = time.perf_counter()
        if on_window:
            on_window(True)
        calls0, prev = self.calls, None
        while time.perf_counter() - t0 < seconds:
            self.call()
            if prev is not None:
                jax.block_until_ready(prev)
            prev = self.state.t
        if on_window:
            on_window(False)
        jax.block_until_ready(self.state)
        return (self.calls - calls0) * self.per_call, time.perf_counter() - t0

    def free(self):
        self.state = self.trainer = None
        gc.collect()


def _leaf_gaps(prog, ref):
    """Per leaf, |norm(prog) - norm(ref)| against the larger of its
    reference norm and the median leaf's; leaves the reference leaves at
    under a thousandth of the median leaf's norm are left out (nan)."""
    rn = np.array([np.linalg.norm(r) for r in ref])
    pn = np.array([np.linalg.norm(p) for p in prog])
    med = np.median(rn)
    gaps = np.abs(pn - rn) / np.maximum(rn, med)
    return np.where(rn >= 1e-3 * med, gaps, np.nan)


def _norm_gap(prog, ref):
    """The worst leaf's gap (``_leaf_gaps``)."""
    return float(np.nanmax(_leaf_gaps(prog, ref)))


def _buffer_leaves(flat, like):
    """Split (M, P) flat rows into per-leaf (M, ...) blocks."""
    import jax
    out, off = [], 0
    for leaf in jax.tree_util.tree_leaves(like):
        n = int(np.prod(leaf.shape))
        out.append(flat[:, off:off + n])
        off += n
    return out


def compare(first, ref_out, params0):
    """The three compared numbers of the program's first rounds against
    the reference's."""
    import jax
    losses, w_ref, buf_ref = ref_out[:3]
    loss_gap = float(np.max(np.abs(first["local_loss"] - np.asarray(losses))
                            / np.abs(np.asarray(losses))))
    p0 = jax.tree_util.tree_leaves(params0)
    change = _norm_gap(
        [p - q for p, q in zip(jax.tree_util.tree_leaves(first["params"]), p0)],
        [p - q for p, q in zip(jax.tree_util.tree_leaves(w_ref), p0)])
    update = _norm_gap(first["buffers"], jax.tree_util.tree_leaves(buf_ref))
    return {"loss_gap": loss_gap, "update_gap": update, "change_gap": change}


def events(state):
    """The program's discrete state after a call, as the reference's
    ``replay`` gives its own."""
    return {"slot_clients": np.asarray(state.slot_clients),
            "has_update": np.asarray(state.has_update),
            "aoi": np.asarray(state.aoi)}


def look(first, ref_out, params0):
    """Where the program and the reference part: each round's loss gap and
    delivered count, how many entries of the discrete state differ (a
    client selected, holding an update, or delivered otherwise: AoI), and
    each leaf's gap (leaves in pytree order)."""
    import jax
    losses, w_ref, buf_ref, delivered, ref_events = ref_out
    p0 = jax.tree_util.tree_leaves(params0)
    return {
        "differ": {k: int(np.sum(np.asarray(first["events"][k]) != v))
                   for k, v in ref_events.items()},
        "loss_gaps": (np.abs(first["local_loss"] - np.asarray(losses))
                      / np.abs(np.asarray(losses))).tolist(),
        "delivered": [first["n_success"].tolist(), delivered],
        "change_leaves": _leaf_gaps(
            [p - q for p, q in zip(jax.tree_util.tree_leaves(first["params"]), p0)],
            [p - q for p, q in zip(jax.tree_util.tree_leaves(w_ref), p0)]).tolist(),
        "update_leaves": _leaf_gaps(
            first["buffers"], jax.tree_util.tree_leaves(buf_ref)).tolist()}


def replay(rounds, cfg, mix, reference, dtype):
    """The reference over the program's first call's rounds."""
    return reference.replay(rounds.inputs[6], rounds.inputs[:6],
                            list(rounds._keys[0]), cfg, mix["aggregator"],
                            dtype)


def run(ctx):
    """One run of a training cell; see ``bench/run.py`` for ``ctx``."""
    import jax
    cfg, mix = ctx.cfg, ctx.mix
    reference = ctx.bench.reference(cfg["name"])
    rounds = Rounds(cfg, mix, ctx.seed, reference, log=ctx.log)
    counter = harness.CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if ctx.trace else None
    try:
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
        setup_s = time.perf_counter() - ctx.t_start
        done, secs = rounds.window(
            ctx.seconds, lambda on: setattr(counter, "active", on))
        obs = {"cfg": cfg, "bench": ctx.bench, "mix": mix,
               "device_kind": ctx.devices[0].device_kind,
               "rounds": done, "elapsed_s": secs}
        if trace_dir is not None:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            from bench.tracing import Trace
            obs["trace"] = Trace.from_file(glob.glob(
                os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0])
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ctx.log(f"window: {done} rounds in {secs} s, {counter.count} compiles; "
            f"first rounds' n_success {rounds.first['n_success'].tolist()}")
    device = harness.device_record(ctx.devices)
    rounds.free()
    import jax.numpy as jnp
    numbers = compare(rounds.first, replay(rounds, cfg, mix, reference, jnp.float32),
                      rounds.params0)
    checks = {k: {"value": v, "limit": cfg["check"][f"{k}_limit"]}
              for k, v in numbers.items()}
    return {"e2e": {"setup_s": setup_s, "fl_rounds_per_s": done / secs},
            "obs": obs, "checks": checks, "device": device,
            "attempted": done, "failed": 0}


def control(ctx, seeds):
    """Readings for the limits, one line per seed: the program against
    the reference (the lower readings) and the reference in bfloat16 in
    the program's place (the control)."""
    import json
    import jax
    import jax.numpy as jnp
    reference = ctx.bench.reference(ctx.cfg["name"])
    rows = []
    for seed in seeds:
        rounds = Rounds(ctx.cfg, ctx.mix, seed, reference)
        rounds.first["events"] = events(rounds.state)
        rounds.free()
        ref = replay(rounds, ctx.cfg, ctx.mix, reference, jnp.float32)
        low = replay(rounds, ctx.cfg, ctx.mix, reference, jnp.bfloat16)
        as_program = {"local_loss": np.asarray(low[0]), "params": low[1],
                      "buffers": jax.tree_util.tree_leaves(low[2]),
                      "n_success": np.asarray(low[3]), "events": low[4]}
        rows.append({"seed": seed,
                     "program": compare(rounds.first, ref, rounds.params0),
                     "control_bf16": compare(as_program, ref, rounds.params0),
                     "look": look(rounds.first, ref, rounds.params0),
                     "look_control": look(as_program, ref, rounds.params0)})
        ctx.log(json.dumps(rows[-1]))
        del rounds
        gc.collect()
    return rows


# Faults a training cell can have, planted in the program underneath the
# timed path: for the tests, and for the readings that bound the limits.
@contextlib.contextmanager
def fault_state_unchanged():
    """``run`` returns the state it was given."""
    from unittest import mock
    import repro.fl.sparse as sparse
    run = sparse.SparseAsyncFLTrainer.run

    def frozen(self, state, *args):
        _, metrics = run(self, state, *args)
        return state, metrics
    with mock.patch.object(sparse.SparseAsyncFLTrainer, "run", frozen):
        yield


@contextlib.contextmanager
def fault_half_batch():
    """Each client's local steps see only half their mini-batch, the mean
    taken over the rest."""
    from unittest import mock
    import repro.fl.sparse as sparse
    sgd = sparse.local_sgd

    def half(loss_fn, params, bx, by, lr):
        b = bx.shape[1]
        return sgd(loss_fn, params, bx[:, : b - b // 2], by[:, : b - b // 2], lr)
    with mock.patch.object(sparse, "local_sgd", half):
        yield


@contextlib.contextmanager
def fault_altered_aggregate():
    """One coordinate of each round's aggregate is altered where the
    aggregation produces it."""
    from unittest import mock
    import repro.fl.sparse as sparse
    agg = sparse.dispatch_aggregate

    def altered(*args):
        return agg(*args).at[0].add(1.0)
    with mock.patch.object(sparse, "dispatch_aggregate", altered):
        yield


FAULTS = {"state_unchanged": fault_state_unchanged,
          "half_batch": fault_half_batch,
          "altered_aggregate": fault_altered_aggregate}
