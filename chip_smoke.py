"""Bring-up smoke test: the scheduler's main path on a TPU, end to end.

Usage (from the repository root, on a machine with a TPU):

  python chip_smoke.py               # one chip: kernels, serving, trainer, sweep
  python chip_smoke.py --four-chips  # only the sharded paths, on four chips

One process drives everything.  The phases, in order:

  kernels  each Pallas kernel of the main path, compiled for the chip, vs
           its jnp oracle (``repro.kernels.ref``) on seeded inputs at the
           shapes the main path uses;
  serve    the multi-tenant ``SchedServer`` at the launcher's defaults:
           256 tenants, ``warm()``, a few hundred requests through
           ``serve_stream`` with one leave/join churn and no compiles after
           warm-up, single-tenant serve == offline simulator bitwise, and
           ``save()``/``restore()`` mid-stream resuming bitwise;
  trainer  ``SparseAsyncFLTrainer`` at N=100,000 clients / M=64 slots under
           Markov churn with the ``mean`` and ``coordinate_median``
           aggregators, and the sparse == dense trainer bitwise check at the
           paper's FL scale (M = N, the dense trainer's donated path);
  sweep    a GLR-CUCB H=1024 geometric-grid bucket over four scenario
           families through ``sweep()``, batch-of-1 == serial bitwise.

``--four-chips`` runs only the multi-chip path and what it is compared
with: ``SchedServer(shard=True)`` at 10^4 tenants vs its unsharded twin,
and ``sweep(shard=True)`` vs the unsharded sweep, both bitwise.

Every check that fails raises, so the script exits non-zero; it exits
non-zero before any phase when JAX finds no TPU.  For each compiled serve
step, trainer scan and sweep program it prints whether the HLO holds a
Pallas kernel (``tpu_custom_call``).  Wall and compile times are printed
as SET-UP numbers (compile included, cold or warm cache): they are not
benchmark numbers.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import make_aggregator
from repro.core.availability import MarkovChurn
from repro.core.bandits import GLRCUCB
from repro.core.bandits.base import stack_params
from repro.core.channels import (
    GilbertElliottProcess, JammingOverlay, MobilityDriftProcess,
    PiecewiseProcess, ShadowingProcess, make_scenario, make_stationary,
    random_piecewise_env, realize_processes, scenario_realize_key)
from repro.core.regret import simulate_aoi_regret
from repro.data.pipeline import (
    client_batch_indices,
    gather_backends,
    gather_client_batches,
)
from repro.fl import (AsyncFLConfig, AsyncFLTrainer, SparseAsyncFLTrainer,
                      SparseFLConfig)
from repro.fl.sparse import _DATA_TAG
from repro.kernels import glr_scan as _glr
from repro.kernels import glr_step as _gs
from repro.kernels import ref
from repro.kernels import robust_agg as _ra
from repro.kernels import weighted_aggregate as _wa
from repro.sim import (SchedServer, ServeRequest, SweepCase,
                       offline_round_stream, simulate_aoi_regret_batch,
                       sweep, sweep_cache_stats)
from repro.sim.shard import sweep_mesh
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.tree import tree_flatten_concat

# == jax.random.PRNGKey(0), built without touching the backend: nothing may
# compile before main() has set up the compilation cache
KEY = np.zeros((2,), np.uint32)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def report_kernel(label: str, compiled, expect_kernel: bool) -> None:
    found = has_kernel(compiled)
    print(f"  hlo: {label} tpu_custom_call={found}", flush=True)
    if expect_kernel:
        check(found, f"{label} runs a Pallas kernel")


def setup_times(phase: str, wall_s: float, compile_s: float) -> None:
    print(f"[set-up, not a benchmark number] phase={phase} "
          f"wall_s={wall_s} compile_s={compile_s}", flush=True)


def bit_diffs(a, b):
    """Where two pytrees differ: ``(leaf path, detail)`` per unequal leaf."""
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return [("<structure>", f"{len(la)} leaves vs {len(lb)}")]
    out = []
    for (path, x), y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape:
            out.append((jax.tree_util.keystr(path), f"{x.shape} vs {y.shape}"))
        elif not np.array_equal(x, y):
            bad = x != y
            out.append((jax.tree_util.keystr(path),
                        f"{int(bad.sum())}/{x.size} elements differ"))
    return out


def check_bits(a, b, what: str) -> None:
    diffs = bit_diffs(a, b)
    if diffs:
        raise SmokeFailure(f"{what}: {diffs[:8]}")
    print(f"  ok: {what}", flush=True)


# ---------------------------------------------------------------------------
# phase 2: kernels vs their jnp oracles
# ---------------------------------------------------------------------------

def _bernoulli_prefix_state(key, lead, n, h, steps):
    """A consistent streaming-detector state ``(cum, total, base, counts)``
    reached by appending ``steps`` masked Bernoulli samples per channel
    through the oracle — ``steps > h`` wraps the ring."""
    def one(k):
        def step(carry, kk):
            cum, total, base, counts = carry
            k1, k2 = jax.random.split(kk)
            r = jax.random.bernoulli(k1, 0.4, (n,)).astype(jnp.float32)
            sch = jax.random.bernoulli(k2, 0.7, (n,))
            cum, total, base = ref.glr_stream_append(cum, total, base,
                                                     counts, r, sch)
            return (cum, total, base, counts + sch.astype(jnp.float32)), None
        init = (jnp.zeros((n, h)), jnp.zeros((n,)), jnp.zeros((n,)),
                jnp.zeros((n,)))
        return jax.lax.scan(step, init, jax.random.split(k, steps))[0]

    keys = jax.random.split(key, int(np.prod(lead)) if lead else 1)
    out = jax.jit(jax.vmap(one))(keys)
    return jax.tree_util.tree_map(
        lambda x: x.reshape(lead + x.shape[1:]), out)


def _glr_inputs(key, lead, n, h):
    cum, total, base, counts = _bernoulli_prefix_state(key, lead, n, h,
                                                       steps=2 * h + 7)
    k1, k2 = jax.random.split(jax.random.fold_in(key, 1))
    r = jax.random.bernoulli(k1, 0.5, lead + (n,)).astype(jnp.float32)
    sch = jax.random.bernoulli(k2, 0.7, lead + (n,))
    return cum, total, base, counts, r, sch


def _timed_compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _check_glr_step(label, kernel_fn, oracle_fn, args):
    compiled, cs = _timed_compile(kernel_fn, *args)
    got = compiled(*args)
    want = jax.jit(oracle_fn)(*args)
    for name, g, w in zip(("cum", "total", "base"), got[:3], want[:3]):
        check_bits(g, w, f"{label} {name} bitwise (integer prefixes)")
    stat_g, stat_w = np.asarray(got[3]), np.asarray(want[3])
    finite = np.isfinite(stat_w)
    diff = float(np.max(np.abs(stat_g - stat_w)[finite], initial=0.0))
    check(np.allclose(stat_g, stat_w, rtol=1e-5, atol=1e-5),
          f"{label} stats within 1e-5 (max |diff| {diff})")
    return cs


def phase_kernels(backend="pallas", n=16, h=1024, g=256, gh=256, m=64,
                  p=1 << 20, chunk=8192):
    """Each kernel on ``backend`` ("pallas" on the chip, "pallas_interpret"
    in a CPU rehearsal) vs its oracle, at the main path's shapes."""
    interpret = backend == "pallas_interpret"
    compile_s = 0.0
    key = jax.random.fold_in(KEY, 2)

    # glr_step, 2-D (the sweep / offline-simulator form), both split grids
    args = _glr_inputs(key, (), n, h)
    for grid in ("all", "geometric"):
        compile_s += _check_glr_step(
            f"glr_step N={n} H={h} {grid}",
            lambda *a, grid=grid: _gs.glr_step(*a, split_grid=grid,
                                               interpret=interpret),
            lambda *a, grid=grid: ref.glr_step(*a, split_grid=grid),
            args)

    # glr_step_tenants (the serving form), both split grids
    targs = _glr_inputs(jax.random.fold_in(key, 3), (g,), n, gh)
    for grid in ("all", "geometric"):
        compile_s += _check_glr_step(
            f"glr_step_tenants G={g} N={n} H={gh} {grid}",
            lambda *a, grid=grid: _gs.glr_step_tenants(
                *a, split_grid=grid, interpret=interpret),
            lambda *a, grid=grid: jax.vmap(
                lambda *b: ref.glr_step(*b, split_grid=grid))(*a),
            targs)

    # weighted_aggregate (Eq. 7), f32 and bf16 updates
    k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 4), 3)
    upd = jax.random.normal(k1, (m, p), jnp.float32)
    scale = jax.random.uniform(k2, (m,)) * (
        jax.random.uniform(k3, (m,)) < 0.8)
    for dtype in (jnp.float32, jnp.bfloat16):
        u = upd.astype(dtype)
        compiled, cs = _timed_compile(
            lambda a, b: _wa.weighted_aggregate(a, b, interpret=interpret),
            u, scale)
        compile_s += cs
        got = np.asarray(compiled(u, scale))
        want = np.asarray(jax.jit(ref.weighted_aggregate)(u, scale))
        check(np.allclose(got, want, rtol=1e-4, atol=1e-4),
              f"weighted_aggregate M={m} P={p} {jnp.dtype(dtype).name} "
              f"within 1e-4 (max |diff| {float(np.max(np.abs(got - want)))})")

    # robust_trimmed at median depth: rank selection is exact, and at most
    # two values are kept per coordinate, so the kernel is bitwise
    mask = jax.random.bernoulli(jax.random.fold_in(key, 5), 0.8,
                                (m,)).astype(jnp.float32)
    n_succ = jnp.sum(mask)
    k_med = jnp.maximum(jnp.floor((n_succ - 1.0) / 2.0), 0.0)
    compiled, cs = _timed_compile(
        lambda *a: _ra.robust_trimmed(*a, interpret=interpret),
        upd, mask, n_succ, k_med)
    compile_s += cs
    got = compiled(upd, mask, n_succ, k_med)

    @jax.jit
    def oracle_chunked(u, msk, ns, k):
        # the oracle's (M, M, P) comparison tensor does not fit the chip at
        # full P: evaluate it one column chunk at a time
        cols = u.reshape(m, p // chunk, chunk).transpose(1, 0, 2)
        out = jax.lax.map(lambda c: ref.robust_trimmed(c, msk, ns, k), cols)
        return out.reshape(p)

    want = oracle_chunked(upd, mask, n_succ, k_med)
    check_bits(got, want,
               f"robust_trimmed M={m} P={p} median (n={int(n_succ)}) bitwise")

    # glr_scan (the recompute detector's kernel) on Bernoulli histories
    hist = jax.random.bernoulli(jax.random.fold_in(key, 6), 0.4,
                                (n, h)).astype(jnp.float32)
    counts = jax.random.randint(jax.random.fold_in(key, 7), (n,), 0, h + 1)
    compiled, cs = _timed_compile(
        lambda a, b: _glr.glr_scan(a, b, interpret=interpret), hist, counts)
    compile_s += cs
    got = np.asarray(compiled(hist, counts))
    want = np.asarray(jax.jit(ref.glr_scan)(hist, counts))
    check(np.allclose(got, want, rtol=1e-5, atol=1e-5),
          f"glr_scan N={n} H={h} within 1e-5")
    return compile_s


# ---------------------------------------------------------------------------
# phase 3: the multi-tenant scheduling service
# ---------------------------------------------------------------------------

def _pool_server(sched, capacity, slots, tenants, keys, shard=False):
    server = SchedServer(sched, capacity=capacity, slots=slots, shard=shard)
    for i, tid in enumerate(tenants):
        server.join(tid, key=keys[i], hp={"gamma": 0.8 + 0.4 * i / capacity})
    return server


def _requests(tenants, states, keys, start, stop):
    n_ten = len(tenants)
    return [ServeRequest(tenants[j % n_ten],
                         states[(j // n_ten) % states.shape[0], j % n_ten],
                         keys[j]) for j in range(start, stop)]


def phase_serve(capacity=256, slots=64, n=16, m=4, h=256, n_req=768,
                t_par=200, expect_kernel=True):
    sched = GLRCUCB(n, m, history=h, detector_stride=5, split_grid="auto")
    tenants = [f"job-{i}" for i in range(capacity)]
    tkeys = np.asarray(jax.random.split(jax.random.fold_in(KEY, 10),
                                        capacity))
    rounds = 8
    means = jax.random.uniform(jax.random.fold_in(KEY, 11), (capacity, n),
                               minval=0.15, maxval=0.9)
    states = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(KEY, 12), means[None], (rounds, capacity, n)),
        np.float32)
    rkeys = np.asarray(jax.random.split(jax.random.fold_in(KEY, 13), n_req))

    # -- single-tenant serve == offline simulator, bitwise ------------------
    server = SchedServer(sched, capacity=capacity, slots=slots)
    report_kernel(f"serve step (slots={slots})", server._step, expect_kernel)
    env = random_piecewise_env(KEY, n, t_par, 3)
    off = simulate_aoi_regret(sched, env, KEY, t_par, collect_curve=False,
                              return_state=True)
    pkeys, pstates = offline_round_stream(env, KEY, t_par)
    pkeys, pstates = np.asarray(pkeys), np.asarray(pstates, np.float32)
    server.join("parity", key=KEY)
    for t in range(t_par):
        server.serve([ServeRequest("parity", pstates[t], pkeys[t])])
    prow = server.tenant_state("parity")
    check_bits((off["final_sched_state"], off["aoi_pi"]),
               (prow.sched_state, prow.aoi),
               f"single-tenant serve == offline simulator bitwise ({t_par} rounds)")
    server.leave("parity")

    # -- 256 tenants, warm(), a churned serve_stream, no compiles after ----
    for i, tid in enumerate(tenants):
        server.join(tid, key=tkeys[i], hp={"gamma": 0.8 + 0.4 * i / capacity})
    server.warm()
    misses0, compiles0 = sweep_cache_stats()["misses"], server.compiles
    churn = {"done": 0}

    def source():
        for j, rq in enumerate(_requests(tenants, states, rkeys, 0, n_req)):
            if j == n_req // 2:
                server.leave(tenants[0])
                server.join(tenants[0], key=tkeys[0])
                churn["done"] += 1
            yield rq

    out = dict(server.serve_stream(source()))
    check(churn["done"] == 1 and len(out) == n_req,
          f"serve_stream answered {len(out)} requests across one churn")
    asg = np.stack([out[i] for i in range(n_req)])
    check(asg.shape == (n_req, m) and asg.min() >= 0 and asg.max() < n
          and all(len(set(row)) == m for row in asg),
          f"every assignment is {m} distinct channels in [0, {n})")
    check(sweep_cache_stats()["misses"] == misses0
          and server.compiles == compiles0,
          "no compiles after warm()")

    # -- save() / restore() mid-stream resumes bitwise (donation on) -------
    half = n_req // 2
    reqs = _requests(tenants, states, rkeys, 0, n_req)
    full = [a for _, a in _pool_server(sched, capacity, slots, tenants,
                                       tkeys).serve_stream(iter(reqs))]
    first_srv = _pool_server(sched, capacity, slots, tenants, tkeys)
    first = [a for _, a in first_srv.serve_stream(iter(reqs[:half]))]
    with tempfile.TemporaryDirectory() as ckpt:
        first_srv.save(ckpt, step=half)
        resumed_srv = SchedServer(sched, capacity=capacity, slots=slots)
        resumed_srv.restore(ckpt)
        second = [a for _, a in resumed_srv.serve_stream(iter(reqs[half:]))]
    check_bits(full, first + second,
               f"save()/restore() at request {half} resumes bitwise")
    return server.compile_s


# ---------------------------------------------------------------------------
# phase 4: the population trainer
# ---------------------------------------------------------------------------

def phase_trainer(n=100_000, m=64, nch=16, d=16, nex=8, bsz=4, rounds=4,
                  pn=20, pnch=30, pr=6, expect_kernel=True):
    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    rng = np.random.default_rng(0)
    cx = jnp.asarray(rng.normal(size=(n, nex, d)).astype(np.float32))
    cy = jnp.asarray(rng.normal(size=(n, nex)).astype(np.float32))
    keys = jax.random.split(jax.random.fold_in(KEY, 20), rounds)
    compile_s = 0.0
    for agg in ("mean", "coordinate_median"):
        tr = SparseAsyncFLTrainer(
            SparseFLConfig(n_clients=n, n_sched=m, n_channels=nch,
                           batch_size=bsz, local_epochs=1, staleness_cap=8),
            GLRCUCB(nch, m, history=128),
            make_stationary(jnp.linspace(0.9, 0.3, nch)), loss_fn,
            availability=MarkovChurn(p_drop=0.05, p_rejoin=0.5),
            aggregator=make_aggregator(agg))
        params0 = {"w": jnp.zeros((d,), jnp.float32)}
        t0 = time.perf_counter()
        st, mets = tr.run(tr.init(params0, KEY), cx, cy, keys)
        jax.block_until_ready(st.params)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        st, mets = tr.run(tr.init(params0, KEY), cx, cy, keys)
        jax.block_until_ready(st.params)
        second = time.perf_counter() - t0
        compile_s += max(first - second, 0.0)
        print(f"[set-up, not a benchmark number] trainer {agg}: first run "
              f"(compile + {rounds} rounds) {first} s, second run {second} s",
              flush=True)
        check(bool(jnp.isfinite(tree_flatten_concat(st.params)).all())
              and bool(jnp.isfinite(mets["local_loss"]).all())
              and mets["local_loss"].shape == (rounds,),
              f"sparse trainer N={n} M={m} {agg}: {rounds} rounds finite")
        report_kernel(
            f"sparse trainer scan ({agg})",
            type(tr)._run_plain.lower(tr, tr.init(params0, KEY), cx, cy,
                                      keys, tr.env,
                                      gather_backends(cx, cy)).compile(),
            expect_kernel)

    # -- dense == sparse at M = N (the dense trainer's donated path) -------
    prng = np.random.default_rng(7)
    pcx = jnp.asarray(prng.normal(size=(pn, 16, 8)).astype(np.float32))
    pcy = jnp.asarray(prng.normal(size=(pn, 16)).astype(np.float32))

    def ploss(p, x, y):
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    pp0 = {"w": jnp.zeros((8,), jnp.float32), "b": jnp.zeros((), jnp.float32)}
    psched = GLRCUCB(pnch, pn, history=64)
    proc = make_scenario("piecewise", n_channels=pnch, horizon=pr,
                         n_breakpoints=2)
    rk = jax.random.fold_in(KEY, 41)
    dense = AsyncFLTrainer(
        AsyncFLConfig(n_clients=pn, n_channels=pnch, local_epochs=2,
                      staleness_cap=3, max_update_norm=50.0),
        psched, proc, ploss, realize_key=rk)
    sparse = SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=pn, n_sched=pn, n_channels=pnch,
                       batch_size=3, local_epochs=2, staleness_cap=3,
                       max_update_norm=50.0),
        psched, proc, ploss, realize_key=rk)
    pkeys = jax.random.split(jax.random.fold_in(KEY, 42), pr)
    ids = jnp.arange(pn, dtype=jnp.int32)
    bxs, bys = [], []
    for r_ in range(pr):   # the dense side replays the sparse on-device draw
        idx = client_batch_indices(jax.random.fold_in(pkeys[r_], _DATA_TAG),
                                   ids, 16, 2, 3)
        bx_, by_ = gather_client_batches(pcx, pcy, ids, idx)
        bxs.append(bx_)
        bys.append(by_)
    bx, by = jnp.stack(bxs), jnp.stack(bys)
    ds, dm = dense.run(dense.init(pp0, KEY), bx, by, pkeys)
    ss, sm = sparse.run(sparse.init(pp0, KEY), pcx, pcy, pkeys)
    shared = ("params", "buffers", "has_update", "last_success", "aoi",
              "staleness", "contrib", "zeta", "contrib_buf", "sched_state",
              "env_state")
    check_bits(({f: getattr(ds, f) for f in shared}, dm),
               ({f: getattr(ss, f) for f in shared}, {k: sm[k] for k in dm}),
               f"sparse == dense trainer bitwise at M=N={pn} ({pr} rounds)")
    run_fn = (type(dense)._run_plain if jax.default_backend() == "cpu"
              else type(dense)._run_donated)
    report_kernel("dense trainer scan",
                  run_fn.lower(dense, dense.init(pp0, KEY), bx, by, pkeys,
                               dense.env).compile(),
                  expect_kernel)
    return compile_s


# ---------------------------------------------------------------------------
# phase 5: batched regret sweeps
# ---------------------------------------------------------------------------

def _scenario_cases(sched, n, horizon, seeds, tag):
    scenarios = [
        ("ge", GilbertElliottProcess(n, horizon, p_gb=0.05)),
        ("mobility", MobilityDriftProcess(n, horizon, amplitude=0.3)),
        ("shadowing", ShadowingProcess(n, horizon, rho=0.92)),
        ("jam", JammingOverlay(base=PiecewiseProcess(n, horizon, 3),
                               strength=0.8)),
    ]
    return [SweepCase(f"{name}/s{i}", sched, proc,
                      jax.random.fold_in(KEY, tag + 37 * j + i), horizon)
            for j, (name, proc) in enumerate(scenarios)
            for i in range(seeds)]


def phase_sweep(n=6, m=2, h=1024, horizon=2000, seeds=2, expect_kernel=True):
    sched = GLRCUCB(n, m, history=h, detector_stride=5,
                    split_grid="geometric")
    cases = _scenario_cases(sched, n, horizon, seeds, tag=300)
    results, report = sweep(cases, collect_curve=False)
    check(len(report) == 1, f"{len(cases)} cases over 4 families = 1 bucket")
    check(all(np.isfinite(float(results[c.name]["final_regret"]))
              for c in cases), "every case's regret is finite")

    envs = realize_processes([c.env for c in cases],
                             jnp.stack([scenario_realize_key(c.key)
                                        for c in cases]))
    hparams = stack_params([c.scheduler for c in cases])
    report_kernel(
        f"sweep bucket (GLR-CUCB H={h} geometric, {len(cases)} cases)",
        simulate_aoi_regret_batch.lower(
            sched, envs, jnp.stack([c.key for c in cases]), horizon,
            collect_curve=False, hparams=hparams,
            hp_axis=None if hparams is None else 0).compile(),
        expect_kernel)

    c0 = cases[0]
    one, _ = sweep([SweepCase("one", c0.scheduler, c0.env, c0.key,
                              horizon)], collect_curve=False)
    serial = simulate_aoi_regret(sched, c0.env, c0.key, horizon,
                                 collect_curve=False)
    check_bits(serial, one["one"], "sweep batch-of-1 == serial bitwise")
    return sum(b.compile_s for b in report)


# ---------------------------------------------------------------------------
# four chips: sharded serving and sweeps vs their unsharded twins
# ---------------------------------------------------------------------------

def phase_four_chips(tenants=10_000, slots=64, n=16, m=4, h=64,
                     sweep_n=6, sweep_h=1024, horizon=2000,
                     expect_kernel=True):
    mesh = sweep_mesh()
    print(f"  mesh: {mesh.devices.size} devices", flush=True)
    sched = GLRCUCB(n, m, history=h, detector_stride=5, split_grid="auto")
    ids = list(range(tenants))
    tkeys = np.asarray(jax.random.split(jax.random.fold_in(KEY, 50), tenants))
    big = SchedServer(sched, capacity=tenants, slots=slots, shard=True,
                      mesh=mesh)
    twin = SchedServer(sched, capacity=tenants, slots=slots)
    report_kernel(f"sharded serve step ({tenants} tenants)", big._step,
                  expect_kernel)
    for i in ids:
        big.join(i, key=tkeys[i])
        twin.join(i, key=tkeys[i])
    states = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(KEY, 51), 0.6, (2, tenants, n)), np.float32)
    rkeys = np.asarray(jax.random.split(jax.random.fold_in(KEY, 52),
                                        4 * slots))
    reqs = _requests(ids, states, rkeys, 0, 4 * slots)
    want = twin.serve(reqs)
    got = big.serve(reqs)
    check_bits((got, jax.tree_util.tree_map(
                   lambda x: np.asarray(x)[:twin.rows], big._state)),
               (want, twin._state),
               f"sharded server ({tenants} tenants, rows={big.rows}) == "
               "unsharded twin bitwise (assignments and every state leaf)")

    ssched = GLRCUCB(sweep_n, 2, history=sweep_h, detector_stride=5,
                     split_grid="geometric")
    cases = _scenario_cases(ssched, sweep_n, horizon, seeds=1, tag=600)[:3]
    sharded, srep = sweep(cases, collect_curve=False, shard=True, mesh=mesh)
    plain, _ = sweep(cases, collect_curve=False)
    check(all(r.sharded for r in srep), "the sharded sweep ran sharded")
    check_bits(sharded, plain,
               f"sweep(shard=True) == sweep() bitwise ({len(cases)} cases, "
               f"padded to the {mesh.devices.size}-device mesh)")
    return big.compile_s + twin.compile_s + sum(r.compile_s for r in srep)


# ---------------------------------------------------------------------------

def run_phase(name, fn, **kw):
    print(f"[phase] {name}", flush=True)
    t0 = time.perf_counter()
    compile_s = fn(**kw)
    setup_times(name, time.perf_counter() - t0, compile_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serving and sweep paths "
                         "(needs four chips)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script checks the chip path only", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.four_chips:
        run_phase("four_chips", phase_four_chips)
    else:
        run_phase("kernels", phase_kernels)
        run_phase("serve", phase_serve)
        run_phase("trainer", phase_trainer)
        run_phase("sweep", phase_sweep)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
