"""Benchmark harness — one function per paper table/figure.

  fig2a_regret       AoI regret: GLR-CUCB / M-Exp3 (+AA) vs random and the
                     related-work baselines (channel-aware, Lyapunov) (Fig. 2a)
  fig2b_breakpoints  GLR-CUCB regret vs number of breakpoints C_T   (Fig. 2b)
  fig2c_scale        M-Exp3 regret vs |C(N, M)|                     (Fig. 2c)
  fig3_accuracy      FL test accuracy, mean±std over seeds, both regimes,
                     paper policies vs related-work baselines        (Fig. 3)
  fig4_fairness      cumulative AoI variance (fairness), mean±std    (Fig. 4)
  fl_batch           serial-vs-batched speedup of the vmapped FL engine
                     (simulate_fl_batch) + batch-of-1 bitwise parity
  fl_substrate       sparse event-driven FL substrate (repro.fl.sparse) at
                     population scale: FL rounds/sec at N=100,000 clients /
                     M=64 slots under availability churn, plus the
                     dense-vs-sparse bitwise parity bit at the paper's FL
                     scale (M = N: identity selection must reproduce the
                     dense AsyncFLTrainer exactly)
  glr_detector       per-step microbench of the GLR-CUCB detector at H=1024:
                     streaming carried-prefix state vs the legacy cumsum
                     recompute (+ the geometric split grid), restart-round
                     parity, and streaming-vs-recompute bitwise parity on
                     the fig2a workloads
  hp_grid            16-point gamma x delta GLR-CUCB tuning grid (H=1024,
                     streaming detector) as ONE vmapped program vs the
                     per-point sweep (each point a fresh config = a fresh
                     compile) + grid-of-1 parity
  scenario_suite     12-scenario x 8-seed grid across 4 channel-scenario
                     families (Gilbert-Elliott fading, mobility drift,
                     SNR shadowing, jamming overlay) as ONE sweep bucket
                     vs the per-case serial loop + grid-of-1 parity
                     (``--scenarios`` runs only the two scenario suites)
  scenario_suite_glr the same 12-scenario grid scheduled by GLR-CUCB
                     (streaming detector) — the piecewise-regime policy the
                     recompute detector kept out of batched sweeps
  chaos_suite        closed-loop adversaries + fault injection: the
                     reactive-jammer/congestion grid as ONE sweep bucket
                     (+ batch-of-1 parity bit), the reactive-vs-matched-
                     open-loop scheduling shift (GLR-CUCB restarts AND
                     regret must differ), and the FL degradation bits —
                     quarantined trainer finite under 20% NaN corruption
                     while the unguarded baseline diverges
  serve_suite        multi-tenant scheduler-as-a-service (repro.sim.serve):
                     256 concurrent tenants from ONE compiled step — p50/p99
                     decision latency + decisions/sec under Poisson arrivals
                     with tenant churn (leave/re-join, zero recompiles) vs a
                     per-tenant serial-dispatch baseline, plus the
                     single-tenant serve == offline-simulator parity bit
  kernels            Pallas kernel wall-time vs jnp oracle (interpret mode)
  roofline           dry-run roofline table (reads experiments/dryrun/*.json)

All regret figures run on the batched `repro.sim` engine: cases are grouped
into vmappable buckets and each bucket executes as ONE XLA program (vmap
over seeds/envs).  fig2c additionally measures the serial per-seed baseline
in the same process and reports the batched speedup.  The FL figures run on
the batched FL engine (``simulate_fl_batch``): all seeds of one policy
train as ONE vmapped scan program per checkpoint segment — error bars cost
one executable, not S runs.

Output: ``name,us_per_call,derived`` CSV on stdout plus ``BENCH_sim.json``
(per-figure wall time, fig2c + fl_batch + hp_grid speedups, batch-of-1 /
grid-of-1 parity bits, sweep executable-cache hit/miss counts) at the repo
root, so engine performance is tracked across PRs.

The harness enables JAX's *persistent* compilation cache
(``repro.utils.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache/`` at the repo root) so back-to-back benchmark runs skip
warm compiles entirely; ``--no-persistent-cache`` turns it off for
clean-compile measurements.

``--quick`` shrinks every figure (T=500, few seeds, short FL run) for CI
smoke coverage.
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import time

import jax

from repro.utils.compile_cache import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# must run before the FIRST compile of the process (JAX latches the cache
# decision at first use) — hence module-import time, ahead of the
# module-level ``PRNGKey``
PERSISTENT_CACHE = ("--no-persistent-cache" not in sys.argv
                    and enable_compile_cache())

import jax.numpy as jnp
import numpy as np

from repro.core.bandits import (
    AoIAware, ChannelAwareAsync, GLRCUCB, LyapunovSched, MExp3,
    RandomScheduler, RoundRobinScheduler)
from repro.core.channels import (
    GilbertElliottProcess,
    JammingOverlay,
    MobilityDriftProcess,
    PiecewiseProcess,
    ShadowingProcess,
    make_stationary,
    random_adversarial_env,
    random_piecewise_env,
    registered_scenarios,
    stack_envs,
)
from repro.core.regret import (
    regret_growth_exponent,
    simulate_aoi_regret,
    sublinearity_index,
)
from repro.sim import (
    SchedServer,
    ServeRequest,
    SweepCase,
    offline_round_stream,
    simulate_aoi_regret_batch,
    simulate_fl_batch,
    sweep,
    sweep_cache_stats,
)

KEY = jax.random.PRNGKey(42)
ROWS = []
BENCH = {"figures": {}}          # -> BENCH_sim.json
QUICK = False


def row(name: str, us_per_call: float, derived):
    ROWS.append(f"{name},{us_per_call:.1f},{derived}")
    print(ROWS[-1], flush=True)


def _timed(fn, *args, reps: int = 1, **kw):
    jax.block_until_ready(fn(*args, **kw))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
        jax.block_until_ready(out)          # block every rep: measure execution,
    return out, (time.perf_counter() - t0) / reps * 1e6   # not dispatch


def _figure(fn):
    """Run one figure, recording its wall time and the per-phase sweep
    executable-cache traffic into BENCH."""
    t0 = time.perf_counter()
    s0 = sweep_cache_stats()
    fn()
    s1 = sweep_cache_stats()
    BENCH["figures"][fn.__name__] = round(time.perf_counter() - t0, 3)
    delta = {k: s1[k] - s0[k] for k in s1}
    if any(delta.values()):
        BENCH.setdefault("sweep_exec_cache_phases", {})[fn.__name__] = delta


def _horizon() -> int:
    return 500 if QUICK else 20000


# ---------------------------------------------------------------------------
# Fig. 2a — regret under the paper's exact setup (T=20000, M=2, N=5, C_T=5)
# ---------------------------------------------------------------------------

def fig2a_regret():
    T, N, M = _horizon(), 5, 2
    env = random_piecewise_env(KEY, N, T, 5)
    aenv = random_adversarial_env(KEY, N, T, flip_prob=0.002)
    scheds = [
        ("random", RandomScheduler(N, M)),
        ("round-robin", RoundRobinScheduler(N, M)),          # ablation: fair, no learning
        ("channel-aware", ChannelAwareAsync(N, M)),          # Hu et al.-style baseline
        ("lyapunov", LyapunovSched(N, M)),                   # Perazzone et al.-style
        ("glr-cucb", GLRCUCB(N, M, history=1024, detector_stride=5)),
        ("cucb-static", GLRCUCB(N, M, history=1024,          # ablation: detector off
                                detector_stride=10**9)),
        ("aa-glr-cucb", AoIAware(GLRCUCB(N, M, history=1024, detector_stride=5))),
        ("m-exp3", MExp3(N, M, gamma=0.5)),
        ("aa-m-exp3", AoIAware(MExp3(N, M, gamma=0.5))),
    ]
    adv_scheds = [
        # adversarial: M-Exp3 with the Exp3.S weight-sharing term (the family
        # the paper derives from [34]; plain Exp3 cannot track mid-stream shifts)
        ("random", RandomScheduler(N, M)),
        ("channel-aware", ChannelAwareAsync(N, M)),
        ("lyapunov", LyapunovSched(N, M)),
        ("m-exp3", MExp3(N, M, gamma=0.5, share_alpha=1e-3)),
        ("aa-m-exp3", AoIAware(MExp3(N, M, gamma=0.5, share_alpha=1e-3))),
        ("glr-cucb", GLRCUCB(N, M, history=1024, detector_stride=5)),
    ]
    cases = (
        [SweepCase(f"piecewise/{n}", s, env, KEY, T) for n, s in scheds]
        + [SweepCase(f"adversarial/{n}", s, aenv, KEY, T) for n, s in adv_scheds]
    )
    results, report = sweep(cases, block=True)
    us = {n: b.wall_s / b.batch * 1e6 for b in report for n in b.names}
    for name, _ in scheds:
        out = results[f"piecewise/{name}"]
        sub = float(sublinearity_index(out["regret"]))
        expo = regret_growth_exponent(out["regret"])
        row(f"fig2a/piecewise/{name}", us[f"piecewise/{name}"],
            f"regret={float(out['final_regret']):.0f};sublin={sub:.3f};"
            f"growth_exp={expo:.2f}")
    for name, _ in adv_scheds:
        out = results[f"adversarial/{name}"]
        row(f"fig2a/adversarial/{name}", us[f"adversarial/{name}"],
            f"regret={float(out['final_regret']):.0f}")


# ---------------------------------------------------------------------------
# Fig. 2b — impact of breakpoints on GLR-CUCB
# ---------------------------------------------------------------------------

def fig2b_breakpoints():
    """Controlled: segment means are rotations of one fixed profile, so the
    ONLY thing that varies with C_T is how often the best set moves."""
    from repro.core.channels import make_piecewise
    T, N, M = _horizon(), 5, 2
    profile = jnp.array([0.9, 0.7, 0.5, 0.3, 0.1])
    s = GLRCUCB(N, M, history=1024, detector_stride=5)
    cases = []
    for c_t in [0, 3, 6, 9, 12]:
        means = jnp.stack([jnp.roll(profile, sh) for sh in range(c_t + 1)])
        brk = jnp.linspace(0, T, c_t + 2)[1:-1].astype(jnp.int32)
        cases.append(SweepCase(f"C_T={c_t}", s, make_piecewise(means, brk), KEY, T))
    results, report = sweep(cases, block=True)
    us = {n: b.wall_s / b.batch * 1e6 for b in report for n in b.names}
    for c in cases:
        row(f"fig2b/glr-cucb/{c.name}", us[c.name],
            f"regret={float(results[c.name]['final_regret']):.0f}")


# ---------------------------------------------------------------------------
# Fig. 2c — M-Exp3 vs super-arm count |C(N, M)|, averaged over env seeds.
# The multi-seed sweep is the engine's showcase: per N, all seeds run as one
# vmapped program.  The serial per-seed baseline is measured in the same
# process (same compiled serial path the old harness used) for BENCH_sim.
# ---------------------------------------------------------------------------

def fig2c_scale():
    T, M = _horizon(), 2
    seeds = 1 if QUICK else 24    # large enough that the batched win (~6x)
                                  # clears the 5x tracking floor with margin
    serial_s = batched_s = 0.0
    for n in [4, 5, 6, 7]:
        s = MExp3(n, M, gamma=0.5)
        envs = [
            random_adversarial_env(
                jax.random.fold_in(KEY, 100 * n + i), n, T, flip_prob=0.002)
            for i in range(seeds)
        ]
        # --- serial baseline: one compiled program, executed per seed -------
        jax.block_until_ready(simulate_aoi_regret(s, envs[0], KEY, T))
        t0 = time.perf_counter()
        serial_out = [simulate_aoi_regret(s, e, KEY, T) for e in envs]
        jax.block_until_ready(serial_out)
        serial_s += time.perf_counter() - t0
        # --- batched engine: all seeds in one vmapped program ---------------
        stacked = stack_envs(envs)
        keys = jnp.stack([KEY] * seeds)
        jax.block_until_ready(simulate_aoi_regret_batch(s, stacked, keys, T))
        t0 = time.perf_counter()
        out = simulate_aoi_regret_batch(s, stacked, keys, T)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        batched_s += dt

        vals = np.asarray(out["final_regret"])
        serial_vals = np.asarray([o["final_regret"] for o in serial_out])
        if not np.array_equal(vals, serial_vals):
            row(f"fig2c/PARITY-MISMATCH/N={n}", 0.0,
                f"batched={vals};serial={serial_vals}")
        row(f"fig2c/m-exp3/N={n}|C|={s.n_super_arms}", dt / seeds * 1e6,
            f"regret={vals.mean():.0f}±{vals.std():.0f}")

    BENCH["fig2c_speedup"] = {
        "seeds_per_n": seeds,
        "serial_s": round(serial_s, 3),
        "batched_s": round(batched_s, 3),
        "speedup": round(serial_s / max(batched_s, 1e-9), 2),
    }
    # us_per_call column carries 0.0: this row is an aggregate (the real
    # numbers live in the derived field and in BENCH_sim.json)
    row("fig2c/engine-speedup", 0.0,
        f"serial_s={serial_s:.2f};batched_s={batched_s:.2f};"
        f"speedup={serial_s / max(batched_s, 1e-9):.2f}x")


# ---------------------------------------------------------------------------
# batch-of-1 parity — the engine must reproduce the serial path bitwise
# ---------------------------------------------------------------------------

def batch1_parity():
    T, N, M = min(_horizon(), 2000), 5, 2
    env = random_piecewise_env(KEY, N, T, 3)
    s = GLRCUCB(N, M, history=256, detector_stride=5)
    serial = simulate_aoi_regret(s, env, KEY, T)
    batched = simulate_aoi_regret_batch(
        s, stack_envs([env]), jnp.stack([KEY]), T)
    match = all(
        np.array_equal(np.asarray(serial[k]), np.asarray(batched[k][0]))
        for k in serial
    )
    BENCH["batch1_bitwise_match"] = bool(match)
    row("sim/batch1-parity", 0.0, f"bitwise_match={match}")


# ---------------------------------------------------------------------------
# glr_detector — streaming vs recompute GLR detector, per-step, at H=1024
# ---------------------------------------------------------------------------

def glr_detector():
    """Per-step microbench of the GLR-CUCB detector hot path at H=1024.

    Drives ``GLRCUCB.update`` through a policy-free rotating schedule (the
    reward stream is identical for every implementation) long enough for
    the ring buffer to wrap, and times three detector configs:

      recompute   legacy path: O(N*H) one-hot append every step + cumsum
                  prefix recompute per detection round (``ops.glr_scan``)
      streaming   carried prefix-sum state: O(N) scatter append + the dense
                  split grid evaluated on the M scheduled rows only
      geometric   streaming + the O(log H) power-of-two split grid

    Restart-round sequences must be identical between recompute and
    streaming (integer prefixes => bitwise-equal statistics); the geometric
    grid trades a bounded detection delay for the cheaper test, so its
    restart agreement is recorded but not gated.  Also re-checks full
    ``simulate_aoi_regret`` bitwise parity on the fig2a piecewise and
    adversarial workloads (same env constructions, same GLR config)."""
    h, n, m = 1024, 8, 2
    t_steps = 600 if QUICK else 6000          # > H*N/M: the ring wraps
    env = random_piecewise_env(jax.random.fold_in(KEY, 55), n, t_steps, 4)

    def driver(sched):
        @jax.jit
        def run():
            def step(state, inp):
                t, k = inp
                ch = (t + jnp.arange(m)) % n
                rewards = env.sample(t, k)[ch]
                state = sched.update(state, t, ch, rewards,
                                     jnp.zeros((), jnp.int32))
                return state, state.restarts
            return jax.lax.scan(step, sched.init(KEY),
                                (jnp.arange(t_steps),
                                 jax.random.split(KEY, t_steps)))
        return run

    runs = {}
    for label, cfg in [
        ("recompute", GLRCUCB(n, m, history=h, detector_stride=5,
                              detector_impl="recompute")),
        ("streaming", GLRCUCB(n, m, history=h, detector_stride=5)),
        ("geometric", GLRCUCB(n, m, history=h, detector_stride=5,
                              split_grid="geometric")),
    ]:
        (state, trace), us = _timed(driver(cfg), reps=1 if QUICK else 3)
        runs[label] = (np.asarray(trace), us / t_steps)
        row(f"glr_detector/{label}", us / t_steps,
            f"H={h};steps={t_steps};restarts={int(state.restarts)}")

    restart_parity = bool(
        np.array_equal(runs["recompute"][0], runs["streaming"][0]))
    geo_match = bool(
        np.array_equal(runs["recompute"][0], runs["geometric"][0]))

    # --- committed-workload parity: the fig2a GLR config, end to end -------
    t_sim = _horizon()
    workload_parity = {}
    for wname, wenv in [
        ("piecewise", random_piecewise_env(KEY, 5, t_sim, 5)),
        ("adversarial", random_adversarial_env(KEY, 5, t_sim,
                                               flip_prob=0.002)),
    ]:
        mk = lambda impl: GLRCUCB(5, 2, history=1024, detector_stride=5,
                                  detector_impl=impl)
        a = simulate_aoi_regret(mk("recompute"), wenv, KEY, t_sim)
        b = simulate_aoi_regret(mk("streaming"), wenv, KEY, t_sim)
        workload_parity[wname] = bool(all(
            np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a))

    speedup = runs["recompute"][1] / runs["streaming"][1]
    geo_speedup = runs["recompute"][1] / runs["geometric"][1]
    BENCH["glr_detector"] = {
        "history": h,
        "channels": n,
        "steps": t_steps,
        "detector_stride": 5,
        "recompute_us_per_step": round(runs["recompute"][1], 2),
        "streaming_us_per_step": round(runs["streaming"][1], 2),
        "geometric_us_per_step": round(runs["geometric"][1], 2),
        "speedup": round(speedup, 2),
        "geometric_speedup": round(geo_speedup, 2),
        "restart_parity": restart_parity,
        "geometric_restart_match": geo_match,
        "workload_bitwise": workload_parity,
    }
    row("glr_detector/summary", 0.0,
        f"speedup={speedup:.2f}x;geometric={geo_speedup:.2f}x;"
        f"restart_parity={restart_parity};workloads={workload_parity}")


# ---------------------------------------------------------------------------
# hp_grid — hyper-parameter-vmapped tuning sweep vs the per-point sweep
# ---------------------------------------------------------------------------

def hp_grid():
    """16-point gamma x delta GLR-CUCB grid.  Per-point, every grid value is
    a new frozen config = a new trace + compile + dispatch; vmapped, the
    traced scalars ride the engine's hp axis and the whole grid is ONE
    compiled program (one per policy *family*).  Also re-checks grid-of-1
    bitwise parity against the per-value serial run on every run.

    Tunes the full-window detector (history=1024, the fig2a config).  This
    was infeasible before the streaming detector: the recompute path's
    per-step O(N*H) append + cumsum made the (G, N, H) batched scan
    CPU-memory-bound at H=1024 (the grid had to retreat to H=256).  The
    carried prefix state keeps the per-step work O(N), so the vmapped grid
    wins on execution *and* on the 16->1 compile amortization."""
    T, N, M = _horizon(), 5, 2
    env = random_piecewise_env(jax.random.fold_in(KEY, 77), N, T, 5)
    base = GLRCUCB(N, M, history=1024, detector_stride=5)
    gammas = [0.5, 0.75, 1.0, 1.25]
    deltas = [1e-4, 1e-3, 1e-2, 1e-1]
    grid = [base.replace_traced(gamma=g, delta=d) for g in gammas for d in deltas]

    # --- per-point sweep: the pre-hp-axis cost model (compile per point) ----
    t0 = time.perf_counter()
    serial_out = [simulate_aoi_regret(s, env, KEY, T, collect_curve=False)
                  for s in grid]
    jax.block_until_ready(serial_out)
    serial_s = time.perf_counter() - t0

    # --- vmapped grid through sweep(): ONE bucket, ONE compile --------------
    stats0 = sweep_cache_stats()
    cases = [SweepCase(f"g{g}/d{d}", s, env, KEY, T)
             for s, (g, d) in zip(grid, [(g, d) for g in gammas for d in deltas])]
    t0 = time.perf_counter()
    results, report = sweep(cases, collect_curve=False, block=True)
    grid_s = time.perf_counter() - t0
    stats1 = sweep_cache_stats()
    compiles = stats1["misses"] - stats0["misses"]
    n_buckets = len(report)

    # vmapped grid must reproduce the per-point results bitwise
    grid_match = all(
        np.array_equal(np.asarray(serial_out[i]["final_regret"]),
                       np.asarray(results[c.name]["final_regret"]))
        for i, c in enumerate(cases))

    # --- grid-of-1 parity: hp fed as input vs baked-in constant -------------
    tuned = grid[5]
    hp1 = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], tuned.params())
    g1 = simulate_aoi_regret_batch(
        base, env, KEY, T, collect_curve=False,
        env_axis=None, key_axis=None, hparams=hp1, hp_axis=0)
    s1 = serial_out[5]
    grid1_match = all(
        np.array_equal(np.asarray(s1[k]), np.asarray(g1[k][0])) for k in s1)

    speedup = serial_s / max(grid_s, 1e-9)
    best = min(range(len(grid)),
               key=lambda i: float(serial_out[i]["final_regret"]))
    BENCH["hp_grid"] = {
        "history": base.history,
        "grid": len(grid),
        "gammas": gammas,
        "deltas": deltas,
        "serial_s": round(serial_s, 3),
        "grid_s": round(grid_s, 3),
        "speedup": round(speedup, 2),
        "buckets": n_buckets,
        "compile_count": compiles,
        "grid_vs_serial_bitwise": bool(grid_match),
        "grid1_bitwise_match": bool(grid1_match),
    }
    row("sim/hp-grid1-parity", 0.0, f"bitwise_match={grid1_match}")
    row("hp_grid/glr-cucb/gamma-x-delta", grid_s / len(grid) * 1e6,
        f"grid={len(grid)};buckets={n_buckets};compiles={compiles};"
        f"serial_s={serial_s:.2f};grid_s={grid_s:.2f};speedup={speedup:.2f}x;"
        f"best=gamma{gammas[best // len(deltas)]}/delta{deltas[best % len(deltas)]}")


# ---------------------------------------------------------------------------
# scenario_suite — mixed-family channel-scenario grid through the registry
# ---------------------------------------------------------------------------

def _scenario_suite_impl(record_key, s):
    """Shared body of the two scenario suites: 12 scenarios x S seeds across
    the four table-form families, ONE sweep bucket vs the per-case serial
    loop, grid-vs-serial + grid-of-1 bitwise parity re-checked per run."""
    T = 300 if QUICK else 2000
    seeds = 2 if QUICK else 8
    n = s.n_channels
    scenarios = (
        [(f"ge/{v}", GilbertElliottProcess(n, T, p_gb=v))
         for v in (0.02, 0.05, 0.15)]
        + [(f"mobility/{v}", MobilityDriftProcess(n, T, amplitude=v))
           for v in (0.15, 0.3, 0.45)]
        + [(f"shadowing/{v}", ShadowingProcess(n, T, rho=v))
           for v in (0.85, 0.92, 0.97)]
        + [(f"jam/{v}", JammingOverlay(base=PiecewiseProcess(n, T, 3),
                                       strength=v))
           for v in (0.5, 0.8, 1.0)]
    )
    families = sorted({p.FAMILY for _, p in scenarios})
    cases = [
        SweepCase(f"{name}/s{i}", s, p,
                  jax.random.fold_in(KEY, 900 + 37 * j + i), T)
        for j, (name, p) in enumerate(scenarios)
        for i in range(seeds)
    ]

    # warm both paths (fig2c/fl_batch methodology): the serial sim compile,
    # the per-family grid-of-1 realizers (the realizer fn is cached per
    # family but jit re-traces per key-batch shape, so warm one realize()
    # per family — not just the first case), and the sweep bucket's AOT
    # executable — the timed region then measures execution, not compiles.
    # The warm-up sweep also yields the compile accounting.
    for _, p in scenarios[::3]:              # first scenario of each family
        jax.block_until_ready(p.realize(KEY).table)
    simulate_aoi_regret(s, cases[0].env, cases[0].key, T, collect_curve=False)
    stats0 = sweep_cache_stats()
    _, report = sweep(cases, collect_curve=False, block=True)
    compiles = sweep_cache_stats()["misses"] - stats0["misses"]
    buckets = len(report)

    # --- timed: serial per-case loop vs the ONE warmed bucket ---------------
    # best-of-3 like fl_batch: totals are ~seconds on a 2-core box and a
    # single shot is noise-dominated
    serial_s = grid_s = float("inf")
    serial_out = results = None
    for _ in range(1 if QUICK else 3):
        t0 = time.perf_counter()
        serial_out = {c.name: simulate_aoi_regret(s, c.env, c.key, T,
                                                  collect_curve=False)
                      for c in cases}
        jax.block_until_ready(list(serial_out.values()))
        serial_s = min(serial_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        results, report2 = sweep(cases, collect_curve=False, block=True)
        grid_s = min(grid_s, time.perf_counter() - t0)
        assert all(b.cache_hit for b in report2), "warmed bucket must cache-hit"

    grid_match = all(
        np.array_equal(np.asarray(serial_out[c.name]["final_regret"]),
                       np.asarray(results[c.name]["final_regret"]))
        for c in cases)

    # --- grid-of-1: a single-case sweep must equal the serial run bitwise ---
    c0 = cases[0]
    one, _ = sweep([SweepCase("one", c0.scheduler, c0.env, c0.key, T)],
                   collect_curve=False, block=False)
    grid1_match = all(
        np.array_equal(np.asarray(serial_out[c0.name][k]),
                       np.asarray(one["one"][k]))
        for k in serial_out[c0.name])

    speedup = serial_s / max(grid_s, 1e-9)
    BENCH[record_key] = {
        "policy": s.name,
        "scenarios": len(scenarios),
        "families": families,
        "families_registered": len(registered_scenarios()),
        "seeds": seeds,
        "horizon": T,
        "cases": len(cases),
        "serial_s": round(serial_s, 3),
        "grid_s": round(grid_s, 3),
        "speedup": round(speedup, 2),
        "buckets": buckets,
        "compile_count": compiles,
        "grid_vs_serial_bitwise": bool(grid_match),
        "grid1_bitwise_match": bool(grid1_match),
    }
    row(f"sim/{record_key}-grid1-parity", 0.0, f"bitwise_match={grid1_match}")
    row(f"{record_key}/{s.name}/4-families", grid_s / len(cases) * 1e6,
        f"scenarios={len(scenarios)};families={len(families)};"
        f"cases={len(cases)};buckets={buckets};compiles={compiles};"
        f"serial_s={serial_s:.2f};grid_s={grid_s:.2f};speedup={speedup:.2f}x")
    for j, (name, _) in enumerate(scenarios):
        vals = np.asarray([results[f"{name}/s{i}"]["final_regret"]
                           for i in range(seeds)])
        row(f"{record_key}/{name}", 0.0,
            f"regret={vals.mean():.0f}±{vals.std():.0f}")


def scenario_suite():
    """12 scenarios x S seeds spanning FOUR table-form families — bursty
    Gilbert-Elliott fading, mobility drift, SNR-threshold shadowing and a
    jamming overlay on a piecewise base — bucketed by canonical form into
    ONE compiled simulation (the families merge; realization runs as one
    tiny vmapped program per family).  The serial baseline is the per-case
    ``simulate_aoi_regret`` loop over the same (process, key) cases, which
    computes identical environments by construction (shared realization-key
    derivation).  Re-checks grid-vs-serial and grid-of-1 bitwise parity on
    every run.

    The scheduler is M-Exp3 with the Exp3.S sharing term — the policy the
    paper prescribes when the non-stationarity has no detectable
    breakpoint structure, exactly these fading/drift/jamming regimes.  Its
    tiny super-arm ops also vectorize superbly, so the batched win GROWS
    with T (measured 4.5x at T=2000, 5.4x at T=4000 on 2-core CPU)."""
    _scenario_suite_impl("scenario_suite", MExp3(6, 2, gamma=0.5,
                                                 share_alpha=1e-3))


def scenario_suite_glr():
    """The identical 12-scenario grid scheduled by GLR-CUCB, which the
    recompute detector kept out of the batched benchmarks entirely.  The
    streaming detector cuts the batched suite's absolute wall-clock ~3x at
    H=1024 (5.5s -> 1.9s at 96 cases on 2-core CPU; H=512, which also
    exercises ring wraparound at T=2000, runs in ~1.6s) — but the
    batched-vs-serial *ratio* stays ~2.2x, not M-Exp3's ~4.5x: the serial
    streaming path is already fast, and the vmapped append is bound by
    batched scatters (per-channel ring writes), which XLA:CPU serializes.
    The gate therefore sits at >= 1.8x — it tracks that GLR-CUCB stays a
    first-class citizen of the batched sweeps, while the >= 3x detector
    win itself is gated per step by ``glr_detector``."""
    _scenario_suite_impl("scenario_suite_glr",
                         GLRCUCB(6, 2, history=512, detector_stride=5))


# ---------------------------------------------------------------------------
# Fig. 3 / Fig. 4 — FL accuracy + fairness under both regimes
# ---------------------------------------------------------------------------

def _skewed_piecewise(key, n, horizon, c_t, high=0.95, exp=4.0):
    """Good channels are RARE (means ~ u^exp) — the regime where scheduling
    matters; uniform channel pools let random scheduling coast."""
    from repro.core.channels import make_piecewise
    ks = jax.random.split(key, c_t + 1)
    means = jnp.stack(
        [0.03 + (high - 0.03) * jax.random.uniform(k, (n,)) ** exp for k in ks])
    brk = jnp.linspace(0, horizon, c_t + 2)[1:-1].astype(jnp.int32)
    return make_piecewise(means, brk)


def _make_problem(m, alpha, dim, noise, spc, hidden=96):
    from repro.data.dirichlet import dirichlet_partition
    from repro.data.synthetic import SyntheticClassification

    ds = SyntheticClassification(m * spc * 2, n_classes=10, dim=dim,
                                 noise=noise, seed=3)
    (trx, try_), (tex, tey) = ds.split(0.9)
    parts = dirichlet_partition(try_, m, alpha, seed=3, min_per_client=spc)
    cx = np.stack([trx[np.resize(p, spc)] for p in parts])
    cy = np.stack([try_[np.resize(p, spc)] for p in parts])
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    params = {"w1": jax.random.normal(k1, (dim, hidden)) * 0.1,
              "b1": jnp.zeros(hidden),
              "w2": jax.random.normal(k2, (hidden, 10)) * 0.1,
              "b2": jnp.zeros(10)}

    def logits(p, x):
        return jax.nn.relu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    def loss_fn(p, x, y):
        lg = jax.nn.log_softmax(logits(p, x))
        return -jnp.mean(jnp.take_along_axis(lg, y[:, None].astype(jnp.int32), 1))

    tex_j, tey_j = jnp.asarray(tex), jnp.asarray(tey)

    @jax.jit
    def acc_batch(params_b):
        """(B,) test accuracies for a batch of parameter pytrees."""
        def acc(p):
            return jnp.mean(
                (jnp.argmax(logits(p, tex_j), 1) == tey_j).astype(jnp.float32))
        return jax.vmap(acc)(params_b)

    return (cx, cy), params, loss_fn, acc_batch


def _ms(vals) -> str:
    """mean±std formatting for the derived CSV field."""
    v = np.asarray(vals)
    return f"{v.mean():.3f}±{v.std():.3f}"


def _fold_grid(base_key, offsets: jnp.ndarray) -> jnp.ndarray:
    """``fold_in(base_key, o)`` for every entry of an integer array, in ONE
    dispatch (bitwise-identical to the per-element Python loop, which costs
    S x R host round-trips inside timed regions)."""
    flat = jax.vmap(lambda o: jax.random.fold_in(base_key, o))(jnp.ravel(offsets))
    return flat.reshape(offsets.shape + flat.shape[1:])


def _fl_run_batched(scheduler, env, use_matching, rounds, m, n, data,
                    params0, loss_fn, acc_batch, n_seeds, track=(40, 80)):
    """Multi-seed FL on the batched engine: all seeds of one policy run as
    ONE vmapped scan program per checkpoint segment — metrics sync once per
    segment, eval only at checkpoints.  Returns per-seed arrays for error
    bars (mean±std over seeds is the Fig. 3/4 claim)."""
    from repro.data import BatchedFederatedLoader
    from repro.fl import AsyncFLConfig, AsyncFLTrainer
    cx, cy = data
    cfg = AsyncFLConfig(n_clients=m, n_channels=n, local_epochs=3,
                        client_lr=0.15, server_lr=0.15,
                        use_matching=use_matching, use_zeta=use_matching)
    tr = AsyncFLTrainer(cfg, scheduler, env, loss_fn)
    loader = BatchedFederatedLoader(cx, cy, batch_size=16, local_epochs=3,
                                    seeds=[4 + i for i in range(n_seeds)])
    init_keys = jnp.stack([jax.random.fold_in(KEY, 7000 + i)
                           for i in range(n_seeds)])
    states = tr.init_batch(params0, init_keys)
    checkpoints = sorted({t for t in track if t < rounds} | {rounds})
    cum_var, curve = np.zeros((n_seeds,)), {}
    t0 = time.perf_counter()
    start = 0
    for cp in checkpoints:
        seg = cp - start
        bx, by = loader.next_rounds(seg)
        rkeys = _fold_grid(KEY, 500_000 * (jnp.arange(n_seeds) + 1)[:, None]
                           + jnp.arange(start, cp)[None, :])
        states, mets = simulate_fl_batch(
            tr, states, jnp.asarray(bx), jnp.asarray(by), rkeys)
        cum_var += np.asarray(jnp.sum(mets["aoi_var"], axis=1))  # one sync/segment
        if cp in track:
            curve[cp] = _ms(acc_batch(states.params))
        start = cp
    us = (time.perf_counter() - t0) / (rounds * n_seeds) * 1e6
    return np.asarray(acc_batch(states.params)), cum_var, curve, us


def fig3_fig4_fl():
    """Fig. 3 (accuracy) / Fig. 4 (fairness) with mean±std error bars over
    seeds, paper policies next to the related-work baselines — every policy
    runs through the identical batched-FL path and matching layer."""
    rounds, track = (30, (10, 20)) if QUICK else (150, (40, 80))
    n_seeds = 2 if QUICK else 8
    # piecewise-stationary, the paper's large scale: N=30, M=20
    m, n = 20, 30
    data, params, loss_fn, acc_batch = _make_problem(m, alpha=0.1, dim=48,
                                                     noise=1.0, spc=192)
    env = _skewed_piecewise(jax.random.PRNGKey(9), n, rounds, 4)
    for name, sched, match in [
        ("random", RandomScheduler(n, m), False),
        ("channel-aware", ChannelAwareAsync(n, m), False),
        ("lyapunov", LyapunovSched(n, m), False),
        ("glr-cucb", GLRCUCB(n, m, history=256), False),
        ("glr-cucb+aware", GLRCUCB(n, m, history=256), True),
    ]:
        accs, var, curve, us = _fl_run_batched(
            sched, env, match, rounds, m, n, data, params, loss_fn,
            acc_batch, n_seeds, track)
        row(f"fig3/piecewise/{name}", us,
            f"acc={_ms(accs)};seeds={n_seeds};curve={curve}")
        row(f"fig4/piecewise/{name}", us, f"cum_aoi_var={_ms(var)}")

    # extremely non-stationary, the paper's small scale: N=6, M=4
    m, n = 4, 6
    data, params, loss_fn, acc_batch = _make_problem(m, alpha=0.1, dim=48,
                                                     noise=1.0, spc=192)
    aenv = random_adversarial_env(jax.random.PRNGKey(10), n, rounds,
                                  flip_prob=0.01)
    for name, sched, match in [
        ("random", RandomScheduler(n, m), False),
        ("channel-aware", ChannelAwareAsync(n, m), False),
        ("lyapunov", LyapunovSched(n, m), False),
        ("m-exp3", MExp3(n, m, share_alpha=1e-3), False),
        ("m-exp3+aware", MExp3(n, m, share_alpha=1e-3), True),
    ]:
        accs, var, curve, us = _fl_run_batched(
            sched, aenv, match, rounds, m, n, data, params, loss_fn,
            acc_batch, n_seeds, track)
        row(f"fig3/adversarial/{name}", us,
            f"acc={_ms(accs)};seeds={n_seeds};curve={curve}")
        row(f"fig4/adversarial/{name}", us, f"cum_aoi_var={_ms(var)}")


# ---------------------------------------------------------------------------
# fl_batch — serial-vs-batched speedup of the FL engine + batch-of-1 parity
# ---------------------------------------------------------------------------

def fl_batch_bench():
    """The FL analogue of the fig2c speedup row, measured as the complete
    Fig. 3 reproduction workflow: per-seed accuracy curves need a checkpoint
    eval every few rounds, so both paths run checkpoint-segmented training —
    segments of scan-fused rounds, a metric sync and a test-set eval at each
    checkpoint.  Serially that is S x (per-segment dispatch + eval + host
    sync); batched, every segment is ONE vmapped program and ONE vmapped
    eval for all S seeds.  Also re-checks batch-of-1 bitwise parity (the
    engine's contract) on every run."""
    from repro.data import BatchedFederatedLoader
    from repro.fl import AsyncFLConfig, AsyncFLTrainer
    n_seeds = 2 if QUICK else 8
    seg, n_segs = (10, 2) if QUICK else (10, 6)
    rounds = seg * n_segs
    m, n = 4, 6                       # the paper's small FL scale
    data, params, loss_fn, acc_batch = _make_problem(
        m, alpha=0.3, dim=8, noise=1.0, spc=48, hidden=16)
    env = _skewed_piecewise(jax.random.PRNGKey(12), n, rounds, 2)
    cfg = AsyncFLConfig(n_clients=m, n_channels=n, local_epochs=1,
                        client_lr=0.1, server_lr=0.1)
    tr = AsyncFLTrainer(cfg, GLRCUCB(n, m, history=128), env, loss_fn)

    loader = BatchedFederatedLoader(data[0], data[1], batch_size=4,
                                    local_epochs=1,
                                    seeds=[4 + i for i in range(n_seeds)])
    bx, by = loader.next_rounds(rounds)
    bx, by = jnp.asarray(bx), jnp.asarray(by)
    init_keys = jnp.stack([jax.random.fold_in(KEY, 100 + i)
                           for i in range(n_seeds)])
    rkeys = _fold_grid(KEY, 10_000 * (jnp.arange(n_seeds) + 1)[:, None]
                       + jnp.arange(rounds)[None, :])
    lift1 = functools.partial(jax.tree_util.tree_map, lambda x: x[None])

    def serial_all():
        """S independent curve runs: per-seed segments, evals, syncs."""
        for i in range(n_seeds):
            st, cv = tr.init(params, init_keys[i]), 0.0
            for s in range(n_segs):
                sl = slice(s * seg, (s + 1) * seg)
                st, mets = tr.run(st, bx[i, sl], by[i, sl], rkeys[i, sl])
                cv += float(jnp.sum(mets["aoi_var"]))       # per-segment sync
                float(acc_batch(lift1(st.params))[0])       # checkpoint eval
    def batched_all():
        st, cv = tr.init_batch(params, init_keys), np.zeros(n_seeds)
        for s in range(n_segs):
            sl = slice(s * seg, (s + 1) * seg)
            st, mets = simulate_fl_batch(
                tr, st, bx[:, sl], by[:, sl], rkeys[:, sl])
            cv += np.asarray(jnp.sum(mets["aoi_var"], axis=1))
            np.asarray(acc_batch(st.params))                # checkpoint eval

    serial_all(); batched_all()                             # warm both paths
    serial_s = batched_s = float("inf")
    for _ in range(1 if QUICK else 3):                      # de-noise: best-of
        t0 = time.perf_counter()
        serial_all()
        serial_s = min(serial_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        batched_all()
        batched_s = min(batched_s, time.perf_counter() - t0)

    # --- batch-of-1 bitwise parity (re-checked on every run) ----------------
    st_s, mets_s = tr.run(tr.init(params, init_keys[0]), bx[0], by[0], rkeys[0])
    st1, mets1 = simulate_fl_batch(
        tr, tr.init_batch(params, init_keys[:1]), bx[:1], by[:1], rkeys[:1])
    match = all(
        np.array_equal(np.asarray(a), np.asarray(b[0]))
        for a, b in zip(jax.tree_util.tree_leaves(st_s),
                        jax.tree_util.tree_leaves(st1))
    ) and all(
        np.array_equal(np.asarray(mets_s[k]), np.asarray(mets1[k][0]))
        for k in mets_s
    )

    speedup = serial_s / max(batched_s, 1e-9)
    BENCH["fl_batch"] = {
        "seeds": n_seeds,
        "rounds": rounds,
        "checkpoint_every": seg,
        "serial_s": round(serial_s, 3),
        "batched_s": round(batched_s, 3),
        "speedup": round(speedup, 2),
        "batch1_bitwise_match": bool(match),
    }
    row("sim/fl-batch1-parity", 0.0, f"bitwise_match={match}")
    row("sim/fl-batch-speedup", 0.0,
        f"seeds={n_seeds};rounds={rounds};serial_s={serial_s:.2f};"
        f"batched_s={batched_s:.2f};speedup={speedup:.2f}x")


# ---------------------------------------------------------------------------
# fl_substrate — sparse event-driven client axis at N = 1e5
# ---------------------------------------------------------------------------

def fl_substrate():
    """The sparse FL substrate's two acceptance numbers, re-measured per run.

    Throughput: ``SparseAsyncFLTrainer`` at N=100,000 clients / M=64 slots
    (per-client state is O(1) scalars in (N,) arrays; only the M scheduled
    clients train and hit the ``weighted_aggregate`` kernel) under Markov
    availability churn, reported as warm FL rounds/sec — the dense runtime
    cannot represent this N at all (O(N*P) buffers, all-N training).
    ``--quick`` shrinks the round count but N stays at 1e5: the point of
    the record is the population scale.

    Parity: at M = N the top-M selection degenerates to the identity
    permutation and every gather/scatter is an identity move, so the sparse
    trainer must reproduce the dense ``AsyncFLTrainer`` BITWISE at the
    paper's FL scale (M=20 clients, N=30 channels) — every state leaf and
    every metric.  The bit is gated in CI."""
    from repro.core.availability import MarkovChurn
    from repro.core.channels import make_scenario
    from repro.data.pipeline import client_batch_indices, gather_client_batches
    from repro.fl import (AsyncFLConfig, AsyncFLTrainer, SparseFLConfig,
                          SparseAsyncFLTrainer)
    from repro.fl.sparse import _DATA_TAG
    from repro.utils.tree import tree_flatten_concat

    # --- throughput at population scale ------------------------------------
    n, m, nch, d, nex, bsz = 100_000, 64, 16, 16, 8, 4
    rounds = 4 if QUICK else 24

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    rng = np.random.default_rng(0)
    cx = jnp.asarray(rng.normal(size=(n, nex, d)).astype(np.float32))
    cy = jnp.asarray(rng.normal(size=(n, nex)).astype(np.float32))
    params0 = {"w": jnp.zeros((d,), jnp.float32)}
    tr = SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=n, n_sched=m, n_channels=nch,
                       batch_size=bsz, local_epochs=1, staleness_cap=8),
        GLRCUCB(nch, m, history=128),
        make_stationary(jnp.linspace(0.9, 0.3, nch)), loss_fn,
        availability=MarkovChurn(p_drop=0.05, p_rejoin=0.5))
    keys = jax.random.split(KEY, rounds)
    jax.block_until_ready(tr.run(tr.init(params0, KEY), cx, cy, keys))  # warm
    t0 = time.perf_counter()
    st, mets = tr.run(tr.init(params0, KEY), cx, cy, keys)
    jax.block_until_ready(st.params)
    wall_s = time.perf_counter() - t0
    rps = rounds / wall_s
    finite = bool(jnp.isfinite(tree_flatten_concat(st.params)).all()
                  and jnp.isfinite(mets["local_loss"]).all())
    served = int(jnp.sum(st.aoi < rounds + 1))
    row(f"fl_substrate/throughput/N={n}/M={m}", wall_s / rounds * 1e6,
        f"rounds={rounds};rounds_per_sec={rps:.2f};finite={finite};"
        f"clients_served={served}")

    # --- dense-vs-sparse bitwise parity at the paper's FL scale -------------
    pn, pnch, pr, pe, pb = 20, 30, 6, 2, 3
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
        AsyncFLConfig(n_clients=pn, n_channels=pnch, local_epochs=pe,
                      staleness_cap=3, max_update_norm=50.0),
        psched, proc, ploss, realize_key=rk)
    sparse = SparseAsyncFLTrainer(
        SparseFLConfig(n_clients=pn, n_sched=pn, n_channels=pnch,
                       batch_size=pb, local_epochs=pe, staleness_cap=3,
                       max_update_norm=50.0),
        psched, proc, ploss, realize_key=rk)
    pkeys = jax.random.split(jax.random.fold_in(KEY, 42), pr)
    ids = jnp.arange(pn, dtype=jnp.int32)
    bxs, bys = [], []
    for r_ in range(pr):   # dense side replays the sparse on-device data draw
        kd = jax.random.fold_in(pkeys[r_], _DATA_TAG)
        idx = client_batch_indices(kd, ids, 16, pe, pb)
        bx_, by_ = gather_client_batches(pcx, pcy, ids, idx)
        bxs.append(bx_)
        bys.append(by_)
    ds, dm = dense.run(dense.init(pp0, KEY), jnp.stack(bxs), jnp.stack(bys),
                       pkeys)
    ss, sm = sparse.run(sparse.init(pp0, KEY), pcx, pcy, pkeys)
    shared = [
        (ds.params, ss.params), (ds.buffers, ss.buffers),
        (ds.has_update, ss.has_update), (ds.last_success, ss.last_success),
        (ds.aoi, ss.aoi), (ds.staleness, ss.staleness),
        (ds.contrib, ss.contrib), (ds.zeta, ss.zeta),
        (ds.contrib_buf, ss.contrib_buf), (ds.sched_state, ss.sched_state),
        (ds.env_state, ss.env_state),
    ]
    parity = all(
        np.array_equal(np.asarray(la), np.asarray(lb))
        for a, b in shared
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b))
    ) and all(
        np.array_equal(np.asarray(dm[k]), np.asarray(sm[k])) for k in dm)
    row("fl_substrate/dense-vs-sparse-parity", 0.0,
        f"M=N={pn};rounds={pr};bitwise_match={parity}")

    BENCH["fl_substrate"] = {
        "n_clients": n,
        "n_sched": m,
        "n_channels": nch,
        "rounds": rounds,
        "wall_s": round(wall_s, 3),
        "rounds_per_sec": round(rps, 2),
        "finite": finite,
        "clients_served": served,
        "availability": "markov_churn",
        "parity_n_clients": pn,
        "parity_rounds": pr,
        "dense_vs_sparse_bitwise": bool(parity),
    }


# ---------------------------------------------------------------------------
# chaos_suite — closed-loop adversaries + fault injection + degradation
# ---------------------------------------------------------------------------

def chaos_suite():
    """Robustness record: the PR's acceptance criteria, re-measured per run.

    Regret half: a reactive-jammer x congestion grid of one (T, N) lands in
    ONE sweep bucket (closed-loop envs bucket by canonical-form signature
    exactly like open-loop ones), with the single-case sweep re-checked
    bitwise against the serial harness (batch-of-1 parity).  The follower
    jammer is then compared with the MATCHED open-loop ``JammingOverlay``
    on the same base scenario and seed: GLR-CUCB must experience a
    different restart count AND different AoI regret — the evidence the
    adversary actually closes the loop on the policy's schedule.

    FL half: a 20% NaN-gradient ``FaultProcess`` through the async trainer
    — the quarantined run must stay finite end to end (params, losses)
    while the unguarded baseline diverges; a 2**24 byte-flip run must stay
    on the data scale only when ``max_update_norm`` is set.

    v2 (Byzantine half): the attack x defense matrix — ``sign_flip`` and
    ``inner_product`` at 20% Byzantine against every registered aggregator
    — runs as vmapped sweep buckets (seeds stack per cell); the containment
    bits assert that ``mean`` measurably degrades under both attacks while
    at least one robust aggregator holds the final eval loss near clean,
    that the explicit ``MeanAgg`` + no-fault path is bitwise-identical to
    the legacy default trainer, that a 2-config burst-schedule grid runs as
    <= 2 buckets with batch-of-1 bitwise parity against the serial trainer,
    and that a ``SchedServer`` killed mid-``serve_stream`` and restored
    from its snapshot emits the uninterrupted run's exact assignments."""
    import tempfile

    from repro.core.aggregation import make_aggregator
    from repro.core.channels import make_scenario
    from repro.core.faults import make_fault
    from repro.fl import AsyncFLConfig, AsyncFLTrainer
    from repro.sim import SchedServer, ServeRequest
    from repro.sim.sweep import FLSweepCase
    from repro.utils.tree import tree_flatten_concat

    t_sim, n, m = (400, 8, 3) if QUICK else (4000, 8, 3)
    sched = GLRCUCB(n, m, history=256, detector_stride=5)
    base = PiecewiseProcess(n, t_sim, 4)

    # --- ONE bucket for the whole closed-loop adversary grid ----------------
    procs = (
        [(f"reactive-jam/{v}", make_scenario("reactive_jammer", base=base,
                                             strength=v))
         for v in (0.6, 0.9)]
        + [(f"congestion/{v}", make_scenario("congestion", n_channels=n,
                                             horizon=t_sim, severity=v))
           for v in (0.4, 0.8)]
    )
    cases = [SweepCase(name, sched, p, jax.random.fold_in(KEY, 300 + i), t_sim)
             for i, (name, p) in enumerate(procs)]
    results, report = sweep(cases, collect_curve=False, block=True)
    buckets = len(report)
    for name, _ in procs:
        out = results[name]
        row(f"chaos/{name}", 0.0,
            f"regret={float(out['final_regret']):.0f};"
            f"restarts={int(out['restarts'])};"
            f"success_rate={float(out['success_rate']):.3f}")

    # batch-of-1 parity: a single reactive case through the sweep vs serial
    c0 = cases[0]
    one, _ = sweep([SweepCase("one", c0.scheduler, c0.env, c0.key, t_sim)],
                   collect_curve=False, block=False)
    serial0 = simulate_aoi_regret(sched, c0.env, c0.key, t_sim,
                                  collect_curve=False)
    batch1_match = all(
        np.array_equal(np.asarray(serial0[k]), np.asarray(one["one"][k]))
        for k in serial0)
    row("chaos/reactive-batch1-parity", 0.0, f"bitwise_match={batch1_match}")

    # --- reactive vs matched open-loop: the scheduling-shift acceptance -----
    react = make_scenario("reactive_jammer", base=base, strength=0.9)
    openl = JammingOverlay(base=base, horizon=t_sim, strength=0.9)
    rr = simulate_aoi_regret(sched, react, KEY, t_sim, collect_curve=False)
    ro = simulate_aoi_regret(sched, openl, KEY, t_sim, collect_curve=False)
    restart_shift = int(rr["restarts"]) != int(ro["restarts"])
    regret_shift = float(rr["final_regret"]) != float(ro["final_regret"])
    row("chaos/reactive-vs-openloop", 0.0,
        f"reactive_regret={float(rr['final_regret']):.0f};"
        f"openloop_regret={float(ro['final_regret']):.0f};"
        f"reactive_restarts={int(rr['restarts'])};"
        f"openloop_restarts={int(ro['restarts'])}")

    # --- FL degradation bits -----------------------------------------------
    rounds, m_fl, n_fl, d = (20 if QUICK else 40), 6, 9, 12

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    params0 = {"w": jnp.full((d,), 0.5, jnp.float32)}
    bx = jax.random.normal(jax.random.fold_in(KEY, 31),
                           (rounds, m_fl, 1, 4, d))
    by = jnp.sum(bx, -1) * 0.3
    rkeys = jax.random.split(jax.random.fold_in(KEY, 32), rounds)
    env_fl = make_stationary(jnp.full((n_fl,), 0.8))

    def fl_final(faults, **cfg_kw):
        cfg = AsyncFLConfig(n_clients=m_fl, n_channels=n_fl, **cfg_kw)
        tr = AsyncFLTrainer(cfg=cfg, scheduler=GLRCUCB(n_fl, m_fl, history=64),
                            env=env_fl, loss_fn=loss_fn, faults=faults)
        st, mets = tr.run(tr.init(params0, KEY), bx, by, rkeys)
        return tree_flatten_concat(st.params), mets

    nan_faults = make_fault("nan_grads", rate=0.2)
    w_q, mets_q = fl_final(nan_faults, quarantine=True)
    w_u, _ = fl_final(nan_faults, quarantine=False)
    quarantined_finite = bool(jnp.isfinite(w_q).all()
                              and jnp.isfinite(mets_q["local_loss"]).all())
    unguarded_diverged = not bool(jnp.isfinite(w_u).all())
    row("chaos/fl-nan-20pct", 0.0,
        f"quarantined_finite={quarantined_finite};"
        f"unguarded_diverged={unguarded_diverged};"
        f"final_loss={float(mets_q['local_loss'][-1]):.4f}")

    flip = make_fault("byte_flip", rate=0.3, exponent=24.0)
    w_c, _ = fl_final(flip, max_update_norm=1e3)
    norm_cap_held = bool(jnp.isfinite(w_c).all()
                         and float(jnp.abs(w_c).max()) < 1e3)
    row("chaos/fl-byte-flip-capped", 0.0, f"norm_cap_held={norm_cap_held}")

    # --- v2: Byzantine attack x robust-aggregation matrix -------------------
    # every cell (attack x defense) runs its seeds as ONE vmapped sweep
    # bucket; containment is judged on the final params' loss over a
    # held-out batch, against the clean (no-fault, default-mean) run.  The
    # matrix keeps its own 40-round horizon in BOTH modes (the model is a
    # 12-dim linear problem — the cost is negligible) so the quick-mode CI
    # regen reproduces the committed full-mode containment numbers exactly.
    byz_rounds = 40
    bxz = jax.random.normal(jax.random.fold_in(KEY, 31),
                            (byz_rounds, m_fl, 1, 4, d))
    byy = jnp.sum(bxz, -1) * 0.3
    ex = jax.random.normal(jax.random.fold_in(KEY, 33), (256, d))
    ey = jnp.sum(ex, -1) * 0.3

    def eval_loss(p) -> float:
        return float(loss_fn(p, ex, ey))

    def mk_trainer(faults, aggregator):
        return AsyncFLTrainer(
            cfg=AsyncFLConfig(n_clients=m_fl, n_channels=n_fl),
            scheduler=GLRCUCB(n_fl, m_fl, history=64), env=env_fl,
            loss_fn=loss_fn, faults=faults, aggregator=aggregator)

    attacks = {
        "sign_flip": make_fault("sign_flip", rate=0.2, scale=8.0),
        "inner_product": make_fault("inner_product", rate=0.2, strength=8.0),
    }
    defenses = {
        "mean": None,
        "trimmed_mean": make_aggregator("trimmed_mean", trim_frac=0.34),
        "coordinate_median": make_aggregator("coordinate_median"),
        "norm_clip": make_aggregator("norm_clip", clip_norm=1.0),
    }
    seeds = 2
    cells = [("clean", mk_trainer(None, None))] + [
        (f"{a}+{dname}", mk_trainer(fault, dfn))
        for a, fault in attacks.items() for dname, dfn in defenses.items()]
    byz_cases = [
        FLSweepCase(f"byz/{name}/s{s}", tr_, params0,
                    jax.random.fold_in(KEY, 700 + s), bxz, byy,
                    jax.random.split(jax.random.fold_in(KEY, 710 + s),
                                     byz_rounds))
        for name, tr_ in cells for s in range(seeds)]
    byz_res, byz_report = sweep(byz_cases, collect_curve=False, block=True)
    losses = {}
    for name, _ in cells:
        v = float(np.mean([
            eval_loss(byz_res[f"byz/{name}/s{s}"]["state"].params)
            for s in range(seeds)]))
        losses[name] = v
        row(f"chaos/byz/{name}", 0.0,
            f"eval_loss={v:.4f};seeds={seeds}")

    clean_l = losses["clean"]
    robust_names = ("trimmed_mean", "coordinate_median", "norm_clip")
    # `mean` must measurably degrade under EVERY attack (>= 3x the clean
    # eval loss); a defense "contains" an attack when it absorbs >= 70% of
    # that degradation (excess loss over clean at most 0.3x the mean
    # path's).  The expected shape of the record: trimmed_mean and
    # coordinate_median contain sign_flip (far-out-of-range rows trim
    # away) but NOT the ALIE-style inner_product, whose colluding rows
    # hide inside the honest per-coordinate range — norm_clip bounds its
    # magnitude instead and contains both.
    mean_degraded = all(
        (not np.isfinite(losses[f"{a}+mean"]))
        or losses[f"{a}+mean"] >= 3.0 * clean_l
        for a in attacks)

    def _contains(dname, a):
        l, ml = losses[f"{a}+{dname}"], losses[f"{a}+mean"]
        if not np.isfinite(l):
            return False
        if not np.isfinite(ml):
            return True
        return l - clean_l <= 0.3 * (ml - clean_l)

    contained_by = {
        dname: all(_contains(dname, a) for a in attacks)
        for dname in robust_names}
    byz_contained = any(contained_by.values())
    row("chaos/byz-containment", 0.0,
        f"mean_degraded={mean_degraded};contained="
        + ",".join(sorted(k for k, v in contained_by.items() if v)))

    # clean-path parity: explicit MeanAgg + no fault is bitwise the legacy
    # default (aggregator=None) trainer — state leaves AND metrics
    tr_legacy = mk_trainer(None, None)
    tr_mean = mk_trainer(None, make_aggregator("mean"))
    st_l, mets_l = tr_legacy.run(tr_legacy.init(params0, KEY), bx, by, rkeys)
    st_m, mets_m = tr_mean.run(tr_mean.init(params0, KEY), bx, by, rkeys)
    clean_agg_bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(st_l),
                        jax.tree_util.tree_leaves(st_m))
    ) and all(
        np.array_equal(np.asarray(mets_l[k]), np.asarray(mets_m[k]))
        for k in mets_l)
    row("chaos/clean-agg-parity", 0.0, f"bitwise_match={clean_agg_bitwise}")

    # --- v2: burst fault schedules (Gilbert-Elliott carry) ------------------
    # a 2-config burst grid over the SAME base attack: two trainers, <= 2
    # sweep buckets, and the first case re-checked bitwise against the
    # serial trainer (schedule carry is part of the scanned state)
    base_flip = make_fault("sign_flip", rate=0.3, scale=6.0)
    burst_trainers = [
        mk_trainer(make_fault("burst", base=base_flip, p_on=0.15, p_off=0.35),
                   defenses["coordinate_median"]),
        mk_trainer(make_fault("burst", base=base_flip, p_on=0.35, p_off=0.15),
                   defenses["coordinate_median"]),
    ]
    burst_cases = [
        FLSweepCase(f"burst/{i}", tr_, params0, jax.random.fold_in(KEY, 800),
                    bx, by, rkeys)
        for i, tr_ in enumerate(burst_trainers)]
    burst_res, burst_report = sweep(burst_cases, collect_curve=False,
                                    block=True)
    burst_buckets = len(burst_report)
    st_bs, mets_bs = burst_trainers[0].run(
        burst_trainers[0].init(params0, jax.random.fold_in(KEY, 800)),
        bx, by, rkeys)
    sw0 = burst_res["burst/0"]
    burst_batch1 = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(st_bs),
                        jax.tree_util.tree_leaves(sw0["state"]))
    ) and all(
        np.array_equal(np.asarray(mets_bs[k]), np.asarray(sw0["metrics"][k]))
        for k in mets_bs)
    burst_finite = all(
        bool(jnp.isfinite(tree_flatten_concat(
            burst_res[c.name]["state"].params)).all())
        for c in burst_cases)
    row("chaos/burst-grid", 0.0,
        f"buckets={burst_buckets};batch1_bitwise={burst_batch1};"
        f"finite={burst_finite}")

    # --- v2: serving-tier crash recovery ------------------------------------
    # kill a serve_stream at the halfway snapshot, restore into a FRESH
    # server, and require the resumed stream's assignments to be bitwise
    # the uninterrupted run's
    t_srv = 24
    srv_rows = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(KEY, 900), 0.6, (t_srv, n)), np.float32)
    srv_keys = np.asarray(jax.random.split(
        jax.random.fold_in(KEY, 901), 2 * t_srv), np.uint32)

    def srv_reqs(t0, t1):
        return [ServeRequest(tenant=ten, rewards=srv_rows[t],
                             key=srv_keys[2 * t + i])
                for t in range(t0, t1)
                for i, ten in enumerate(("a", "b"))]

    def mk_server():
        srv = SchedServer(sched, capacity=4, slots=4)
        for ten in ("a", "b"):
            srv.join(ten)
        return srv

    srv_full = mk_server()
    base_asg = [a for _, a in srv_full.serve_stream(iter(srv_reqs(0, t_srv)))]
    srv_a = mk_server()
    first = [a for _, a in srv_a.serve_stream(iter(srv_reqs(0, t_srv // 2)))]
    with tempfile.TemporaryDirectory() as ckpt_dir:
        srv_a.save(ckpt_dir, step=t_srv // 2)
        srv_b = mk_server()          # the "crashed-and-restarted" process
        srv_b.restore(ckpt_dir)
        second = [a for _, a in
                  srv_b.serve_stream(iter(srv_reqs(t_srv // 2, t_srv)))]
    resumed = first + second
    serve_restore_bitwise = (
        len(resumed) == len(base_asg)
        and all(np.array_equal(x, y) for x, y in zip(resumed, base_asg)))
    row("chaos/serve-restore", 0.0,
        f"rounds={t_srv};bitwise_match={serve_restore_bitwise}")

    BENCH["chaos_suite"] = {
        "horizon": t_sim,
        "grid_cases": len(cases),
        "buckets": buckets,
        "batch1_bitwise_match": bool(batch1_match),
        "reactive_restarts": int(rr["restarts"]),
        "openloop_restarts": int(ro["restarts"]),
        "reactive_regret": round(float(rr["final_regret"]), 1),
        "openloop_regret": round(float(ro["final_regret"]), 1),
        "restart_shift": bool(restart_shift),
        "regret_shift": bool(regret_shift),
        "fl_rounds": rounds,
        "nan_rate": 0.2,
        "quarantined_finite": quarantined_finite,
        "unguarded_diverged": unguarded_diverged,
        "norm_cap_held": norm_cap_held,
        "byz_rate": 0.2,
        "byz_seeds": seeds,
        "byz_eval_loss": {
            k: (round(v, 4) if np.isfinite(v) else None)
            for k, v in losses.items()},
        "clean_agg_bitwise": bool(clean_agg_bitwise),
        "mean_degraded": bool(mean_degraded),
        "contained_by": {k: bool(v) for k, v in contained_by.items()},
        "byz_contained": bool(byz_contained),
        "burst_buckets": int(burst_buckets),
        "burst_batch1_bitwise": bool(burst_batch1),
        "burst_finite": bool(burst_finite),
        "serve_restore_bitwise": bool(serve_restore_bitwise),
    }
    row("chaos/summary", 0.0,
        f"buckets={buckets};batch1={batch1_match};"
        f"restart_shift={restart_shift};regret_shift={regret_shift};"
        f"quarantined_finite={quarantined_finite};"
        f"unguarded_diverged={unguarded_diverged};"
        f"clean_agg_bitwise={clean_agg_bitwise};"
        f"mean_degraded={mean_degraded};byz_contained={byz_contained};"
        f"burst_buckets={burst_buckets};burst_batch1={burst_batch1};"
        f"serve_restore={serve_restore_bitwise}")


# ---------------------------------------------------------------------------
# serve_suite — multi-tenant scheduler-as-a-service (repro.sim.serve)
# ---------------------------------------------------------------------------

def serve_suite():
    """256 concurrent tenants answered from ONE compiled step: p50/p99/p999
    decision latency, queue depth and decisions/sec under Poisson arrivals
    with tenant churn (the pipelined ``serve_stream`` loop), pipelined vs
    synchronous saturated throughput at equal batch size (gated >= 1.3x),
    both vs a per-tenant serial-dispatch baseline (slot batch of 1), the
    single-tenant serve == offline-simulator bitwise-parity bit, and a
    sharded 10^4-tenant server (NamedSharding slot placement) with its
    sharded == unsharded bitwise-parity bit.

    Churn (leave + re-join with fresh hyper-parameters) re-enters the
    cached admit executable, and autosize resizes re-enter the warmed
    ladder — ``compiles_churn_episode`` counts the sweep executable-cache
    misses across the whole Poisson episode and is gated at <= 2 in CI."""
    from repro.launch.sched_serve import (
        pipelined_poisson_episode,
        pipelined_throughput,
        saturated_throughput,
    )

    C, B = 256, 64                       # tenant capacity, requests per step
    t_par = 150 if QUICK else 1000       # parity-replay rounds
    n_req = C * (2 if QUICK else 12)     # Poisson episode length
    n_serial = B * (2 if QUICK else 8)   # serial-baseline request count
    n, m, h = 16, 4, 256
    sched = GLRCUCB(n, m, history=h, detector_stride=5, split_grid="auto")

    m0 = sweep_cache_stats()["misses"]
    server = SchedServer(sched, capacity=C, slots=B)
    serial = SchedServer(sched, capacity=C, slots=1)   # serial dispatch
    compiles_warmup = sweep_cache_stats()["misses"] - m0

    # -- single-tenant parity: serve == offline simulator, bitwise ---------
    env = random_piecewise_env(KEY, n, t_par, 3)
    off = simulate_aoi_regret(sched, env, KEY, t_par, collect_curve=False,
                              return_state=True)
    rkeys, rstates = offline_round_stream(env, KEY, t_par)
    rkeys = np.asarray(rkeys)
    rstates = np.asarray(rstates, np.float32)
    server.join("parity", key=KEY)
    for t in range(t_par):
        server.serve([ServeRequest("parity", rstates[t], rkeys[t])])
    prow = server.tenant_state("parity")
    parity = all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(jax.tree_util.tree_leaves(off["final_sched_state"]),
                        jax.tree_util.tree_leaves(prow.sched_state))
    ) and bool(jnp.array_equal(off["aoi_pi"], prow.aoi))
    server.leave("parity")

    # -- tenant pool: per-tenant keys + traced-hp overrides ----------------
    tenant_ids = [f"job-{i}" for i in range(C)]
    for i, tid in enumerate(tenant_ids):
        server.join(tid, key=jax.random.fold_in(KEY, i),
                    hp={"gamma": 0.8 + 0.4 * i / C})
        serial.join(tid, key=jax.random.fold_in(KEY, i))
    rounds = 32
    means = jax.random.uniform(KEY, (C, n), minval=0.15, maxval=0.9)
    states = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(KEY, 1), means[None], (rounds, C, n)), np.float32)
    keys = np.asarray(jax.random.split(jax.random.fold_in(KEY, 2),
                                       max(n_req, n_serial)))

    # -- saturated throughput: sync batched vs serial vs pipelined ---------
    # best-of-2 on the gated pair: scheduler-noise robustness for the CI
    # speedup floor
    rate = max(saturated_throughput(server, tenant_ids, states, keys, n_req)
               for _ in range(2))
    serial_rate = saturated_throughput(serial, tenant_ids, states, keys,
                                       n_serial)
    speedup = rate / serial_rate
    # pipelined serve_stream at the SAME fixed batch size (autosize off):
    # the overlap of host packing/conversion with the in-flight device step
    # is the only difference — gated >= 1.3x in CI
    pipe_rate = max(
        pipelined_throughput(server, tenant_ids, states, keys, n_req)
        for _ in range(2))
    pipe_speedup = pipe_rate / rate

    # -- Poisson episode at 80% of saturation, with churn, pipelined -------
    server.warm()                   # ladder precompiled: resizes cost 0
    m1 = sweep_cache_stats()["misses"]
    st0 = server.stats()
    lam = 0.8 * rate
    arrivals = np.cumsum(
        np.random.default_rng(0).exponential(1.0 / lam, size=n_req))
    lat, wall, churn_events, depths = pipelined_poisson_episode(
        server, tenant_ids, states, keys, arrivals, churn_stride=8)
    compiles_churn = sweep_cache_stats()["misses"] - m1
    st1 = server.stats()
    occupancy = ((st1["served"] - st0["served"])
                 / max(st1["rows_dispatched"] - st0["rows_dispatched"], 1))
    p50, p99, p999 = (float(x) for x in np.percentile(lat, [50, 99, 99.9]))

    # -- sharded capacity scale-out: 10^4 tenants, bitwise vs unsharded ----
    C2, B2 = 10_000, 64
    n_req2 = B2 * (2 if QUICK else 8)
    sched2 = GLRCUCB(n, m, history=64, detector_stride=5, split_grid="auto")
    big = SchedServer(sched2, capacity=C2, slots=B2, shard=True)
    big_un = SchedServer(sched2, capacity=C2, slots=B2)
    big_ids = list(range(C2))
    for i in big_ids:
        k_i = jax.random.fold_in(KEY, i)
        big.join(i, key=k_i)
        big_un.join(i, key=k_i)
    states2 = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(KEY, 3), 0.6, (4, C2, n)), np.float32)
    reqs2 = [ServeRequest(big_ids[j % C2],
                          states2[(j // C2) % states2.shape[0], j % C2],
                          keys[j]) for j in range(B2)]
    want2 = big_un.serve(reqs2)
    got2 = big.serve(reqs2)
    sharded_parity = all(
        np.array_equal(a, b) for a, b in zip(got2, want2)) and all(
        bool(np.array_equal(np.asarray(x), np.asarray(y)[: x.shape[0]]))
        for x, y in zip(jax.tree_util.tree_leaves(big_un._state),
                        jax.tree_util.tree_leaves(big._state)))
    big_rate = saturated_throughput(big, big_ids, states2, keys, n_req2)

    row("serve/saturated-batched", 1e6 / rate,
        f"decisions_per_sec={rate:.0f};tenants={C};slot_batch={B}")
    row("serve/saturated-serial", 1e6 / serial_rate,
        f"decisions_per_sec={serial_rate:.0f};speedup={speedup:.1f}")
    row("serve/saturated-pipelined", 1e6 / pipe_rate,
        f"decisions_per_sec={pipe_rate:.0f};speedup_vs_sync={pipe_speedup:.2f}")
    row("serve/poisson", wall / n_req * 1e6,
        f"p50_ms={p50 * 1e3:.2f};p99_ms={p99 * 1e3:.2f};"
        f"p999_ms={p999 * 1e3:.2f};qdepth_mean={depths.mean():.1f};"
        f"occupancy={occupancy:.2f};churn_events={churn_events};"
        f"compiles={compiles_churn}")
    row("serve/sharded-10k", 1e6 / big_rate,
        f"decisions_per_sec={big_rate:.0f};tenants={C2};"
        f"rows={big.rows};parity={sharded_parity}")
    row("serve/parity", 0.0, f"single_tenant_parity={parity}")
    BENCH["serve_suite"] = {
        "tenants": C,
        "slot_batch": B,
        "decisions_per_sec": round(rate, 1),
        "serial_decisions_per_sec": round(serial_rate, 1),
        "speedup_vs_serial": round(speedup, 2),
        "pipelined_decisions_per_sec": round(pipe_rate, 1),
        "pipelined_speedup_vs_sync": round(pipe_speedup, 2),
        "p50_ms": round(p50 * 1e3, 3),
        "p99_ms": round(p99 * 1e3, 3),
        "p999_ms": round(p999 * 1e3, 3),
        "queue_depth_mean": round(float(depths.mean()), 2),
        "queue_depth_max": int(depths.max()),
        "batch_occupancy": round(float(occupancy), 3),
        "poisson_decisions_per_sec": round(n_req / wall, 1),
        "offered_load_frac": 0.8,
        "churn_events": churn_events,
        "compiles_warmup": compiles_warmup,
        "compiles_churn_episode": compiles_churn,
        "single_tenant_parity": bool(parity),
        "sharded_tenants": C2,
        "sharded_rows": int(big.rows),
        "sharded_decisions_per_sec": round(big_rate, 1),
        "sharded_parity": bool(sharded_parity),
    }


# ---------------------------------------------------------------------------
# kernels (interpret mode on CPU — relative numbers only)
# ---------------------------------------------------------------------------

def kernels():
    from repro.kernels import ops, ref

    hist = jax.random.bernoulli(KEY, 0.4, (8, 1024)).astype(jnp.float32)
    counts = jnp.full((8,), 1024, jnp.int32)
    _, us_k = _timed(lambda: ops.glr_scan(hist, counts, backend="pallas_interpret"))
    _, us_r = _timed(lambda: ops.glr_scan(hist, counts, backend="jnp"))
    row("kernel/glr_scan/pallas-interp", us_k, f"ref_us={us_r:.0f}")

    upd = jax.random.normal(KEY, (16, 1 << 16), jnp.bfloat16)
    sc = jax.random.uniform(KEY, (16,))
    _, us_k = _timed(lambda: ops.weighted_aggregate(upd, sc,
                                                    backend="pallas_interpret"))
    _, us_r = _timed(lambda: ref.weighted_aggregate(upd, sc))
    row("kernel/weighted_aggregate/pallas-interp", us_k, f"ref_us={us_r:.0f}")

    q = jax.random.normal(KEY, (1, 4, 512, 128), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, 512, 128))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 2, 512, 128))
    _, us_k = _timed(lambda: ops.flash_attention(
        q, k, v, causal=True, backend="pallas_interpret"))
    _, us_r = _timed(lambda: ref.mha_attention(q, k, v, causal=True))
    row("kernel/flash_attention/pallas-interp", us_k, f"ref_us={us_r:.0f}")


# ---------------------------------------------------------------------------
# roofline table from dry-run artifacts
# ---------------------------------------------------------------------------

def roofline():
    files = sorted(glob.glob(os.path.join("experiments", "dryrun", "*.json")))
    if not files:
        row("roofline/missing", 0.0, "run python -m repro.launch.dryrun first")
        return
    for f in files:
        rec = json.load(open(f))
        tag = f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']}"
        if rec["status"] != "ok":
            row(tag, 0.0, rec.get("reason", rec.get("error", ""))[:60])
            continue
        r = rec["roofline"]
        row(tag, r["step_time_lower_bound_s"] * 1e6,
            f"bottleneck={r['bottleneck']};mfu_bound={r['mfu_bound']:.4f}"
            if r["mfu_bound"] else f"bottleneck={r['bottleneck']}")


def main() -> None:
    global QUICK
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: T=500, single seed, short FL run")
    ap.add_argument("--scenarios", action="store_true",
                    help="run only the two channel-scenario suites (emits "
                         "the scenario_suite and scenario_suite_glr BENCH "
                         "records; composes with --quick)")
    ap.add_argument("--bench-out", default=os.path.join(ROOT, "BENCH_sim.json"),
                    help="where to write the engine wall-time record")
    ap.add_argument("--no-persistent-cache", action="store_true",
                    help="skip the on-disk jax compilation cache (measure "
                         "cold compiles; handled at module import, accepted "
                         "here for --help)")
    args = ap.parse_args()
    QUICK = args.quick

    print("name,us_per_call,derived")
    BENCH["quick"] = QUICK
    dev = jax.devices()[0]
    BENCH["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
    BENCH["persistent_compilation_cache"] = bool(PERSISTENT_CACHE)
    figures = ((scenario_suite, scenario_suite_glr) if args.scenarios else
               (fig2a_regret, fig2b_breakpoints, fig2c_scale, batch1_parity,
                glr_detector, hp_grid, scenario_suite, scenario_suite_glr,
                chaos_suite, fig3_fig4_fl, fl_batch_bench, fl_substrate,
                serve_suite, kernels, roofline))
    for fig in figures:
        _figure(fig)
    # per-run compile accounting of the sweep executable cache: misses are
    # actual lowers+compiles, hits are reused executables (per-figure
    # breakdown in sweep_exec_cache_phases)
    stats = sweep_cache_stats()
    total = stats["hits"] + stats["misses"]
    stats["hit_rate"] = round(stats["hits"] / total, 3) if total else None
    BENCH["sweep_exec_cache"] = stats
    with open(args.bench_out, "w") as f:
        json.dump(BENCH, f, indent=2, sort_keys=True)
    print(f"# wrote {args.bench_out}", flush=True)


if __name__ == "__main__":
    main()
