"""Heterogeneous sweep driver: group cases into vmappable buckets.

A figure-level sweep mixes schedulers (different state pytrees), horizons
and env families — those cannot share one vmap.  ``sweep`` groups cases by
(scheduler *structural signature*, horizon, env treedef + leaf shapes),
runs each bucket through ``simulate_aoi_regret_batch`` as ONE compiled
program, and returns per-case results keyed by case name.

Scheduler configs are frozen dataclasses (hashable, compared by value);
the bucket key is their ``hp_signature()``: every structural field by
value, traced hyper-parameter fields by *name only*.  Two cases whose
schedulers differ solely in traced scalars (``gamma``, ``delta``, EMA
rates, ...) therefore land in ONE bucket — the per-case values are stacked
into an ``hparams`` pytree and fed through the engine's vmapped
hyper-parameter axis, so a 16-point tuning grid costs one compile, not 16.

Compiled programs are additionally kept in a process-level AOT executable
cache keyed on the bucket signature (+ batch size / backend / mesh):
repeated ``sweep`` calls with structurally identical buckets — e.g. a
benchmark running fig2a then a tuning grid with the same policy family, or
two grids with different scalar values — reuse the executable instead of
re-lowering.  ``sweep_cache_stats()`` exposes hit/miss counts (the
benchmark harness reports them in ``BENCH_sim.json``, with a per-figure
breakdown and overall hit rate, so every compile is attributable; case
keys and traced scalar values never enter a bucket signature, and
``block=False`` sweeps bypass the AOT cache by design).

Scenario processes (``repro.core.channels.ChannelProcess``) drop into
``SweepCase.env`` unrealized: cases bucket by the scenario's canonical-form
signature — families merge — and the bucket runner realizes them (one
vmapped ``scenario_grid`` program per family) before the ONE compiled
simulation runs.  A 12-scenario × S-seed grid spanning four table-form
families is one simulation bucket.

``sweep(..., shard=True)`` distributes every regret bucket's batch axis
over a 1-D device mesh via ``repro.sim.shard`` (``shard_map``; buckets are
embarrassingly parallel).  On a single device the sharded program is
bitwise identical to the unsharded one, so the path stays exercised in CPU
CI.

FL cases (``FLSweepCase``) ride the same driver: a mixed case list is
bucketed with regret cases side by side, and each FL bucket executes as one
``simulate_fl_batch`` program (vmap over seeds).  FL buckets merge by the
trainer's VALUE-based ``bucket_signature()`` (cfg + scheduler
``hp_signature`` + env canonical shapes + loss-fn identity + fault
instance): distinct trainer instances that differ only in scheduler traced
scalars or env values share one bucket — the scalars are stacked into the
state ``hp`` axis and the envs stacked into the engine's env operand axis.
Scenario-backed trainers (constructed from an unrealized
``ChannelProcess``) are re-realized PER CASE from
``scenario_realize_key(case.init_key)`` — the same derivation the regret
path uses — so each Monte-Carlo seed sees its own channel trajectory
(the trainer's own PRNGKey(0)-fallback env is never used by the sweep).
``shard=True`` spreads FL buckets over the device mesh exactly like
regret buckets (bitwise identical on one device).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.bandits.base import stack_params
from repro.core.channels import (
    ChannelEnv,
    ChannelProcess,
    realize_processes,
    scenario_realize_key,
    stack_envs,
)
from repro.sim import shard as _shard
from repro.sim.engine import simulate_aoi_regret_batch
from repro.sim.fl_batch import simulate_fl_batch


@dataclasses.dataclass(frozen=True)
class SweepCase:
    """One (name, scheduler, env, key, horizon) simulation request.

    ``env`` is a realized ``ChannelEnv`` or an unrealized
    ``ChannelProcess`` scenario.  Process cases bucket by the scenario's
    *canonical-form signature* (``env_signature()``), not its family: a
    mixed grid of Gilbert–Elliott / mobility / shadowing / jamming
    scenarios of one (T, N) lands in ONE simulation bucket (realization
    runs per family through ``scenario_grid`` — one tiny vmapped program
    each), with the scenario drawn from ``scenario_realize_key(key)``,
    matching what ``simulate_aoi_regret`` derives on the serial path.
    """

    name: str
    scheduler: Any
    env: Any                     # ChannelEnv | ChannelProcess
    key: jax.Array
    horizon: int


@dataclasses.dataclass(frozen=True)
class FLSweepCase:
    """One (name, trainer, params, init_key, round data, round keys) FL run.

    ``trainer`` is an ``AsyncFLTrainer``; cases whose trainers share a
    ``bucket_signature()`` (same config / scheduler family / env structure
    / loss fns — VALUES may differ) batch into one vmapped program, one
    entry per case: fold the seed into ``init_key``/``round_keys`` and draw
    ``batches_*`` from a per-seed loader.  Scenario-process trainers get a
    per-case realization drawn from ``scenario_realize_key(init_key)`` —
    the serial-equivalent trainer is ``AsyncFLTrainer(..., env=process,
    realize_key=scenario_realize_key(init_key))``.  The sweep result for an
    FL case is ``{"state": final AsyncFLState, "metrics": {name: (R,)}}``.
    """

    name: str
    trainer: Any
    params: Any
    init_key: jax.Array
    batches_x: Any               # (R, M, E, B, ...) per-round client data
    batches_y: Any               # (R, M, E, B)
    round_keys: jax.Array        # (R,)


@dataclasses.dataclass
class BucketReport:
    """Execution record for one vmappable bucket (for BENCH_sim.json)."""

    names: List[str]
    batch: int
    compile_s: float
    wall_s: float
    cache_hit: bool = False      # AOT executable served from the sweep cache
    sharded: bool = False        # ran through the shard_map path


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def _tree_sig(tree) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple((tuple(jnp.shape(l)), str(jnp.result_type(l))) for l in leaves)
    return (treedef, shapes)


def _sched_sig(sched) -> Any:
    """Structural identity: hp_signature when the policy supports traced
    hyper-parameters, the (hashable) config itself otherwise."""
    fn = getattr(sched, "hp_signature", None)
    return fn() if fn is not None else sched


def _bucket_key(case):
    if isinstance(case, FLSweepCase):
        # value-based trainer signature: equal-signature trainer INSTANCES
        # (same structure, possibly different env values / traced scalars)
        # merge into one bucket and one compiled program
        sig_fn = getattr(case.trainer, "bucket_signature", None)
        tr_sig = sig_fn() if sig_fn is not None else case.trainer
        return ("fl", tr_sig, _tree_sig(case.params),
                _tree_sig((case.batches_x, case.batches_y, case.round_keys)))
    # scenario processes bucket by canonical form + shapes, NOT family:
    # same-signature scenarios realize to stackable envs, so one compiled
    # simulation serves every family of that form
    env_sig = (("scenario",) + case.env.env_signature()
               if isinstance(case.env, ChannelProcess)
               else _tree_sig(case.env))
    return ("regret", _sched_sig(case.scheduler), case.horizon, env_sig)


def group_cases(cases: Sequence[Any]) -> List[List[Any]]:
    """Partition cases into vmappable buckets, preserving first-seen order."""
    buckets: Dict[Any, List[Any]] = {}
    order = []
    for c in cases:
        k = _bucket_key(c)
        if k not in buckets:
            buckets[k] = []
            order.append(k)
        buckets[k].append(c)
    return [buckets[k] for k in order]


# ---------------------------------------------------------------------------
# process-level AOT executable cache
# ---------------------------------------------------------------------------

_EXEC_CACHE: Dict[Any, Any] = {}
_EXEC_STATS = {"hits": 0, "misses": 0}


def sweep_cache_stats() -> Dict[str, int]:
    """Hit/miss counts of the sweep executable cache (misses == compiles)."""
    return dict(_EXEC_STATS)


def clear_sweep_cache() -> None:
    """Drop every cached executable and reset the hit/miss counters."""
    _EXEC_CACHE.clear()
    _EXEC_STATS.update(hits=0, misses=0)


def cached_compile(cache_key, do_lower, span=None):
    """AOT-compile through the process-level executable cache.

    ``do_lower()`` must return a ``jax.stages.Lowered``; its ``.compile()``
    result is memoized under ``cache_key`` and returned as ``(compiled,
    compile_s, cache_hit)``.  A compiled executable must be invoked with
    the exact arg/kwarg split it was lowered with.  On a miss, the context
    manager that ``span()`` returns (if given) is held over the lowering
    and compile: the serving loop passes a ``sched.compile`` profiler span.

    Public so other drivers share ONE cache and ONE accounting stream with
    the sweep: the multi-tenant serving loop (``repro.sim.serve``) registers
    its step/admit executables here, which is what makes tenant churn
    attributably recompile-free — ``sweep_cache_stats()`` misses stay flat
    across join/leave because every churn event re-enters an executable
    this cache already holds.
    """
    compiled = _EXEC_CACHE.get(cache_key)
    if compiled is not None:
        _EXEC_STATS["hits"] += 1
        return compiled, 0.0, True
    t0 = time.perf_counter()
    with span() if span is not None else contextlib.nullcontext():
        compiled = do_lower().compile()
    compile_s = time.perf_counter() - t0
    _EXEC_CACHE[cache_key] = compiled
    _EXEC_STATS["misses"] += 1
    return compiled, compile_s, False


_compile_cached = cached_compile  # internal alias kept for the bucket runners


def _mesh_desc(mesh) -> Any:
    if mesh is None:
        return None
    return tuple(str(d) for d in mesh.devices.flat)


# ---------------------------------------------------------------------------
# bucket runners
# ---------------------------------------------------------------------------

def _run_regret_bucket(bucket, collect_curve: bool, block: bool, mesh=None):
    if isinstance(bucket[0].env, ChannelProcess):
        # realize the bucket's scenarios (grouped per family into vmapped
        # scenario_grid programs) from keys derived exactly as the serial
        # harness derives them — sweep results match per-case
        # simulate_aoi_regret(sched, process, key, T) bitwise
        envs = realize_processes(
            [c.env for c in bucket],
            jnp.stack([scenario_realize_key(c.key) for c in bucket]))
    else:
        envs = stack_envs([c.env for c in bucket])
    keys = jnp.stack([c.key for c in bucket])
    # merge traced scalars: one (B,)-stacked params() pytree for the bucket;
    # the representative scheduler's own traced values never reach the
    # compiled program.  None for knob-free or legacy (no-params())
    # schedulers — those keep the plain init(key) path.
    hparams = stack_params([c.scheduler for c in bucket])
    hp_axis = None if hparams is None else 0
    sched, horizon = bucket[0].scheduler, bucket[0].horizon
    cache_key = (_bucket_key(bucket[0]), len(bucket), collect_curve,
                 jax.default_backend(), _mesh_desc(mesh))

    if mesh is not None:
        d = int(mesh.devices.size)
        envs_c, b = _shard.pad_batch(envs, d)
        keys_c, _ = _shard.pad_batch(keys, d)
        hp_c = _shard.pad_batch(hparams, d)[0] if hparams is not None else None
        fn = _shard.build_sharded(sched, horizon, collect_curve, mesh,
                                  hp_axis=hp_axis)
        do_lower = lambda: jax.jit(fn).lower(envs_c, keys_c, hp_c)
        call = lambda compiled: compiled(envs_c, keys_c, hp_c)
        padded = (-b) % d != 0
        unpad = (lambda out: _shard.unpad_batch(out, b)) if padded else (lambda out: out)
    else:
        do_lower = lambda: simulate_aoi_regret_batch.lower(
            sched, envs, keys, horizon, collect_curve=collect_curve,
            hparams=hparams, hp_axis=hp_axis)
        # a Compiled must be invoked with the arg/kwarg structure it was
        # lowered with — hparams went in as a keyword
        call = lambda compiled: compiled(envs, keys, hparams=hparams)
        unpad = lambda out: out

    cache_hit = False
    if block:
        compiled, compile_s, cache_hit = _compile_cached(cache_key, do_lower)
        t1 = time.perf_counter()
        out = call(compiled)
        jax.block_until_ready(out)
        wall_s = time.perf_counter() - t1
    else:
        t0 = time.perf_counter()
        if mesh is not None:
            out = _shard.sharded_aoi_regret_batch(
                sched, envs, keys, horizon, collect_curve=collect_curve,
                hparams=hparams, hp_axis=hp_axis, mesh=mesh)
            unpad = lambda o: o           # already unpadded by the shard API
        else:
            out = simulate_aoi_regret_batch(
                sched, envs, keys, horizon, collect_curve=collect_curve,
                hparams=hparams, hp_axis=hp_axis)
        compile_s = wall_s = time.perf_counter() - t0
    return unpad(out), compile_s, wall_s, cache_hit


def _fl_bucket_envs(bucket):
    """The bucket's stacked env operand: per-case scenario realizations
    (drawn from ``scenario_realize_key(case.init_key)`` — different seeds,
    different realized tables, matching what a serial trainer constructed
    with ``realize_key=scenario_realize_key(init_key)`` sees) or the cases'
    own trainer envs stacked (equal-signature trainers, possibly different
    env values)."""
    if bucket[0].trainer.scenario is not None:
        return realize_processes(
            [c.trainer.scenario for c in bucket],
            jnp.stack([scenario_realize_key(c.init_key) for c in bucket]))
    return stack_envs([c.trainer.env for c in bucket])


def _run_fl_bucket(bucket, block: bool, mesh=None):
    tr = bucket[0].trainer
    params = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
        *[c.params for c in bucket])
    # per-case scheduler traced scalars: equal-signature trainers may carry
    # different gamma/delta/... values — they ride the state hp axis, never
    # the representative trainer's own values
    hparams = stack_params([c.trainer.scheduler for c in bucket])
    states = tr.init_batch(
        params, jnp.stack([c.init_key for c in bucket]), params_axis=0,
        hp=hparams, hp_axis=None if hparams is None else 0)
    envs = _fl_bucket_envs(bucket)
    bx = jnp.stack([jnp.asarray(c.batches_x) for c in bucket])
    by = jnp.stack([jnp.asarray(c.batches_y) for c in bucket])
    rkeys = jnp.stack([c.round_keys for c in bucket])
    cache_key = (_bucket_key(bucket[0]), len(bucket),
                 jax.default_backend(), _mesh_desc(mesh))

    if mesh is not None:
        d = int(mesh.devices.size)
        states_c, b = _shard.pad_batch(states, d)
        envs_c = _shard.pad_batch(envs, d)[0]
        bx_c = _shard.pad_batch(bx, d)[0]
        by_c = _shard.pad_batch(by, d)[0]
        rkeys_c = _shard.pad_batch(rkeys, d)[0]
        fn = _shard.build_fl_sharded(tr, mesh)
        do_lower = lambda: jax.jit(fn).lower(states_c, bx_c, by_c, rkeys_c,
                                             envs_c)
        call = lambda compiled: compiled(states_c, bx_c, by_c, rkeys_c, envs_c)
        padded = (-b) % d != 0
        unpad = ((lambda out: _shard.unpad_batch(out, b)) if padded
                 else (lambda out: out))
    else:
        do_lower = lambda: simulate_fl_batch.lower(
            tr, states, bx, by, rkeys, envs=envs, env_axis=0)
        call = lambda compiled: compiled(states, bx, by, rkeys, envs)
        unpad = lambda out: out

    cache_hit = False
    if block:
        compiled, compile_s, cache_hit = _compile_cached(cache_key, do_lower)
        t1 = time.perf_counter()
        out = call(compiled)
        jax.block_until_ready(out)
        wall_s = time.perf_counter() - t1
    else:
        t0 = time.perf_counter()
        if mesh is not None:
            out = _shard.build_fl_sharded(tr, mesh)(
                states_c, bx_c, by_c, rkeys_c, envs_c)
        else:
            out = simulate_fl_batch(tr, states, bx, by, rkeys,
                                    envs=envs, env_axis=0)
        compile_s = wall_s = time.perf_counter() - t0
    final_states, metrics = unpad(out)
    return ({"state": final_states, "metrics": metrics},
            compile_s, wall_s, cache_hit)


def sweep(
    cases: Sequence[Any],
    collect_curve: bool = True,
    block: bool = True,
    shard: bool = False,
    mesh: Optional[Any] = None,
) -> Tuple[Dict[str, Dict[str, Any]], List[BucketReport]]:
    """Run every case, batching compatible ones into single XLA programs.

    ``cases`` may mix ``SweepCase`` (regret) and ``FLSweepCase`` (federated
    training) entries; each bucket is homogeneous and executes through the
    matching engine (``simulate_aoi_regret_batch`` / ``simulate_fl_batch``).
    Regret cases whose schedulers differ only in traced hyper-parameters
    share one bucket (the scalars are stacked and vmapped — see module
    docstring), so a tuning grid compiles once per policy family.

    ``shard=True`` spreads each regret bucket's batch over a 1-D device
    mesh (``mesh`` or all local devices) via ``repro.sim.shard``; a single
    device runs the identical program (bitwise) through the same path.

    Returns ``(results, report)``:
      results: case name -> the ``simulate_aoi_regret`` result dict (regret
               cases) or ``{"state": AsyncFLState, "metrics": {k: (R,)}}``
               (FL cases), batch axis already stripped.
      report:  one ``BucketReport`` per executed bucket: ``compile_s`` from
               an AOT lower+compile (0.0 when the executable cache hit —
               see ``cache_hit``), ``wall_s`` the blocked execution time.
               ``block=False`` skips AOT and blocking for latency-insensitive
               callers; both times then record only dispatch (not execution)
               and must not be used as measurements.
    """
    names = [c.name for c in cases]
    if len(set(names)) != len(names):
        raise ValueError(f"sweep: duplicate case names: {names}")
    run_mesh = (mesh if mesh is not None else _shard.sweep_mesh()) if shard else None

    results: Dict[str, Dict[str, Any]] = {}
    report: List[BucketReport] = []
    for bucket in group_cases(cases):
        if isinstance(bucket[0], FLSweepCase):
            out, compile_s, wall_s, hit = _run_fl_bucket(bucket, block, run_mesh)
        else:
            out, compile_s, wall_s, hit = _run_regret_bucket(
                bucket, collect_curve, block, run_mesh)
        sharded = run_mesh is not None

        for i, c in enumerate(bucket):
            results[c.name] = jax.tree_util.tree_map(lambda x, i=i: x[i], out)
        report.append(BucketReport(
            names=[c.name for c in bucket], batch=len(bucket),
            compile_s=compile_s, wall_s=wall_s, cache_hit=hit, sharded=sharded))
    return results, report
