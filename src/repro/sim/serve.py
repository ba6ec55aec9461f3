"""Multi-tenant scheduler-as-a-service: ONE compiled step for every job.

The paper's scheduler is the per-round decision loop of a single federated
job.  This module serves it as a shared online service: many concurrent FL
deployments (tenants) each submit ``(tenant_id, reward_vector) ->
schedule`` requests, and every batch of requests — whichever tenants they
belong to — executes as one fixed-shape XLA program over device-resident
per-tenant state.

Tenant-axis state contract
--------------------------
``TenantSlots`` stacks, per slot, the complete per-job decision state:

* the policy state pytree (for GLR-CUCB that includes the streaming
  detector's carried prefix rings ``cum``/``total``/``base`` — PR 5 made
  this O(N) per tenant, which is what lets thousands of tenants' full
  scheduler state live on device),
* the Sec.-V matcher normalizers (``MatcherState``),
* per-client AoI, the tenant's round clock ``t``, a membership flag, and
  decision/success counters.

Every leaf has leading shape ``rows >= capacity + 1``: row ``capacity`` is
a scratch slot that absorbs padding writes (see below) and is never read.
Unsharded servers use exactly ``capacity + 1`` rows; sharded servers round
``rows`` up to the device count (the extra rows are additional never-read
scratch), so every leaf partitions evenly over the mesh.

Sharded capacity
----------------
``SchedServer(..., shard=True)`` places every ``TenantSlots`` leaf over the
1-D "cases" device mesh (``repro.sim.shard.shard_slots`` — the same
``NamedSharding`` recipe the sparse FL client axis rides).  The serve step
is gather / per-row compute / scatter on slot indices, so XLA splits the
O(capacity) state residency across devices with no cross-device traffic
beyond the (slots,) gathers; the per-row math runs replicated inside
``shard_map`` (see ``make_serve_step``).  On a single device the placement is
the identity — results are bitwise unchanged — which is what lets
``capacity`` grow to 10^4–10^5 tenants without touching the step program.
Host bookkeeping stays O(1) per join/leave at any capacity: the free-slot
pool (``_FreePool``) is a fresh-slot counter plus a recycle stack, never an
eagerly materialized list.

Request batching / padding rules
--------------------------------
Requests are batched into a fixed number of ``slots`` per step (the step's
shape NEVER changes, so one executable serves any traffic mix):

* short batches are padded with rows targeting the scratch slot, mask off;
* a masked row computes the full per-request math but merges to the OLD
  gathered values, so its scatter write is a bitwise no-op on live state —
  and duplicate scatter indices (every pad row hits the scratch slot) all
  carry identical values, keeping the write order-independent;
* at most one LIVE request per tenant per batch (``SchedServer`` defers
  duplicates to the next step), so live scatter indices never collide.

Unlike ``sim/shard.py``'s pad-by-cycling (where duplicate rows recompute
real *read-only* simulations), serve steps WRITE per-tenant state — cycling
would double-update a tenant — hence the scratch-row scheme.

Pipelined serving (``serve_stream``)
------------------------------------
``serve()`` is the synchronous loop: it converts each step's assignment to
``np.ndarray`` (a device sync) before packing the next step.
``serve_stream()`` is the pipelined generator: while step k executes on
device, the host packs and dispatches step k+1 and only then converts step
k's assignment — request batching and result conversion overlap the
in-flight device step, and results come back with ONE STEP of latency
(yielded in dispatch order).  The stream also autosizes the slot batch
from observed queue depth, moving between AOT-cached executables (one per
ladder size, all through ``cached_compile``) so resizing costs zero
recompiles after warmup.  ``tests/test_serve_scale.py`` pins the stream's
output bitwise-equal to the synchronous loop over the same request trace,
including across churn and mid-stream resizes.

Spans and latency counters
--------------------------
Each ``serve_stream`` iteration opens one ``jax.profiler.TraceAnnotation``
per phase it works in, with the stream step and batch size as metadata
(``step``, ``b``) while a profiler session is on: ``sched.source`` (pulling
from the caller's iterable; the caller's own work nests inside),
``sched.take_batch``, ``sched.pack`` (with ``_sanitize_rewards``),
``sched.dispatch`` (``_get_step`` and the executable call),
``sched.fetch`` (the previous step's assignment to host memory) and
``sched.deliver`` (yielding its answers; the caller's handling nests
inside).  ``join``/``leave`` open ``sched.admit``, and an executable-cache
miss ``sched.compile``.  While a session is on, two cumulative histograms
over ``LATENCY_EDGES_S`` time the streamed requests, one clock read each:
*queue wait*, from when the stream pulled a request from its source to when
its step's executable call returned, and *in flight*, from then to the end
of ``sched.fetch``.  A step only logs its times; they are binned,
vectorised, once ``_LATENCY_BIN_AFTER`` requests are logged and whenever
``stats()`` reports them.  With no session on, an iteration costs one
``TraceAnnotation.is_enabled()`` and a no-op context manager per phase: a
clock read per request was measurable on a host whose clock is slow to read.

Boundary hygiene and crash recovery
-----------------------------------
Reward vectors are sanitized at the packing boundary (``_sanitize_rewards``):
non-finite entries become 0.0 and finite entries clip to [0, 1] before they
can reach the compiled step, with a per-tenant ``bad_rewards`` counter in
``stats()``; valid vectors pack bitwise-unchanged.  ``save()``/``restore()``
snapshot the complete serving state — the device-resident ``TenantSlots``
pytree via ``repro.checkpoint.io`` plus a JSON sidecar for the host
bookkeeping (tenant map, free-slot pool, counters) — so a server killed
mid-``serve_stream`` resumes from the last snapshot and emits the exact
decision stream the uninterrupted run would have produced
(``tests/test_serve_restore.py``).

Churn without recompiles
------------------------
``join``/``leave`` run one shared ``admit`` program that overwrites a
single slot with a freshly initialized tenant row: the membership flag and
the traced hyper-parameter pytree are *inputs*, so joining, leaving and
re-joining with different gamma/delta all re-enter the same executable.
Both the step and admit programs are AOT-compiled through the sweep
driver's process-level executable cache (``repro.sim.sweep.cached_compile``)
— a churn episode of any length costs exactly the warmup compiles and
``sweep_cache_stats()`` misses stay flat afterwards.

Parity with the offline simulator — and with the FL trainers
------------------------------------------------------------
The per-request transition calls ``repro.core.regret.policy_round`` — the
exact function the offline ``simulate_aoi_regret`` scan body runs — so a
single tenant served one request per round on the stream
``offline_round_stream(env, key, T)`` reproduces the offline simulation
*bitwise* (state, AoI and restart counts; asserted in
``tests/test_serve.py`` and gated in CI via the ``serve_suite`` benchmark).

FL trainers consume schedules from a server through the same protocol
(``AsyncFLTrainer.run_served`` / ``SparseAsyncFLTrainer.run_served``): the
trainer posts its realized channel vector, round key, contributions AND its
own AoI (``ServeRequest.aoi`` — the trainer resets AoI on *aggregated*
deliveries, not raw channel successes, so the server's select/match must
read the caller's freshness state), and gets back the (M,) assignment plus
the post-step matcher row (``ServeDecision``).  One trainer served this way
reproduces its standalone ``run()`` bitwise (``tests/test_fl_served.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import os
import time
from collections import deque
from typing import (
    Any, Dict, Iterable, Iterator, List, NamedTuple, Optional, Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.checkpoint.io import restore_checkpoint, save_checkpoint

from repro.core.aoi import init_aoi, update_aoi
from repro.core.bandits.base import init_with_hp
from repro.core.matching import AdaptiveMatcher, MatcherState
from repro.core.regret import policy_round
from repro.sim.shard import shard_slots, sweep_mesh
from repro.sim.sweep import _sched_sig, cached_compile

# Bucket edges of serve_stream's latency histograms, in seconds: geometric,
# 25 a decade (ratio 10**0.04, about 1.096) from 1 us to 100 s.  Bucket 0
# counts times under 1 us, bucket i times in [edges[i-1], edges[i]), and the
# last bucket 100 s and more.
_PER_DECADE = 25
LATENCY_EDGES_S = np.geomspace(1e-6, 100.0, 8 * _PER_DECADE + 1)
# requests whose logged times serve_stream folds into the histograms at once
_LATENCY_BIN_AFTER = 4096
# a phase's span while no profiler session is on
_NO_SPAN = contextlib.nullcontext()


def _bucket_counts(seconds, weights=None) -> np.ndarray:
    """Counts of ``seconds`` in the buckets of ``LATENCY_EDGES_S``, each
    time counted ``weights`` times where given.  The bucket comes from the
    logarithm (a tenth of the cost of a search over the edges)."""
    with np.errstate(divide="ignore"):
        pos = np.floor(np.log10(np.asarray(seconds, np.float64) * 1e6)
                       * _PER_DECADE)
    idx = np.clip(pos, -1, LATENCY_EDGES_S.size - 1).astype(np.int64) + 1
    return np.bincount(idx, weights, minlength=LATENCY_EDGES_S.size + 1
                       ).astype(np.int64)


def latency_quantile(edges, counts, q: float) -> Optional[float]:
    """The ``q``-th percentile, in seconds, of a latency histogram as
    ``stats()`` reports it (``latency_edges_s`` and one of the ``*_counts``),
    interpolated linearly inside its bucket; ``None`` for an empty one.  The
    first bucket spans [0, edges[0]); the open last bucket gives its edge."""
    counts = np.asarray(counts, np.float64)
    total = counts.sum()
    if total <= 0:
        return None
    lo_edges = np.concatenate([[0.0], edges])
    rank = q / 100.0 * total
    cum = np.cumsum(counts)
    # the first bucket whose count reaches the rank, and a non-empty one
    i = max(int(np.searchsorted(cum, rank, side="left")),
            int(np.argmax(counts > 0)))
    if i >= len(edges):
        return float(edges[-1])
    lo, hi = lo_edges[i], edges[i]
    return float(lo + (hi - lo) * (rank - (cum[i] - counts[i])) / counts[i])


class TenantSlots(NamedTuple):
    """Device-resident state for ``capacity`` tenants + scratch row(s).

    Every leaf's leading axis is ``rows >= capacity + 1``; row ``capacity``
    is the scratch slot padding writes land on (never read, never live).
    Sharded servers may carry extra trailing scratch rows so ``rows``
    divides the device mesh.
    """

    sched_state: Any          # policy state pytree, leaves (rows, ...) —
                              # includes the streaming-GLR prefix rings
    matcher_state: MatcherState   # Sec.-V normalizers, leaves (rows,)
    aoi: jnp.ndarray          # (rows, M) per-client AoI
    t: jnp.ndarray            # (rows,) int32 per-tenant round clock
    active: jnp.ndarray       # (rows,) bool membership mask
    decisions: jnp.ndarray    # (rows,) int32 requests served
    successes: jnp.ndarray    # (rows,) f32 cumulative successful transmissions


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    """One tenant's per-round decision request.

    ``rewards`` is the tenant's realized (N,) channel-state vector for this
    round (the scheduled entries become the policy's semi-bandit feedback);
    ``key`` is the tenant's round key — for bitwise parity with the offline
    simulator, feed the keys ``offline_round_stream`` derives.  ``contrib``
    (optional, (M,)) carries the FL job's per-client marginal contributions
    for the Sec.-V matcher; defaults to uniform.  ``aoi`` (optional, (M,))
    overrides the server's carried AoI row for this request's select/match:
    FL trainers own their AoI semantics (reset on aggregation, not on raw
    channel success) and post it here; ``None`` keeps the server's row.
    """

    tenant: Any
    rewards: Any
    key: Any
    contrib: Any = None
    aoi: Any = None


class ServeDecision(NamedTuple):
    """One request's full decision: the (M,) channel assignment plus the
    post-step Sec.-V matcher row (``v_max``/``a_max``/``beta_t`` scalars) —
    what an FL trainer needs to carry its matcher state bitwise."""

    assignment: np.ndarray
    matcher_state: MatcherState


class _FreePool:
    """O(1)-per-op free-slot pool over ``capacity`` slots.

    Fresh slots are handed out from a monotonically advancing counter and
    returned slots from a LIFO recycle stack, so construction, ``pop`` and
    ``push`` cost O(1) at ANY capacity — a capacity=10^9 server's
    bookkeeping is as cheap as a capacity=4 one (micro-tested in
    ``tests/test_serve_scale.py``); nothing ever materializes an
    O(capacity) Python structure.  Allocation order matches the legacy
    eager list: fresh slots come out 0, 1, 2, ... and the most recently
    freed slot is reused first.
    """

    __slots__ = ("_capacity", "_next_fresh", "_recycled")

    def __init__(self, capacity: int):
        self._capacity = capacity
        self._next_fresh = 0
        self._recycled: List[int] = []

    def __len__(self) -> int:
        return (self._capacity - self._next_fresh) + len(self._recycled)

    def pop(self) -> int:
        if self._recycled:
            return self._recycled.pop()
        if self._next_fresh < self._capacity:
            slot = self._next_fresh
            self._next_fresh += 1
            return slot
        raise IndexError("pop from empty _FreePool")

    def push(self, slot: int) -> None:
        self._recycled.append(slot)


def init_slots(scheduler, capacity: int, matcher_beta: float = 0.5,
               rows: Optional[int] = None) -> TenantSlots:
    """Fresh all-inactive slot state (``rows`` defaults to ``capacity + 1``
    — see TenantSlots; sharded servers pass a mesh-divisible ``rows``)."""
    matcher = AdaptiveMatcher(matcher_beta)
    rows = capacity + 1 if rows is None else rows

    def row(key):
        return TenantSlots(
            sched_state=scheduler.init(key),
            matcher_state=matcher.init(),
            aoi=init_aoi(scheduler.n_clients),
            t=jnp.zeros((), jnp.int32),
            active=jnp.zeros((), bool),
            decisions=jnp.zeros((), jnp.int32),
            successes=jnp.zeros((), jnp.float32),
        )

    # slot contents are placeholders until `admit` overwrites them (slots
    # start inactive); a fixed fan-out key keeps the initial state reproducible
    return jax.vmap(row)(jax.random.split(jax.random.PRNGKey(0), rows))


def make_serve_step(scheduler, use_matching: bool = False,
                    matcher_beta: float = 0.5, score_kind: str = "ucb",
                    mesh=None):
    """Build the batched serving step ``(state, slots, rewards, keys,
    contrib, aoi, aoi_set, mask) -> (state, assignment, matcher_state)``.

    ``slots (B,) int32`` maps each request row to its tenant slot (pad rows
    target the scratch slot); ``rewards (B, N)``; ``keys (B, 2) uint32``
    round keys; ``contrib (B, M)``; ``aoi (B, M)`` per-request AoI override,
    applied where ``aoi_set (B,) bool``; ``mask (B,) bool`` marks real rows.
    Returns the updated state, the per-request ``(B, M)`` channel assignment
    (pad/inactive rows: all -1) and the post-step matcher rows ((B,)-leaved
    ``MatcherState`` — served FL trainers carry these).

    The per-request transition is ``repro.core.regret.policy_round`` — the
    offline scan body's own code — optionally composed with the Sec.-V
    matcher.  ``score_kind`` routes the matcher's channel-ranking source
    exactly like ``repro.core.matching.matcher_scores``: ``"ucb"`` uses the
    policy's native ``channel_scores`` (Eq. 30), ``"mean"`` its historical
    ``mean_scores`` (Eq. 31) when the policy provides them.

    ``mesh`` (the sharded server's) runs the per-request math under
    ``shard_map``, replicated on every device: the compiler cannot partition
    a Pallas kernel (the detector's ``glr_step``), and the B gathered rows
    are tiny next to the sharded slot state, whose gather and scatter stay
    partitioned.  Every device computes the unsharded program, so results
    are bitwise those of the unsharded step.
    """
    matcher = AdaptiveMatcher(matcher_beta)

    def scores_of(sstate, t):
        if score_kind == "mean":
            fn = getattr(scheduler, "mean_scores", None)
            if fn is not None:
                return fn(sstate, t)
        return scheduler.channel_scores(sstate, t)

    def one(row: TenantSlots, r_vec, key, contrib, aoi_in, aoi_set):
        # the request key is the tenant's round key; the env half of the
        # split belongs to whoever realized r_vec (offline_round_stream
        # mirrors the offline simulator's derivation exactly)
        _, k_sel = jax.random.split(key)
        row_aoi = jnp.where(aoi_set, aoi_in, row.aoi)
        if use_matching:
            channels, aux = scheduler.select(row.sched_state, row.t, k_sel,
                                             row_aoi)
            scores = scores_of(row.sched_state, row.t)
            assignment, mstate = matcher.match(
                row.matcher_state, channels, scores, contrib, row_aoi)
            rewards = r_vec[assignment]
            sstate = scheduler.update(row.sched_state, row.t, assignment,
                                      rewards, aux)
            aoi = update_aoi(row_aoi, rewards > 0.5)
        else:
            sstate, aoi, assignment, rewards = policy_round(
                scheduler, row.sched_state, row_aoi, row.t, k_sel, r_vec)
            mstate = row.matcher_state
        new_row = TenantSlots(
            sched_state=sstate,
            matcher_state=mstate,
            aoi=aoi,
            t=row.t + 1,
            active=row.active,
            decisions=row.decisions + 1,
            successes=row.successes + jnp.sum(rewards),
        )
        return new_row, assignment

    rows_fn = jax.vmap(one)
    if mesh is not None:
        rows_fn = jax.shard_map(rows_fn, mesh=mesh, in_specs=P(),
                                out_specs=P(), check_vma=False)

    def serve_step(state: TenantSlots, slots, rewards, keys, contrib,
                   aoi, aoi_set, mask):
        sub = jax.tree_util.tree_map(lambda x: x[slots], state)
        live = mask & sub.active
        new_rows, assignment = rows_fn(sub, rewards, keys, contrib, aoi,
                                       aoi_set)

        def merge(new, old):
            m = live.reshape(live.shape + (1,) * (new.ndim - 1))
            return jnp.where(m, new, old)

        # dead rows (pad / inactive / masked) merge back to their gathered
        # values, so their scatter is a bitwise no-op — and every pad row's
        # duplicate write to the scratch slot carries identical values,
        # keeping the scatter order-independent
        merged = jax.tree_util.tree_map(merge, new_rows, sub)
        out = jax.tree_util.tree_map(
            lambda s, v: s.at[slots].set(v), state, merged)
        assignment = jnp.where(live[:, None], assignment, -1)
        return out, assignment, merged.matcher_state

    return serve_step


def make_admit(scheduler, matcher_beta: float = 0.5):
    """Build the join/leave program ``(state, slot, key, hp, active) ->
    state``: overwrite one slot with a freshly initialized tenant row.

    ``hp`` is the scheduler's traced hyper-parameter pytree (per-tenant
    gamma/delta/... ride here) and ``active`` a traced bool — join
    (``True``) and leave (``False``) are the SAME executable, so tenant
    churn never compiles.
    """
    matcher = AdaptiveMatcher(matcher_beta)

    def admit(state: TenantSlots, slot, key, hp, active):
        fresh = TenantSlots(
            sched_state=init_with_hp(scheduler, key, hp),
            matcher_state=matcher.init(),
            aoi=init_aoi(scheduler.n_clients),
            t=jnp.zeros((), jnp.int32),
            active=jnp.asarray(active, bool),
            decisions=jnp.zeros((), jnp.int32),
            successes=jnp.zeros((), jnp.float32),
        )
        return jax.tree_util.tree_map(
            lambda s, v: s.at[slot].set(v), state, fresh)

    return admit


def offline_round_stream(env, key, horizon: int):
    """The ``(keys, states)`` stream the offline simulator consumes.

    ``keys[t]`` is the round key ``simulate_aoi_regret(sched, env, key, T)``
    feeds its step, and ``states[t]`` the (N,) channel realization it draws
    from the env half of that key — so replaying this stream through the
    serving loop one request per round reproduces the offline simulation
    bitwise.  Open-loop canonical envs only (the serving loop has no
    closed-loop feedback channel).
    """
    keys = jax.random.split(jax.random.fold_in(key, 1), horizon)

    def row(t, k):
        k_env, _ = jax.random.split(k)
        return env.sample(t, k_env)

    states = jax.vmap(row)(jnp.arange(horizon), keys)
    return keys, states


class SchedServer:
    """Online scheduling service over a fixed-capacity tenant pool.

    Two programs are compiled per (policy family, shape) configuration —
    the batched serve step and the admit program — both AOT through the
    sweep driver's process-level executable cache, so a second server with
    the same shape (or any amount of tenant churn) compiles nothing.
    ``warm()`` optionally precompiles the autosizing ladder (one step
    executable per batch size ≤ ``slots``) so ``serve_stream`` resizes
    between cached executables.  The step's tenant-state operand is
    donated: per-step state updates are in-place on backends with donation.

    ``serve(requests)`` batches requests into fixed-size steps (padding
    short batches with scratch-slot rows, deferring same-tenant duplicates
    to the next step) and returns each request's (M,) channel assignment in
    request order, synchronizing on every step.  ``serve_stream(requests)``
    is the pipelined double-buffered loop (results lag dispatch by one
    step); ``serve_decisions(requests)`` additionally returns the post-step
    matcher rows (the FL trainers' protocol).

    ``shard=True`` places the tenant-slot state over the 1-D "cases" device
    mesh (identity — bitwise — on one device), scaling ``capacity`` to
    10^4–10^5; host bookkeeping is O(1) per join/leave at any capacity.
    """

    def __init__(self, scheduler, capacity: int = 256, slots: int = 16,
                 use_matching: bool = False, matcher_beta: float = 0.5,
                 donate: bool = True, score_kind: str = "ucb",
                 shard: bool = False, mesh=None):
        if capacity < 1:
            raise ValueError(f"SchedServer: capacity must be >= 1, got {capacity}")
        if slots < 1:
            raise ValueError(f"SchedServer: slots must be >= 1, got {slots}")
        if score_kind not in ("ucb", "mean"):
            raise ValueError(f"SchedServer: score_kind must be 'ucb' or "
                             f"'mean', got {score_kind!r}")
        self.scheduler = scheduler
        self.capacity = capacity
        self.slots = slots
        self.use_matching = use_matching
        self.matcher_beta = matcher_beta
        self.score_kind = score_kind
        self.shard = bool(shard)
        self._donate = bool(donate)
        if self.shard:
            self._mesh = sweep_mesh() if mesh is None else mesh
            d = int(self._mesh.devices.size)
            # round the slot axis up to the mesh: rows capacity+1 .. rows-1
            # are extra never-read scratch, so every leaf partitions evenly
            self.rows = -(-(capacity + 1) // d) * d
        else:
            self._mesh = None
            self.rows = capacity + 1
        self._state = init_slots(scheduler, capacity, matcher_beta,
                                 rows=self.rows)
        if self.shard:
            self._state = shard_slots(self._state, self._mesh)
        # pin the slot state's output placement to its input placement on a
        # mesh: left to the compiler, a zero-size leaf (the streaming
        # detector's (rows, N, 0) ``hist``) comes back replicated, and the
        # next call's AOT executable — lowered for the sharded input — then
        # refuses it
        self._state_out = (jax.tree_util.tree_map(lambda x: x.sharding,
                                                  self._state)
                           if self.shard else None)
        self._tenants: Dict[Any, int] = {}
        self._free = _FreePool(capacity)
        self._hp_defaults = dict(getattr(scheduler, "params", dict)())
        self._served = 0
        self._steps = 0
        self._stream_steps = 0
        self._rows_dispatched = 0
        self._sizes_used: Dict[int, int] = {}
        self._bad_rewards: Dict[Any, int] = {}
        self._queue_wait = np.zeros(LATENCY_EDGES_S.size + 1, np.int64)
        self._inflight = np.zeros(LATENCY_EDGES_S.size + 1, np.int64)
        # logged by serve_stream, not binned yet: per dispatched step (call
        # returned at, its requests' pull times), per fetched step (in-flight
        # seconds, requests), and how many requests the first holds
        self._wait_log: List[Tuple[float, List[float]]] = []
        self._flight_log: List[Tuple[float, int]] = []
        self._logged = 0

        self._sig = _sched_sig(scheduler)
        self._backend = jax.default_backend()
        self._step_fn = make_serve_step(scheduler, use_matching=use_matching,
                                        matcher_beta=matcher_beta,
                                        score_kind=score_kind,
                                        mesh=self._mesh)
        # batch-size ladder for serve_stream autosizing: powers of two up
        # to `slots` (plus `slots` itself) — each size is its own AOT-cached
        # executable, so resizing between them never recompiles after warmup
        self._ladder = sorted({1 << i for i in range(slots.bit_length())
                               if (1 << i) <= slots} | {slots})
        self.compile_s = 0.0
        self.compiles = 0
        self._step_cache: Dict[int, Any] = {}
        self._templates: Dict[int, Tuple] = {}
        self._step = self._get_step(slots)

        admit_fn = make_admit(scheduler, matcher_beta=matcher_beta)
        donate_idx = (0,) if self._donate else ()
        admit_ex = (self._state, jnp.zeros((), jnp.int32),
                    jnp.zeros((2,), jnp.uint32),
                    {k: jnp.asarray(v, jnp.float32)
                     for k, v in self._hp_defaults.items()},
                    jnp.zeros((), bool))
        self._admit, admit_compile_s, admit_hit = cached_compile(
            ("serve_admit", self._sig, capacity, self.rows,
             float(matcher_beta), tuple(sorted(self._hp_defaults)),
             self._donate, self._backend, self._mesh),
            lambda: jax.jit(admit_fn, donate_argnums=donate_idx,
                            out_shardings=self._state_out).lower(*admit_ex),
            span=lambda: TraceAnnotation("sched.compile"))
        self.compile_s += admit_compile_s
        self.compiles += int(not admit_hit)

    # ------------------------------------------------------------- compile
    def _get_step(self, b: int):
        """The serve-step executable for batch size ``b`` (AOT-cached)."""
        fn = self._step_cache.get(b)
        if fn is not None:
            return fn
        n, m = self.scheduler.n_channels, self.scheduler.n_clients
        donate_idx = (0,) if self._donate else ()
        step_ex = (self._state,
                   jnp.zeros((b,), jnp.int32),
                   jnp.zeros((b, n), jnp.float32),
                   jnp.zeros((b, 2), jnp.uint32),
                   jnp.ones((b, m), jnp.float32),
                   jnp.zeros((b, m), jnp.float32),
                   jnp.zeros((b,), bool),
                   jnp.zeros((b,), bool))
        fn, compile_s, hit = cached_compile(
            ("serve_step", self._sig, self.capacity, self.rows, b,
             self.use_matching, float(self.matcher_beta), self.score_kind,
             self._donate, self._backend, self._mesh),
            lambda: jax.jit(self._step_fn, donate_argnums=donate_idx,
                            out_shardings=(self._state_out, None, None)
                            ).lower(*step_ex),
            span=lambda: TraceAnnotation("sched.compile", b=b))
        self._step_cache[b] = fn
        self.compile_s += compile_s
        self.compiles += int(not hit)
        return fn

    def warm(self, sizes: Optional[Sequence[int]] = None) -> None:
        """Precompile step executables for ``sizes`` (default: the whole
        autosizing ladder) so a later ``serve_stream`` resizes without ever
        missing the executable cache."""
        for b in (self._ladder if sizes is None else sizes):
            self._get_step(int(b))

    def _pick_size(self, depth: int) -> int:
        """Smallest ladder batch size covering ``depth`` queued requests."""
        for b in self._ladder:
            if b >= depth:
                return b
        return self.slots

    # -------------------------------------------------------------- tenants
    def join(self, tenant, key=None, hp: Optional[Dict[str, Any]] = None) -> int:
        """Admit ``tenant`` into a free slot (fresh policy/matcher/AoI state).

        ``hp`` overrides traced hyper-parameters for this tenant (e.g.
        per-job gamma/delta); unknown names raise.  Returns the slot index.
        """
        if tenant in self._tenants:
            raise ValueError(f"SchedServer.join: tenant {tenant!r} already live")
        if not len(self._free):
            raise RuntimeError(
                f"SchedServer.join: at capacity ({self.capacity} tenants "
                f"live) — leave() an existing tenant or construct the "
                f"server with a larger capacity")
        overrides = dict(hp or {})
        unknown = set(overrides) - set(self._hp_defaults)
        if unknown:
            raise ValueError(
                f"SchedServer.join: unknown hyper-parameters {sorted(unknown)} "
                f"(traced: {sorted(self._hp_defaults)})")
        with TraceAnnotation("sched.admit"):
            merged = {k: jnp.asarray(overrides.get(k, v), jnp.float32)
                      for k, v in self._hp_defaults.items()}
            if key is None:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(0), len(self._tenants) + 1)
            slot = self._free.pop()
            self._state = self._admit(
                self._state, jnp.asarray(slot, jnp.int32),
                jnp.asarray(key, jnp.uint32), merged, jnp.asarray(True))
        self._tenants[tenant] = slot
        return slot

    def leave(self, tenant) -> None:
        """Evict ``tenant``: clear its slot's state and free the slot (the
        same admit executable as ``join``, membership flag False)."""
        slot = self._tenants.pop(tenant, None)
        if slot is None:
            raise KeyError(f"SchedServer.leave: unknown tenant {tenant!r}")
        with TraceAnnotation("sched.admit"):
            self._state = self._admit(
                self._state, jnp.asarray(slot, jnp.int32),
                jnp.zeros((2,), jnp.uint32),
                {k: jnp.asarray(v, jnp.float32)
                 for k, v in self._hp_defaults.items()},
                jnp.asarray(False))
        self._free.push(slot)

    @property
    def tenants(self) -> Dict[Any, int]:
        return dict(self._tenants)

    def tenant_state(self, tenant) -> TenantSlots:
        """This tenant's state row (policy state, matcher state, AoI,
        clocks) — a snapshot for inspection/parity checks."""
        slot = self._tenants[tenant]
        return jax.tree_util.tree_map(lambda x: x[slot], self._state)

    # ---------------------------------------------------------- persistence
    def save(self, directory: str, step: int = 0) -> str:
        """Snapshot the full serving state to ``directory``.

        Two artifacts: the device-resident ``TenantSlots`` pytree goes
        through ``repro.checkpoint.io.save_checkpoint`` (atomic npz +
        manifest, ``step_{step}.npz``), and the host bookkeeping — tenant
        map, free-pool cursor/recycle stack, counters, ``bad_rewards`` —
        lands in a ``serve_{step}.json`` sidecar.  Tenant ids must
        round-trip through JSON (ints / strings / floats); a restored
        server continues the decision stream bitwise (see ``restore``).
        Synchronizes on the state (device work must retire before the
        bytes are read), so snapshot mid-``serve_stream`` is safe between
        steps.
        """
        path = save_checkpoint(directory, step, self._state)
        meta = {
            "sig": str(self._sig),
            "capacity": self.capacity,
            "rows": self.rows,
            "slots": self.slots,
            "tenants": [[t, int(s)] for t, s in self._tenants.items()],
            "free_next_fresh": self._free._next_fresh,
            "free_recycled": list(self._free._recycled),
            "served": self._served,
            "steps": self._steps,
            "stream_steps": self._stream_steps,
            "rows_dispatched": self._rows_dispatched,
            "sizes_used": [[int(b), int(c)]
                           for b, c in self._sizes_used.items()],
            "bad_rewards": [[t, int(c)]
                            for t, c in self._bad_rewards.items()],
        }
        with open(os.path.join(directory, f"serve_{step}.json"), "w") as f:
            json.dump(meta, f, indent=1)
        return path

    def restore(self, directory: str, step: Optional[int] = None,
                warm: bool = True) -> int:
        """Load a ``save()`` snapshot into this server; returns the step.

        The server must be constructed with the same scheduler
        configuration / capacity / slots as the one that saved (checked
        against the sidecar — the compiled programs are pure functions of
        that configuration, so a matching server re-enters the same
        executables).  Restores the slot pytree structure-directed
        (bitwise: every leaf comes back with its exact dtype and bytes,
        re-placed on the mesh when sharded) and the host bookkeeping, then
        re-warms the AOT step ladder (``warm=False`` skips, e.g. when the
        process-level executable cache is known hot).  A stream killed
        after step k and resumed from the step-k snapshot emits the exact
        assignments the uninterrupted run would have
        (``tests/test_serve_restore.py``).
        """
        state, step = restore_checkpoint(directory, step=step,
                                         like=self._state)
        with open(os.path.join(directory, f"serve_{step}.json")) as f:
            meta = json.load(f)
        if meta["sig"] != str(self._sig):
            raise ValueError(
                f"SchedServer.restore: snapshot was saved by a different "
                f"scheduler configuration ({meta['sig']} != {self._sig})")
        for field in ("capacity", "rows", "slots"):
            if meta[field] != getattr(self, field):
                raise ValueError(
                    f"SchedServer.restore: snapshot {field}="
                    f"{meta[field]} != server {field}={getattr(self, field)}")
        self._state = shard_slots(state, self._mesh) if self.shard else state
        self._tenants = {t: int(s) for t, s in meta["tenants"]}
        self._free = _FreePool(self.capacity)
        self._free._next_fresh = int(meta["free_next_fresh"])
        self._free._recycled = [int(s) for s in meta["free_recycled"]]
        self._served = int(meta["served"])
        self._steps = int(meta["steps"])
        self._stream_steps = int(meta["stream_steps"])
        self._rows_dispatched = int(meta["rows_dispatched"])
        self._sizes_used = {int(b): int(c) for b, c in meta["sizes_used"]}
        self._bad_rewards = {t: int(c) for t, c in meta["bad_rewards"]}
        if warm:
            self.warm()
        return step

    # -------------------------------------------------------------- serving
    def _sanitize_rewards(self, tenant, rewards) -> np.ndarray:
        """Clip one request's reward vector to finite [0, 1] at the service
        boundary.

        The compiled step trusts its operands (reward semantics are
        probabilities of successful transmission), so a tenant posting NaN /
        inf / out-of-range rewards must be caught HERE, before its vector is
        packed: non-finite entries become 0.0, finite entries clip to
        [0, 1], and the tenant's ``bad_rewards`` counter (surfaced in
        ``stats()``) increments once per offending request.  A valid vector
        takes the early return and is packed bitwise-unchanged — clean
        streams pay one vectorized check and nothing else.
        """
        r = np.asarray(rewards, np.float32)
        finite = np.isfinite(r)
        if finite.all() and (r >= 0.0).all() and (r <= 1.0).all():
            return r
        self._bad_rewards[tenant] = self._bad_rewards.get(tenant, 0) + 1
        return np.clip(np.where(finite, r, 0.0), 0.0, 1.0).astype(np.float32)

    def _take_batch(self, pending: deque, limit: int):
        """Pop up to ``limit`` unique-tenant requests off ``pending``
        (deferring same-tenant duplicates back to the FRONT, in order) —
        the packing rule both serve() and serve_stream() share, so their
        step decomposition of a request trace is identical."""
        batch = []
        used = set()
        deferred = []
        while pending and len(batch) < limit:
            i, rq = pending.popleft()
            slot = self._tenants.get(rq.tenant)
            if slot is None:
                raise KeyError(f"SchedServer.serve: unknown tenant "
                               f"{rq.tenant!r}")
            if slot in used:
                deferred.append((i, rq))
                continue
            used.add(slot)
            batch.append((i, rq, slot))
        pending.extendleft(reversed(deferred))
        return batch

    def _pack(self, batch, b: int):
        """Vectorized host packing of one step's operand arrays (size ``b``).

        Immutable all-default operands (uniform contrib, no AoI override,
        full-live mask) come from per-size cached templates — never mutated,
        so reusing them across steps is safe even under zero-copy
        device transfer."""
        n, m = self.scheduler.n_channels, self.scheduler.n_clients
        live = len(batch)
        tmpl = self._templates.get(b)
        if tmpl is None:
            tmpl = (np.ones((b, m), np.float32),
                    np.zeros((b, m), np.float32),
                    np.zeros((b,), bool),
                    np.ones((b,), bool))
            self._templates[b] = tmpl
        contrib_t, aoi_t, aoi_unset_t, mask_live_t = tmpl

        slots = np.full((b,), self.capacity, np.int32)
        slots[:live] = [s for (_, _, s) in batch]
        rewards = np.zeros((b, n), np.float32)
        rewards[:live] = [self._sanitize_rewards(rq.tenant, rq.rewards)
                          for (_, rq, _) in batch]
        keys = np.zeros((b, 2), np.uint32)
        keys[:live] = [rq.key for (_, rq, _) in batch]

        if any(rq.contrib is not None for (_, rq, _) in batch):
            contrib = contrib_t.copy()
            for j, (_, rq, _) in enumerate(batch):
                if rq.contrib is not None:
                    contrib[j] = rq.contrib
        else:
            contrib = contrib_t
        if any(rq.aoi is not None for (_, rq, _) in batch):
            aoi = aoi_t.copy()
            aoi_set = aoi_unset_t.copy()
            for j, (_, rq, _) in enumerate(batch):
                if rq.aoi is not None:
                    aoi[j] = rq.aoi
                    aoi_set[j] = True
        else:
            aoi, aoi_set = aoi_t, aoi_unset_t
        if live == b:
            mask = mask_live_t
        else:
            mask = np.zeros((b,), bool)
            mask[:live] = True
        return slots, rewards, keys, contrib, aoi, aoi_set, mask

    def _serve_sync(self, requests: Sequence[ServeRequest],
                    want_decisions: bool):
        """The synchronous serving loop: pack, step, SYNC on the assignment,
        repeat — the legacy per-step-blocking baseline ``serve_stream``'s
        pipelining is measured against."""
        n, m = self.scheduler.n_channels, self.scheduler.n_clients
        out: List[Optional[np.ndarray]] = [None] * len(requests)
        decs: List[Optional[ServeDecision]] = [None] * len(requests)
        pending = deque(enumerate(requests))
        while pending:
            batch = self._take_batch(pending, self.slots)

            slots = np.full((self.slots,), self.capacity, np.int32)
            rewards = np.zeros((self.slots, n), np.float32)
            keys = np.zeros((self.slots, 2), np.uint32)
            contrib = np.ones((self.slots, m), np.float32)
            aoi = np.zeros((self.slots, m), np.float32)
            aoi_set = np.zeros((self.slots,), bool)
            mask = np.zeros((self.slots,), bool)
            for j, (i, rq, slot) in enumerate(batch):
                slots[j] = slot
                rewards[j] = self._sanitize_rewards(rq.tenant, rq.rewards)
                keys[j] = np.asarray(rq.key, np.uint32)
                if rq.contrib is not None:
                    contrib[j] = np.asarray(rq.contrib, np.float32)
                if rq.aoi is not None:
                    aoi[j] = np.asarray(rq.aoi, np.float32)
                    aoi_set[j] = True
                mask[j] = True
            self._state, assignment, mstate = self._step(
                self._state, jnp.asarray(slots), jnp.asarray(rewards),
                jnp.asarray(keys), jnp.asarray(contrib), jnp.asarray(aoi),
                jnp.asarray(aoi_set), jnp.asarray(mask))
            assignment = np.asarray(assignment)   # the decision must retire
            if want_decisions:
                mrows = jax.tree_util.tree_map(np.asarray, mstate)
                for j, (i, rq, slot) in enumerate(batch):
                    decs[i] = ServeDecision(
                        assignment=assignment[j],
                        matcher_state=MatcherState(
                            v_max=mrows.v_max[j], a_max=mrows.a_max[j],
                            beta_t=mrows.beta_t[j]))
            for j, (i, rq, slot) in enumerate(batch):
                out[i] = assignment[j]
            self._served += len(batch)
            self._steps += 1
            self._rows_dispatched += self.slots
            self._sizes_used[self.slots] = \
                self._sizes_used.get(self.slots, 0) + 1
        return out, decs

    def serve(self, requests: Sequence[ServeRequest]) -> List[np.ndarray]:
        """Serve a batch of requests; returns each request's (M,) channel
        assignment, in request order.

        Requests are packed into fixed-``slots`` steps; a second request for
        a tenant already in the current step is deferred to the next one
        (live scatter rows must be unique), and short final steps are padded
        with masked scratch-slot rows — the step shape, and therefore the
        executable, never changes.  Synchronous: each step's assignment is
        converted to ``np.ndarray`` (a device sync) before the next step is
        packed; see ``serve_stream`` for the pipelined loop.
        """
        return self._serve_sync(requests, want_decisions=False)[0]

    def serve_decisions(
            self, requests: Sequence[ServeRequest]) -> List[ServeDecision]:
        """``serve()`` returning full ``ServeDecision``s (assignment + the
        post-step matcher row) — the FL trainers' consumption protocol."""
        return self._serve_sync(requests, want_decisions=True)[1]

    def serve_stream(self, requests: Iterable[Optional[ServeRequest]],
                     autosize: bool = True) -> Iterator[Tuple[int, np.ndarray]]:
        """Pipelined serving: a generator yielding ``(index, assignment)``.

        ``requests`` is any iterable of ``ServeRequest`` — including a lazy
        generator whose side effects (``join``/``leave`` churn) interleave
        with serving — optionally punctuated by ``None`` flush markers that
        dispatch whatever is pending without waiting for a full batch.
        ``index`` is the request's position in the stream (flush markers
        don't count); assignments are bitwise identical to the synchronous
        ``serve()`` loop over the same trace.

        Double-buffered, ONE STEP of latency: while step k runs on device,
        the host packs and dispatches step k+1, and only then converts step
        k's assignment to host memory — request batching and result
        conversion overlap the in-flight device step instead of blocking on
        it.  With ``autosize=True`` the slot batch grows/shrinks with the
        observed queue depth, moving between the AOT-cached ladder
        executables (``warm()`` precompiles them; resizing after warmup
        costs zero recompiles).
        """
        pending: deque = deque()
        # (stream indices, assignment, step, batch size, call returned at
        # while a profiler session is on)
        inflight: Optional[Tuple[List[int], Any, int, int,
                                 Optional[float]]] = None
        it = iter(requests)
        exhausted = False
        draining = False
        next_index = 0
        clock = time.perf_counter
        pulled: Dict[int, float] = {}     # stream index -> pull time
        while True:
            # spans, their metadata and the latency counters only while a
            # profiler session is on
            on = TraceAnnotation.is_enabled()
            # ---- pull from the source until a full batch / flush / end ----
            if not exhausted and not draining and len(pending) < self.slots:
                with (TraceAnnotation("sched.source", step=self._stream_steps)
                      if on else _NO_SPAN):
                    while len(pending) < self.slots:
                        try:
                            rq = next(it)
                        except StopIteration:
                            exhausted = True
                            draining = True
                            break
                        if rq is None:
                            draining = True
                            break
                        if on:
                            pulled[next_index] = clock()
                        pending.append((next_index, rq))
                        next_index += 1

            # ---- dispatch the next step (device work starts now) ----------
            dispatched = None
            if pending and (draining or len(pending) >= self.slots):
                depth = len(pending)
                b = self._pick_size(min(depth, self.slots)) if autosize \
                    else self.slots
                k = self._stream_steps
                with (TraceAnnotation("sched.take_batch", step=k, b=b)
                      if on else _NO_SPAN):
                    batch = self._take_batch(pending, b)
                with (TraceAnnotation("sched.pack", step=k, b=b)
                      if on else _NO_SPAN):
                    args = self._pack(batch, b)
                with (TraceAnnotation("sched.dispatch", step=k, b=b)
                      if on else _NO_SPAN):
                    step = self._get_step(b)
                    self._state, assignment, _ = step(self._state, *args)
                returned = clock() if on else None
                idxs = [i for (i, _, _) in batch]
                if pulled:
                    # requests pulled while no session was on have no time
                    taken = [pulled.pop(i) for i in idxs if i in pulled]
                    if on and taken:
                        self._wait_log.append((returned, taken))
                        self._logged += len(taken)
                        if self._logged >= _LATENCY_BIN_AFTER:
                            self._bin_latencies()
                dispatched = (idxs, assignment, k, b, returned)
                self._served += len(batch)
                self._steps += 1
                self._stream_steps += 1
                self._rows_dispatched += b
                self._sizes_used[b] = self._sizes_used.get(b, 0) + 1
            if draining and not pending and not exhausted:
                draining = False          # flush satisfied; resume pulling

            # ---- retire the PREVIOUS step while this one is in flight -----
            if inflight is not None:
                idxs, asg, k, b, returned = inflight
                with (TraceAnnotation("sched.fetch", step=k, b=b)
                      if on else _NO_SPAN):
                    host = np.asarray(asg)
                if on and returned is not None:
                    self._flight_log.append((clock() - returned, len(idxs)))
                with (TraceAnnotation("sched.deliver", step=k, b=b)
                      if on else _NO_SPAN):
                    for j, i in enumerate(idxs):
                        yield i, host[j]
            inflight = dispatched
            if inflight is None and not pending and exhausted:
                return

    def _bin_latencies(self) -> None:
        """Fold the times ``serve_stream`` logged into the histograms."""
        if self._wait_log:
            per_step = [len(p) for _, p in self._wait_log]
            returned = np.repeat([t for t, _ in self._wait_log], per_step)
            pulled = np.fromiter(
                itertools.chain.from_iterable(p for _, p in self._wait_log),
                np.float64, returned.size)
            self._queue_wait += _bucket_counts(returned - pulled)
            self._wait_log.clear()
            self._logged = 0
        if self._flight_log:
            seconds, requests = zip(*self._flight_log)
            self._inflight += _bucket_counts(seconds, requests)
            self._flight_log.clear()

    def stats(self) -> Dict[str, Any]:
        """The service's counters, in containers of their own, so that a
        snapshot stays as it was while the service goes on.
        ``queue_wait_counts`` and ``inflight_counts`` are ``serve_stream``'s
        latency histograms over the buckets of ``latency_edges_s`` (see
        ``LATENCY_EDGES_S``; ``latency_quantile`` reads a percentile)."""
        self._bin_latencies()
        rows = max(self._rows_dispatched, 1)
        return {"tenants": len(self._tenants), "capacity": self.capacity,
                "rows": self.rows, "slots": self.slots,
                "served": self._served, "steps": self._steps,
                "stream_steps": self._stream_steps,
                "rows_dispatched": self._rows_dispatched,
                "batch_occupancy": self._served / rows,
                "sizes_used": dict(self._sizes_used),
                "bad_rewards": dict(self._bad_rewards),
                "sharded": self.shard,
                "compiles": self.compiles, "compile_s": self.compile_s,
                "latency_edges_s": LATENCY_EDGES_S.tolist(),
                "queue_wait_counts": self._queue_wait.tolist(),
                "inflight_counts": self._inflight.tolist()}
