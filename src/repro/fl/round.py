"""Asynchronous FL round runtime (Sec. II-A Steps 1-4 + Sec. IV/V policies).

One round, entirely inside jit:

  Step 1  clients in S_{t-1} receive w_t (everyone else trains nothing and
          keeps its buffered update G~, Eq. 6)
  Step 2  E local SGD epochs, vmapped over clients (Eq. 5); an optional
          ``FaultProcess`` (``repro.core.faults``) then corrupts the fresh
          updates / drops clients — injected exactly between local
          training and the Eq.-6 buffer carry, where real client-side
          failures live
  Step 3  MAB scheduler picks M channels; the adaptive matcher assigns
          them to clients by priority (Eq. 39-40); the channel env draws
          Good/Bad (closed-loop forms read — and are then advanced with —
          the carried interaction state); S_t = clients whose channel was
          Good
  Step 4  server aggregates  w <- w - eta_s/|S_t| * sum_{i in S_t} zeta_i G~_i
          via the fused `weighted_aggregate` kernel (Eq. 7), updates AoI
          (Eq. 8), the contribution buffers (Eq. 41-42), zeta (Eq. 43)
          and the bandit statistics.

          With ``cfg.quarantine`` (default on), Step 4 is gated by a
          graceful-degradation mask: buffer rows that are non-finite or
          (with ``cfg.max_update_norm > 0``) norm-exploded are zeroed out
          of the aggregation, their ``has_update`` is revoked (the
          poisoned G~ is discarded) and the owner re-enters S_t so it
          retrains and retries at its next successful schedule.  A
          staleness cap (``cfg.staleness_cap > 0``) additionally rejects
          buffered updates older than tau rounds (Hu et al.-style age
          cutoff) — rejected-but-delivered clients also re-enter S_t.
          AoI resets only on *aggregated* deliveries, and an all-Bad round
          is a bitwise no-op on ``params`` (a ``where`` on |S_t| > 0, not
          an add of zero — adding 0.0 would still flip -0.0 bits).

Client updates are carried *flattened* (M, P) — the same layout the
contribution estimator needs, and the layout the Pallas aggregation
kernel consumes.  This dense runtime sizes every per-client array to
``cfg.n_clients`` and trains ALL clients each round (Steps 1-2 iterate the
full client set); for the sparse event-driven client axis at N = 1e5+ —
(N,) per-client scalars, (M,) slot buffers gathered per round, an
``AvailabilityProcess`` state machine gating who is schedulable — see
``repro.fl.sparse``, which reproduces this runtime exactly at M = N.

The channel env is a *traced operand* of every compiled entry point (not a
closure constant): ``run``/``round`` pass ``self.env`` at call time, and
the batched engine (``repro.sim.simulate_fl_batch``) accepts stacked
per-case envs, so sweep buckets share one executable across trainers that
differ only in env values or scheduler traced scalars (see
``bucket_signature``).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aoi import init_aoi, update_aoi, aoi_variance
from repro.core.bandits.base import init_with_hp
from repro.core.contribution import (
    ContributionBuffer,
    aggregation_weights,
    init_buffer,
    marginal_contribution,
    update_buffer,
)
from repro.core.channels import ChannelProcess
from repro.core.matching import AdaptiveMatcher, MatcherState, matcher_scores
from repro.fl.client import local_sgd
from repro.kernels import ops
from repro.utils.tree import tree_flatten_concat, tree_unflatten_concat

# fold target for the per-round fault key: keeps the env/select PRNG splits
# bitwise identical whether or not a FaultProcess is attached
_FAULT_TAG = 0xFA17


def dispatch_aggregate(aggregator, buffers, mask, zeta, n_succ):
    """Step-4 aggregation dispatch shared by the dense and sparse runtimes.

    ``aggregator=None`` is the default zeta-weighted masked mean (Eq. 7)
    inlined exactly as the pre-registry code wrote it — same ops, same
    order, so legacy trainers stay bitwise.  Anything else is a
    ``repro.core.aggregation.Aggregator`` (``MeanAgg`` reproduces this
    default bitwise; the robust families trade zeta weighting for
    Byzantine tolerance).  ``buffers`` arrive quarantine-masked; returns
    the (P,) f32 aggregate (zeros when nothing participates).
    """
    if aggregator is None:
        m = buffers.shape[0]
        scale = mask * zeta * (m / jnp.maximum(n_succ, 1.0))
        return ops.weighted_aggregate(buffers, scale)
    return aggregator.aggregate(buffers, mask, zeta, n_succ)


def mean_local_loss(local_losses, active):
    """The ``local_loss`` metric: mean final local loss over the clients that
    trained this round, shared by the dense and sparse runtimes.

    The isfinite guard keeps the *metric* finite even while a faulty
    client's loss blows up (identical arithmetic on healthy rounds).  The
    sum is a fixed pairwise tree of elementwise adds behind an optimization
    barrier, not a reduce: the compiler picks a reduce's association per
    program (fusion, layout), which drifted the metric by an ulp between
    the dense and sparse programs at M = N = 20 while every summand agreed
    bitwise."""
    loss_ok = jnp.isfinite(local_losses).astype(jnp.float32)
    x = jax.lax.optimization_barrier(
        jnp.where(loss_ok > 0.5, local_losses, 0.0) * active)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = jnp.concatenate([x[..., :h] + x[..., h:2 * h], x[..., 2 * h:]],
                            axis=-1)
    return x[..., 0] / jnp.maximum(jnp.sum(active * loss_ok), 1.0)


class AsyncFLState(NamedTuple):
    params: Any                    # global model w_t
    buffers: jnp.ndarray           # (M, P) flattened G~_i (Eq. 6)
    has_update: jnp.ndarray        # (M,) G~ validity
    last_success: jnp.ndarray      # (M,) S_{t-1} indicator
    aoi: jnp.ndarray               # (M,)
    contrib_buf: ContributionBuffer
    contrib: jnp.ndarray           # (M,) C~
    zeta: jnp.ndarray              # (M,) aggregation weights
    sched_state: Any
    matcher_state: MatcherState
    t: jnp.ndarray
    env_state: jnp.ndarray         # (N,) closed-loop interaction carry (dead
                                   # zeros for open-loop canonical forms)
    staleness: jnp.ndarray         # (M,) age of the buffered G~ in rounds —
                                   # NOT AoI, which resets only on aggregation
    fault_state: jnp.ndarray       # fault-schedule carry (burst/Markov on-off;
                                   # dead scalar zero for memoryless families
                                   # and faultless trainers)


class _ServedPre(NamedTuple):
    """Everything a round computes BEFORE the scheduling decision — the
    half of ``_round_impl`` that runs trainer-side when the decision itself
    comes from a ``SchedServer`` (``run_served``).  ``ch_states`` is the
    realized channel vector the trainer posts as the request's rewards."""

    buffers: jnp.ndarray       # (M, P) post-Eq.-6 carry
    has_update: jnp.ndarray    # (M,)
    staleness: jnp.ndarray     # (M,)
    active: jnp.ndarray        # (M,)
    dropped: jnp.ndarray       # (M,)
    local_losses: jnp.ndarray  # (M,)
    ch_states: jnp.ndarray     # (N,) realized Good/Bad vector
    fault_state: jnp.ndarray   # advanced fault-schedule carry


@dataclasses.dataclass(frozen=True)
class AsyncFLConfig:
    n_clients: int
    n_channels: int
    local_epochs: int = 1
    client_lr: float = 0.05
    server_lr: float = 0.05        # eta_s (Eq. 7 uses the raw G~ sum; see DESIGN)
    matcher_beta: float = 0.5
    use_matching: bool = True      # ablation switch (paper's "aware allocation")
    use_zeta: bool = True          # ablation: Eq. 43 weights vs uniform
    # graceful degradation (Step 4 gate).  quarantine=True is numerically
    # identical to the legacy path on healthy data — it only changes which
    # rows *could* aggregate, and healthy rows always pass.
    quarantine: bool = True        # mask non-finite buffer rows out of Eq. 7
    max_update_norm: float = 0.0   # >0: also quarantine rows with ||G~|| above
    staleness_cap: int = 0         # >0: reject buffered G~ older than tau rounds


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: identity hash, so the
class AsyncFLTrainer:                          # jitted round caches per instance
    cfg: AsyncFLConfig                         # (env holds arrays -> unhashable
    scheduler: Any                 # a repro.core.bandits Scheduler   by value)
    env: Any                       # a repro.core.channels ChannelEnv, or an
                                   # unrealized ChannelProcess (realized at
                                   # construction from ``realize_key``; see
                                   # __post_init__ for the PRNGKey(0) fallback)
    loss_fn: Callable              # (params, x, y) -> scalar loss
    proxy_loss_fn: Optional[Callable] = None  # flat params -> scalar (Eq. 35)
    faults: Optional[Any] = None   # a repro.core.faults FaultProcess, or None
    realize_key: Optional[jax.Array] = None   # scenario realization key —
                                   # derive per seed (scenario_realize_key)
                                   # so Monte-Carlo seeds draw distinct
                                   # channel trajectories
    scenario: Optional[ChannelProcess] = None  # set by __post_init__ when env
                                   # was handed in unrealized; the sweep
                                   # driver re-realizes it per case from
                                   # scenario_realize_key(case.init_key)
    aggregator: Optional[Any] = None  # a repro.core.aggregation Aggregator;
                                   # None means the default zeta-weighted
                                   # mean (bitwise-identical to MeanAgg)

    def __post_init__(self):
        if isinstance(self.env, ChannelProcess):
            object.__setattr__(self, "scenario", self.env)
            key = self.realize_key
            if key is None:
                # Documented fallback: direct construction without a key
                # realizes ONE trajectory from PRNGKey(0).  Every seed of a
                # multi-seed simulate_fl_batch run then shares that single
                # realized channel table — fine for a quick smoke run,
                # wrong for Monte-Carlo error bars.  Pass realize_key=
                # scenario_realize_key(seed_key), or hand FLSweepCases to
                # repro.sim.sweep, which derives per-case keys exactly like
                # the regret sweep path does.
                warnings.warn(
                    "AsyncFLTrainer: ChannelProcess env realized with the "
                    "fixed PRNGKey(0) fallback — all seeds will share one "
                    "realized channel trajectory.  Pass realize_key= for "
                    "per-seed scenario draws (repro.sim.sweep derives "
                    "per-case keys automatically).",
                    stacklevel=2)
                key = jax.random.PRNGKey(0)
            object.__setattr__(self, "env", self.env.realize(key))

    def bucket_signature(self) -> Tuple:
        """Value-based identity for sweep bucketing and executable caching.

        Two trainer *instances* with equal signatures lower to the same
        compiled program: the structural parts (cfg, scheduler
        ``hp_signature``, env canonical shapes, loss/proxy function
        identity, fault and aggregator instances) specialize the trace,
        while scheduler
        traced scalars ride the state ``hp`` pytree and env arrays enter as
        operands — so equal-signature trainers share one bucket and one
        executable, with their differing values stacked on the batch axis.
        (``AsyncFLTrainer`` itself still hashes by identity — its env holds
        arrays — which is why this is a method, not ``__hash__``.)
        """
        sig = getattr(self.scheduler, "hp_signature", None)
        sched_sig = sig() if sig is not None else self.scheduler
        if self.scenario is not None:
            env_sig = ("scenario",) + self.scenario.env_signature()
        else:
            leaves, treedef = jax.tree_util.tree_flatten(self.env)
            env_sig = (treedef, tuple(
                (tuple(jnp.shape(l)), str(jnp.result_type(l))) for l in leaves))
        return ("async_fl", self.cfg, sched_sig, env_sig, self.loss_fn,
                self.proxy_loss_fn, self.faults, self.aggregator)

    # ------------------------------------------------------------------ init
    def init(self, params: Any, key: jax.Array, hp: Any = None) -> AsyncFLState:
        m = self.cfg.n_clients
        p = int(tree_flatten_concat(params).shape[0])
        # the state owns copies of the caller's arrays: ``run`` donates the
        # state off-CPU, which would otherwise delete them
        own = functools.partial(jax.tree_util.tree_map, jnp.array)
        return AsyncFLState(
            params=own(params),
            buffers=jnp.zeros((m, p), jnp.float32),
            has_update=jnp.zeros((m,), jnp.float32),
            last_success=jnp.ones((m,), jnp.float32),   # round 0: all start fresh
            aoi=init_aoi(m),
            contrib_buf=init_buffer(m, p),
            contrib=jnp.ones((m,), jnp.float32),
            zeta=jnp.full((m,), 1.0 / m),
            sched_state=init_with_hp(self.scheduler, key, own(hp)),
            matcher_state=AdaptiveMatcher(self.cfg.matcher_beta).init(),
            t=jnp.zeros((), jnp.int32),
            env_state=self.env.interact_init(),
            staleness=jnp.ones((m,), jnp.float32),
            fault_state=(self.faults.schedule_init() if self.faults is not None
                         else jnp.zeros((), jnp.float32)),
        )

    def init_batch(
        self,
        params: Any,
        keys: jax.Array,
        params_axis: int | None = None,
        hp: Any = None,
        hp_axis: int | None = None,
    ) -> AsyncFLState:
        """Stack B independent init states — the input format of the batched
        FL engine (``repro.sim.simulate_fl_batch``).

        ``keys`` carries a leading (B,) axis of per-seed init keys; every leaf
        of the returned state gains the same leading (B,) axis.  ``params`` is
        broadcast to all batch entries by default; pass ``params_axis=0`` for
        per-seed initial models (leaves pre-stacked on a leading axis).

        ``hp`` optionally overrides the scheduler's traced hyper-parameters
        (``scheduler.params()`` pytree): a stacked grid with ``hp_axis=0``
        turns the batch axis into a scheduler *tuning* axis — B grid points
        training through ONE ``simulate_fl_batch`` program — while
        ``hp_axis=None`` broadcasts a single override across the batch.
        """
        return jax.vmap(self.init, in_axes=(params_axis, 0, hp_axis))(
            params, keys, hp)

    # ------------------------------------------------------------------ round
    def _round_impl(
        self,
        state: AsyncFLState,
        batches_x: jnp.ndarray,    # (M, E, B, ...)
        batches_y: jnp.ndarray,    # (M, E, B)
        key: jax.Array,
        env: Any = None,           # traced ChannelEnv operand (None: self.env,
                                   # baked as a trace constant)
    ) -> Tuple[AsyncFLState, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        m = cfg.n_clients
        if env is None:
            env = self.env
        k_env, k_sel = jax.random.split(key)
        t = state.t

        # ---- Steps 1-2: local training for clients in S_{t-1} ------------
        def one_client(bx, by):
            g_tree, loss = local_sgd(self.loss_fn, state.params, bx, by, cfg.client_lr)
            return tree_flatten_concat(g_tree), loss

        fresh_updates, local_losses = jax.vmap(one_client)(batches_x, batches_y)

        # ---- fault injection: between training and the Eq.-6 carry ---------
        if self.faults is not None:
            # the fault stream lives on its own fold of the round key, so a
            # faultless trainer's PRNG consumption is bitwise untouched; the
            # schedule carry (burst/Markov on-off) advances once per round —
            # memoryless families pass it through and consume the key
            # identically to the stateless inject()
            k_fault = jax.random.fold_in(key, _FAULT_TAG)
            fresh_updates, dropped, fault_state = self.faults.inject_sched(
                k_fault, t, fresh_updates, state.fault_state)
        else:
            dropped = jnp.zeros((m,), jnp.float32)
            fault_state = state.fault_state

        # Eq. 6 via `where`, not the arithmetic lerp: a corrupted fresh row
        # must not leak NaN into an inactive client's kept buffer (0 * NaN).
        # A dropped client neither refreshes its buffer nor transmits.
        active = state.last_success * (1.0 - dropped)
        buffers = jnp.where(active[:, None] > 0.5, fresh_updates, state.buffers)
        has_update = jnp.maximum(state.has_update, active)
        staleness = jnp.where(active > 0.5, 1.0, state.staleness + 1.0)

        # ---- Step 3: schedule + match + transmit ---------------------------
        channels, aux = self.scheduler.select(state.sched_state, t, k_sel, state.aoi)
        matcher = AdaptiveMatcher(cfg.matcher_beta)
        if cfg.use_matching:
            # score source routed by the scenario's regime metadata (UCB
            # under stochastic regimes, historical mean under "mean"-hint
            # deterministic/adversarial ones — Eq. 30 vs Eq. 31)
            scores = matcher_scores(
                self.scheduler, state.sched_state, t, env)
            assignment, matcher_state = matcher.match(
                state.matcher_state, channels, scores, state.contrib, state.aoi)
        else:
            assignment = channels
            _, matcher_state = matcher.priorities(
                state.matcher_state, state.contrib, state.aoi)
        # closed-loop API: identical to env.sample(t, k_env) for open-loop
        # forms; reactive envs read the carried interaction state (schedules
        # up to t-1 — one-round observation delay) and then advance it with
        # the channels the matcher actually used this round
        ch_states = env.sample_dyn(t, k_env, state.env_state)
        sched_mask = jnp.zeros((cfg.n_channels,), jnp.float32)
        sched_mask = sched_mask.at[assignment].set(1.0)
        env_state = env.interact_step(state.env_state, t, sched_mask)
        success = (ch_states[assignment] > 0.5).astype(jnp.float32)
        success = success * has_update        # a client with no update yet can't help
        success = success * (1.0 - dropped)   # and a dropped one can't transmit

        # ---- Step 4: quarantine gate + aggregate (Eq. 7, fused kernel) ------
        if cfg.quarantine:
            row_ok = jnp.all(jnp.isfinite(buffers), axis=1)
            if cfg.max_update_norm > 0.0:
                row_ok = row_ok & (
                    jnp.linalg.norm(buffers, axis=1) <= cfg.max_update_norm)
            row_ok = row_ok.astype(jnp.float32)
        else:
            row_ok = jnp.ones((m,), jnp.float32)
        if cfg.staleness_cap > 0:
            fresh_ok = (staleness <= float(cfg.staleness_cap)).astype(jnp.float32)
        else:
            fresh_ok = jnp.ones((m,), jnp.float32)
        agg_mask = success * row_ok * fresh_ok
        n_succ = jnp.sum(agg_mask)

        zeta = state.zeta if cfg.use_zeta else jnp.full((m,), 1.0 / m)
        if cfg.quarantine:
            # zero quarantined rows BEFORE the aggregator: 0 * NaN = NaN, so
            # a zero aggregation weight alone cannot contain a poisoned row
            agg_buffers = jnp.where(agg_mask[:, None] > 0.5, buffers, 0.0)
        else:
            agg_buffers = buffers
        agg_flat = dispatch_aggregate(
            self.aggregator, agg_buffers, agg_mask, zeta, n_succ)  # (P,) f32
        step_vec = -cfg.server_lr / m * agg_flat              # normalized mean step
        delta = tree_unflatten_concat(step_vec, state.params)
        if cfg.quarantine:
            # all-Bad/all-quarantined round: bitwise no-op on params (adding
            # a zero delta would still flip -0.0 bits)
            any_agg = n_succ > 0.0
            params = jax.tree_util.tree_map(
                lambda p_, d: jnp.where(any_agg, p_ + d.astype(p_.dtype), p_),
                state.params, delta)
        else:
            params = jax.tree_util.tree_map(
                lambda p_, d: (p_ + d.astype(p_.dtype)), state.params, delta)

        # degraded-path bookkeeping: poisoned buffers are discarded (the
        # owner must retrain before it can transmit again), and quarantined
        # or stale-rejected-but-delivered clients re-enter S_t so they retry
        # with a fresh update at their next successful schedule — without
        # the re-grant they could never regain has_update and would starve.
        bad_row = 1.0 - row_ok
        stale_reject = success * row_ok * (1.0 - fresh_ok)
        has_update = has_update * row_ok
        last_success = jnp.maximum(agg_mask, jnp.maximum(bad_row, stale_reject))

        # ---- bookkeeping: AoI, bandit, contribution, zeta -------------------
        # AoI resets only on *aggregated* deliveries — a quarantined or stale
        # upload improved nobody's freshness at the server
        aoi = update_aoi(state.aoi, agg_mask > 0.5)
        rewards = ch_states[assignment]
        sched_state = self.scheduler.update(
            state.sched_state, t, assignment, rewards, aux)
        # buffered params each client last trained from (for Eq. 42): current
        # global params serve as the anchor — uploads happened this round.
        params_flat = tree_flatten_concat(params)
        contrib_buf = update_buffer(
            state.contrib_buf, agg_mask > 0.5, agg_buffers,
            jnp.broadcast_to(params_flat, buffers.shape))
        contrib = marginal_contribution(contrib_buf, zeta, self.proxy_loss_fn)
        new_zeta = aggregation_weights(contrib)

        new_state = AsyncFLState(
            params=params,
            buffers=buffers,
            has_update=has_update,
            last_success=last_success,
            aoi=aoi,
            contrib_buf=contrib_buf,
            contrib=contrib,
            zeta=new_zeta,
            sched_state=sched_state,
            matcher_state=matcher_state,
            t=t + 1,
            env_state=env_state,
            staleness=staleness,
            fault_state=fault_state,
        )
        metrics = {
            "local_loss": mean_local_loss(local_losses, active),
            "n_success": n_succ,
            "mean_aoi": jnp.mean(aoi),
            "aoi_var": aoi_variance(aoi),
            "beta_t": matcher_state.beta_t,
            "zeta_max": jnp.max(new_zeta),
        }
        return new_state, metrics

    @functools.partial(jax.jit, static_argnames=("self",))
    def _round_jit(self, state, batches_x, batches_y, key, env):
        return self._round_impl(state, batches_x, batches_y, key, env)

    def round(
        self,
        state: AsyncFLState,
        batches_x: jnp.ndarray,    # (M, E, B, ...)
        batches_y: jnp.ndarray,    # (M, E, B)
        key: jax.Array,
    ) -> Tuple[AsyncFLState, Dict[str, jnp.ndarray]]:
        return self._round_jit(state, batches_x, batches_y, key, self.env)

    # ------------------------------------------------------------------ run
    def _run_impl(self, state, batches_x, batches_y, keys, env=None):
        def step(st, inp):
            bx, by, k = inp
            return self._round_impl(st, bx, by, k, env)

        return jax.lax.scan(step, state, (batches_x, batches_y, keys))

    def _run_vmapped(self, states, batches_x, batches_y, keys,
                     envs=None, env_axis=None):
        """Seed-batched round scan: vmap of ``_run_impl`` over a leading axis.

        This is the ONE program both entry points trace: ``run`` executes it
        at batch 1 (axes added/stripped at the jit boundary) and
        ``repro.sim.simulate_fl_batch`` at batch B.  Sharing the traced
        computation is what makes batch-of-1 engine output *bitwise* equal
        to the serial path: XLA is free to fuse a forward-loss reduction
        differently for (M,) vs (1, M) operands (observed: 1-ulp drift in
        the ``local_loss`` metric), so the serial path must lower the
        batched shapes too, not just the same Python code.

        ``envs``/``env_axis`` feed the channel env as a traced operand:
        ``env_axis=0`` maps stacked per-case envs over the batch (the sweep
        bucket path — trainers differing only in env values share this one
        program), ``None`` broadcasts a single env across the batch.
        ``envs=None`` broadcasts ``self.env``.
        """
        if envs is None:
            envs, env_axis = self.env, None

        def one(state, bx, by, ks, env):
            return self._run_impl(state, bx, by, ks, env)

        return jax.vmap(one, in_axes=(0, 0, 0, 0, env_axis))(
            states, batches_x, batches_y, keys, envs)

    # Two jitted variants: the donated one reuses the carried state's buffers
    # in place (the (M, P) update matrix dominates memory), but XLA:CPU does
    # not implement donation and would warn on every compile — so `run`
    # donates only where donation exists.
    @functools.partial(jax.jit, static_argnames=("self",), donate_argnums=(1,))
    def _run_donated(self, state, batches_x, batches_y, keys, env):
        return self._run_batch1(state, batches_x, batches_y, keys, env)

    @functools.partial(jax.jit, static_argnames=("self",))
    def _run_plain(self, state, batches_x, batches_y, keys, env):
        return self._run_batch1(state, batches_x, batches_y, keys, env)

    def _run_batch1(self, state, batches_x, batches_y, keys, env=None):
        lift = functools.partial(jax.tree_util.tree_map, lambda x: x[None])
        out = self._run_vmapped(lift(state), batches_x[None], batches_y[None],
                                keys[None],
                                envs=self.env if env is None else env)
        return jax.tree_util.tree_map(lambda x: x[0], out)

    def run(
        self,
        state: AsyncFLState,
        batches_x: jnp.ndarray,    # (R, M, E, B, ...) — R rounds of client data
        batches_y: jnp.ndarray,    # (R, M, E, B)
        keys: jnp.ndarray,         # (R,) per-round PRNG keys
        n_rounds: Optional[int] = None,
    ) -> Tuple[AsyncFLState, Dict[str, jnp.ndarray]]:
        """Fuse ``n_rounds`` FL rounds into one ``lax.scan`` XLA program.

        Semantically identical to ``n_rounds`` sequential ``round()`` calls
        with ``keys[t]`` per round, but with no host round-trip between
        rounds: metrics come back as device-resident (R,) arrays (one sync
        when the caller reads them) and, on backends that support donation
        (TPU/GPU), the input state buffers are donated to the output.

        ``n_rounds`` is optional validation sugar — the actual round count is
        the leading axis of ``keys``/``batches_*``.
        """
        r = int(keys.shape[0])
        if n_rounds is not None and n_rounds != r:
            raise ValueError(f"run: n_rounds={n_rounds} != leading axis {r}")
        if int(batches_x.shape[0]) != r or int(batches_y.shape[0]) != r:
            raise ValueError(
                f"run: batches leading axis {batches_x.shape[0]} != keys {r}")
        fn = self._run_plain if jax.default_backend() == "cpu" else self._run_donated
        return fn(state, batches_x, batches_y, keys, self.env)

    # ------------------------------------------------- served (SchedServer)
    def _served_pre_impl(self, state, batches_x, batches_y, key, env):
        """Steps 1-2 + the Eq.-6 carry + the channel realization — the
        exact pre-decision dataflow of ``_round_impl`` (same PRNG layout:
        the select half of the round key belongs to the server)."""
        cfg = self.cfg
        m = cfg.n_clients
        k_env, _ = jax.random.split(key)
        t = state.t

        def one_client(bx, by):
            g_tree, loss = local_sgd(self.loss_fn, state.params, bx, by,
                                     cfg.client_lr)
            return tree_flatten_concat(g_tree), loss

        fresh_updates, local_losses = jax.vmap(one_client)(batches_x, batches_y)
        if self.faults is not None:
            k_fault = jax.random.fold_in(key, _FAULT_TAG)
            fresh_updates, dropped, fault_state = self.faults.inject_sched(
                k_fault, t, fresh_updates, state.fault_state)
        else:
            dropped = jnp.zeros((m,), jnp.float32)
            fault_state = state.fault_state
        active = state.last_success * (1.0 - dropped)
        buffers = jnp.where(active[:, None] > 0.5, fresh_updates, state.buffers)
        has_update = jnp.maximum(state.has_update, active)
        staleness = jnp.where(active > 0.5, 1.0, state.staleness + 1.0)
        ch_states = env.sample_dyn(t, k_env, state.env_state)
        return _ServedPre(buffers=buffers, has_update=has_update,
                          staleness=staleness, active=active, dropped=dropped,
                          local_losses=local_losses, ch_states=ch_states,
                          fault_state=fault_state)

    def _served_post_impl(self, state, pre, assignment, matcher_state, env):
        """Steps 3 (post-decision) + 4 + bookkeeping, given the server's
        assignment and post-step matcher row.  The scheduler state is the
        SERVER's responsibility — the trainer's ``sched_state`` leaf is
        carried unchanged (dead weight kept for pytree stability)."""
        cfg = self.cfg
        m = cfg.n_clients
        t = state.t
        buffers, has_update, staleness = (pre.buffers, pre.has_update,
                                          pre.staleness)
        sched_mask = jnp.zeros((cfg.n_channels,), jnp.float32)
        sched_mask = sched_mask.at[assignment].set(1.0)
        env_state = env.interact_step(state.env_state, t, sched_mask)
        success = (pre.ch_states[assignment] > 0.5).astype(jnp.float32)
        success = success * has_update
        success = success * (1.0 - pre.dropped)

        if cfg.quarantine:
            row_ok = jnp.all(jnp.isfinite(buffers), axis=1)
            if cfg.max_update_norm > 0.0:
                row_ok = row_ok & (
                    jnp.linalg.norm(buffers, axis=1) <= cfg.max_update_norm)
            row_ok = row_ok.astype(jnp.float32)
        else:
            row_ok = jnp.ones((m,), jnp.float32)
        if cfg.staleness_cap > 0:
            fresh_ok = (staleness <= float(cfg.staleness_cap)).astype(jnp.float32)
        else:
            fresh_ok = jnp.ones((m,), jnp.float32)
        agg_mask = success * row_ok * fresh_ok
        n_succ = jnp.sum(agg_mask)

        zeta = state.zeta if cfg.use_zeta else jnp.full((m,), 1.0 / m)
        if cfg.quarantine:
            agg_buffers = jnp.where(agg_mask[:, None] > 0.5, buffers, 0.0)
        else:
            agg_buffers = buffers
        agg_flat = dispatch_aggregate(
            self.aggregator, agg_buffers, agg_mask, zeta, n_succ)
        step_vec = -cfg.server_lr / m * agg_flat
        delta = tree_unflatten_concat(step_vec, state.params)
        if cfg.quarantine:
            any_agg = n_succ > 0.0
            params = jax.tree_util.tree_map(
                lambda p_, d: jnp.where(any_agg, p_ + d.astype(p_.dtype), p_),
                state.params, delta)
        else:
            params = jax.tree_util.tree_map(
                lambda p_, d: (p_ + d.astype(p_.dtype)), state.params, delta)

        bad_row = 1.0 - row_ok
        stale_reject = success * row_ok * (1.0 - fresh_ok)
        has_update = has_update * row_ok
        last_success = jnp.maximum(agg_mask, jnp.maximum(bad_row, stale_reject))

        aoi = update_aoi(state.aoi, agg_mask > 0.5)
        params_flat = tree_flatten_concat(params)
        contrib_buf = update_buffer(
            state.contrib_buf, agg_mask > 0.5, agg_buffers,
            jnp.broadcast_to(params_flat, buffers.shape))
        contrib = marginal_contribution(contrib_buf, zeta, self.proxy_loss_fn)
        new_zeta = aggregation_weights(contrib)

        new_state = AsyncFLState(
            params=params,
            buffers=buffers,
            has_update=has_update,
            last_success=last_success,
            aoi=aoi,
            contrib_buf=contrib_buf,
            contrib=contrib,
            zeta=new_zeta,
            sched_state=state.sched_state,
            matcher_state=matcher_state,
            t=t + 1,
            env_state=env_state,
            staleness=staleness,
            fault_state=pre.fault_state,
        )
        metrics = {
            "local_loss": mean_local_loss(pre.local_losses, pre.active),
            "n_success": n_succ,
            "mean_aoi": jnp.mean(aoi),
            "aoi_var": aoi_variance(aoi),
            "beta_t": matcher_state.beta_t,
            "zeta_max": jnp.max(new_zeta),
        }
        return new_state, metrics

    # Both served halves lower at batch 1 through a vmap, exactly like
    # `_run_batch1` — sharing the batched shapes is what keeps the served
    # trajectory bitwise-equal to `run()` (see `_run_vmapped`'s rationale).
    @functools.partial(jax.jit, static_argnames=("self",))
    def _served_pre_jit(self, state, batches_x, batches_y, key, env):
        lift = functools.partial(jax.tree_util.tree_map, lambda x: x[None])

        def one(s, bx, by, k):
            return self._served_pre_impl(s, bx, by, k, env)

        out = jax.vmap(one)(lift(state), batches_x[None], batches_y[None],
                            key[None])
        return jax.tree_util.tree_map(lambda x: x[0], out)

    @functools.partial(jax.jit, static_argnames=("self",))
    def _served_post_jit(self, state, pre, assignment, matcher_state, env):
        lift = functools.partial(jax.tree_util.tree_map, lambda x: x[None])

        def one(s, p, a, ms):
            return self._served_post_impl(s, p, a, ms, env)

        out = jax.vmap(one)(lift(state), lift(pre), assignment[None],
                            lift(matcher_state))
        return jax.tree_util.tree_map(lambda x: x[0], out)

    def _validate_server(self, server, n_clients: Optional[int] = None) -> None:
        m = self.cfg.n_clients if n_clients is None else n_clients
        if not (self.cfg.use_matching and server.use_matching):
            raise ValueError(
                "run_served: requires use_matching=True on both the trainer "
                "cfg and the SchedServer (the server's non-matching path "
                "owns AoI semantics the trainer cannot override)")
        if float(server.matcher_beta) != float(self.cfg.matcher_beta):
            raise ValueError(
                f"run_served: matcher_beta mismatch (trainer "
                f"{self.cfg.matcher_beta}, server {server.matcher_beta})")
        if (server.scheduler.n_channels != self.cfg.n_channels
                or server.scheduler.n_clients != m):
            raise ValueError(
                f"run_served: server scheduler dims "
                f"(N={server.scheduler.n_channels}, "
                f"M={server.scheduler.n_clients}) do not match the trainer "
                f"(N={self.cfg.n_channels}, M={m})")
        want = "mean" if (getattr(self.env, "score_kind", "ucb") == "mean"
                          and getattr(self.scheduler, "mean_scores", None)
                          is not None) else "ucb"
        if server.score_kind != want:
            raise ValueError(
                f"run_served: this trainer's env routes matcher scores via "
                f"{want!r} but the server was built with "
                f"score_kind={server.score_kind!r}")

    def run_served(
        self,
        state: AsyncFLState,
        batches_x: jnp.ndarray,    # (R, M, E, B, ...)
        batches_y: jnp.ndarray,    # (R, M, E, B)
        keys: jnp.ndarray,         # (R,) per-round PRNG keys
        server,                    # a repro.sim.SchedServer
        tenant,
    ) -> Tuple[AsyncFLState, Dict[str, jnp.ndarray]]:
        """Run R rounds consuming the scheduling decision from ``server``.

        Each round the trainer computes Steps 1-2 locally, posts its
        realized channel vector, round key, contributions and AoI to the
        server (``ServeRequest``), and finishes Steps 3-4 with the returned
        assignment and matcher row — many trainers this way share ONE
        scheduler service.  ``tenant`` must already be joined (join it with
        this trainer's scheduler init key/hp to reproduce ``run()``: the
        served trajectory is then bitwise identical to the standalone scan,
        with the policy state living in the server's tenant row instead of
        ``state.sched_state``).  Closed-loop envs work — the trainer owns
        the env and posts realized vectors, so the feedback loop never
        leaves the trainer.
        """
        self._validate_server(server)
        from repro.sim.serve import ServeRequest   # deferred: sim imports fl

        r = int(keys.shape[0])
        if int(batches_x.shape[0]) != r or int(batches_y.shape[0]) != r:
            raise ValueError(
                f"run_served: batches leading axis {batches_x.shape[0]} != "
                f"keys {r}")
        metrics_rounds = []
        for i in range(r):
            k = keys[i]
            pre = self._served_pre_jit(state, batches_x[i], batches_y[i], k,
                                       self.env)
            dec = server.serve_decisions([ServeRequest(
                tenant, rewards=np.asarray(pre.ch_states),
                key=np.asarray(k), contrib=np.asarray(state.contrib),
                aoi=np.asarray(state.aoi))])[0]
            mstate = MatcherState(
                v_max=jnp.asarray(dec.matcher_state.v_max),
                a_max=jnp.asarray(dec.matcher_state.a_max),
                beta_t=jnp.asarray(dec.matcher_state.beta_t))
            state, mets = self._served_post_jit(
                state, pre, jnp.asarray(dec.assignment), mstate, self.env)
            metrics_rounds.append(mets)
        metrics = {k2: jnp.stack([mm[k2] for mm in metrics_rounds])
                   for k2 in metrics_rounds[0]}
        return state, metrics
