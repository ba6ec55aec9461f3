"""Sparse event-driven FL substrate: the client axis at N = 1e5+.

The dense runtime (``repro.fl.round``) sizes every per-client array to the
client count and trains ALL clients each round — exact, but O(N·P) memory
and O(N) training work per round caps it at a few hundred clients.  This
module is the scale-out: per-client state is O(1) *scalars* in (N,)
arrays, and only the M **scheduled** clients per round pay the O(P) cost —
their flattened updates are gathered into the (M, P) slot buffer the
``weighted_aggregate`` kernel consumes, and the results scattered back.
Per-round cost is O(N) element-wise + top-k plus O(M·(E·B + P)) training /
aggregation — independent of N·P.

One round:

  Select   matcher priorities (Eq. 39) over all N clients, masked by the
           availability process's schedulable set, pick the top-M (the
           priorities call does NOT commit matcher state — the round's
           Step-3 ``match`` does, exactly as in the dense runtime).
  Gather   the M selected clients' mini-batches are drawn on device
           (``repro.data.pipeline.client_batch_indices`` — keyed by
           ``fold_in(round_key ⊕ _DATA_TAG, client_id)``, a pure function
           of round and client id) and their carried state gathered into
           (M,) / (M, P) slot rows.
  Round    Steps 1-4 of the dense runtime run verbatim on the M slot rows:
           local SGD, fault injection, Eq. 6 buffer carry, scheduling +
           matching + transmission, quarantine gate, fused Eq. 7
           aggregation, contribution / zeta updates.
  Scatter  per-client scalars (AoI, staleness, has_update, last_success,
           contribution, zeta) scatter back to their (N,) arrays; the slot
           pool turns over to this round's selection.  A slot's previous
           owner that was not re-selected is **evicted**: its buffered G~
           is discarded (``has_update`` revoked) and ``last_success`` set,
           so at its next grant it retrains from the current global model —
           eviction can therefore never starve a client (asserted in
           ``tests/test_sparse_fl.py``).
  Step     the availability state machine advances on this round's grant
           mask (``repro.core.availability`` — one-round observation
           delay), producing the NEXT round's schedulable set.

**Dense parity.**  At M = N with the default always-available substrate,
selection is the identity permutation (top-N of N, sorted), every gather /
scatter is an identity move, and the PRNG layout matches the dense round
(same ``k_env``/``k_sel`` split; data, fault and availability streams live
on their own ``fold_in`` tags — ``_DATA_TAG``, ``_FAULT_TAG``,
``_AVAIL_TAG`` — so attaching none of them leaves the shared streams
untouched).  ``SparseAsyncFLTrainer`` therefore reproduces
``AsyncFLTrainer`` exactly when the dense trainer is fed the same
device-drawn batches (``tests/test_sparse_fl.py`` pins this at paper
scale; ``benchmarks/run.py`` re-checks it on every run and records the
parity bit in BENCH_sim.json).

The (N,)-leading client arrays ride the 1-D "cases" device mesh from
``repro.sim.shard`` — ``repro.sim.shard.shard_clients`` places them with a
``NamedSharding`` over the mesh axis, and every per-client op here is
element-wise or a gather/scatter, so XLA partitions the O(N) work across
devices with no cross-device traffic outside top-k and the (M,) gathers.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aoi import aoi_variance, init_aoi, update_aoi
from repro.core.availability import AvailabilityProcess
from repro.core.bandits.base import init_with_hp
from repro.core.channels import ChannelProcess
from repro.core.contribution import (
    ContributionBuffer,
    aggregation_weights,
    marginal_contribution,
    update_buffer,
)
from repro.core.matching import AdaptiveMatcher, MatcherState, matcher_scores
from repro.data.pipeline import (
    client_batch_indices,
    gather_backends,
    gather_client_batches,
)
from repro.fl.client import local_sgd
from repro.fl.round import _FAULT_TAG, dispatch_aggregate, mean_local_loss
from repro.utils.tree import tree_flatten_concat, tree_unflatten_concat

# fold targets for the sparse-only PRNG streams: the round key's
# k_env/k_sel split stays bitwise identical to the dense runtime whether
# or not data-on-device / availability are in play
_DATA_TAG = 0xDA7A
_AVAIL_TAG = 0xA7A1


class SparseFLState(NamedTuple):
    params: Any                    # global model w_t
    # ---- (M,) / (M, P) slot pool: this round's scheduled clients --------
    buffers: jnp.ndarray           # (M, P) flattened G~ of the slot owners
    slot_clients: jnp.ndarray      # (M,) int32 owner client ids (-1 empty)
    contrib_buf: ContributionBuffer  # (M, P)/(M,) Eq. 41-42 slot rows
    # ---- (N,) per-client scalars ----------------------------------------
    slot_of: jnp.ndarray           # (N,) int32 client -> slot (-1 none)
    has_update: jnp.ndarray        # (N,) G~ validity
    last_success: jnp.ndarray      # (N,) "trains at next grant" indicator
    aoi: jnp.ndarray               # (N,) Eq. 8
    staleness: jnp.ndarray         # (N,) age of the buffered G~ in rounds —
                                   # NOT AoI, which resets only on aggregation
    contrib: jnp.ndarray           # (N,) C~
    zeta: jnp.ndarray              # (N,) aggregation weights
    avail: jnp.ndarray             # (N,) schedulable mask for THIS round
    avail_state: Any               # availability process state ({} if none)
    # ---- shared with the dense runtime ----------------------------------
    sched_state: Any
    matcher_state: MatcherState
    t: jnp.ndarray
    env_state: jnp.ndarray
    fault_state: jnp.ndarray       # fault-schedule carry (dead scalar zero
                                   # for memoryless families / no faults)


class _SparseServedPre(NamedTuple):
    """The pre-decision half of the sparse round (Select + Gather + train +
    Eq.-6 carry + channel realization) for ``run_served`` — everything up
    to the point where the scheduling decision is needed."""

    sel: jnp.ndarray           # (M,) selected client ids, ascending
    avail_sel: jnp.ndarray     # (M,)
    carried_cb: ContributionBuffer
    buffers: jnp.ndarray       # (M, P)
    has_update: jnp.ndarray    # (M,)
    stale_sel: jnp.ndarray     # (M,)
    active: jnp.ndarray        # (M,)
    dropped: jnp.ndarray       # (M,)
    local_losses: jnp.ndarray  # (M,)
    ch_states: jnp.ndarray     # (N,)
    aoi_sel: jnp.ndarray       # (M,) — posted to the server
    contrib_sel: jnp.ndarray   # (M,) — posted to the server
    fault_state: jnp.ndarray   # advanced fault-schedule carry


@dataclasses.dataclass(frozen=True)
class SparseFLConfig:
    n_clients: int                 # N — total population (1e5+ is the point)
    n_sched: int                   # M — clients granted (and slots) per round
    n_channels: int
    batch_size: int                # mini-batch draw per local step
    local_epochs: int = 1
    client_lr: float = 0.05
    server_lr: float = 0.05
    matcher_beta: float = 0.5
    use_matching: bool = True
    use_zeta: bool = True
    quarantine: bool = True
    max_update_norm: float = 0.0
    staleness_cap: int = 0


@dataclasses.dataclass(frozen=True, eq=False)  # identity hash, like the dense
class SparseAsyncFLTrainer:                    # trainer (env holds arrays)
    cfg: SparseFLConfig
    scheduler: Any
    env: Any                       # ChannelEnv | unrealized ChannelProcess
    loss_fn: Callable
    proxy_loss_fn: Optional[Callable] = None
    faults: Optional[Any] = None
    availability: Optional[AvailabilityProcess] = None
    realize_key: Optional[jax.Array] = None
    scenario: Optional[ChannelProcess] = None
    aggregator: Optional[Any] = None  # a repro.core.aggregation Aggregator;
                                   # None: the default zeta-weighted mean

    def __post_init__(self):
        if isinstance(self.env, ChannelProcess):
            object.__setattr__(self, "scenario", self.env)
            key = self.realize_key
            if key is None:
                warnings.warn(
                    "SparseAsyncFLTrainer: ChannelProcess env realized with "
                    "the fixed PRNGKey(0) fallback — all seeds will share "
                    "one realized channel trajectory.  Pass realize_key= "
                    "for per-seed scenario draws.", stacklevel=2)
                key = jax.random.PRNGKey(0)
            object.__setattr__(self, "env", self.env.realize(key))

    # ------------------------------------------------------------------ init
    def init(self, params: Any, key: jax.Array, hp: Any = None) -> SparseFLState:
        cfg = self.cfg
        n, m = cfg.n_clients, cfg.n_sched
        p = int(tree_flatten_concat(params).shape[0])
        if self.availability is not None:
            astate = self.availability.init_state(n)
        else:
            astate = {}
        return SparseFLState(
            params=params,
            buffers=jnp.zeros((m, p), jnp.float32),
            slot_clients=jnp.full((m,), -1, jnp.int32),
            contrib_buf=ContributionBuffer(
                grads=jnp.zeros((m, p), jnp.float32),
                params=jnp.zeros((m, p), jnp.float32),
                fresh=jnp.zeros((m,), jnp.float32),
            ),
            slot_of=jnp.full((n,), -1, jnp.int32),
            has_update=jnp.zeros((n,), jnp.float32),
            last_success=jnp.ones((n,), jnp.float32),  # round 0: all fresh
            aoi=init_aoi(n),
            staleness=jnp.ones((n,), jnp.float32),
            contrib=jnp.ones((n,), jnp.float32),
            zeta=jnp.full((n,), 1.0 / m),   # dense-compatible at M = N
            avail=jnp.ones((n,), jnp.float32),
            avail_state=astate,
            sched_state=init_with_hp(self.scheduler, key, hp),
            matcher_state=AdaptiveMatcher(cfg.matcher_beta).init(),
            t=jnp.zeros((), jnp.int32),
            env_state=self.env.interact_init(),
            fault_state=(self.faults.schedule_init() if self.faults is not None
                         else jnp.zeros((), jnp.float32)),
        )

    def init_batch(self, params, keys, params_axis=None, hp=None,
                   hp_axis=None) -> SparseFLState:
        """Stack B per-seed init states (same contract as the dense
        ``AsyncFLTrainer.init_batch``)."""
        return jax.vmap(self.init, in_axes=(params_axis, 0, hp_axis))(
            params, keys, hp)

    # ---------------------------------------------------------------- select
    def _select(self, state: SparseFLState) -> jnp.ndarray:
        """Top-M schedulable clients by matcher priority, ascending ids.

        A pure read: matcher state is NOT committed here (the round's
        ``match`` call owns that update, as in the dense runtime).  At
        M = N with every client available this is the identity permutation
        regardless of priority values — the dense-parity anchor.
        """
        matcher = AdaptiveMatcher(self.cfg.matcher_beta)
        lam, _ = matcher.priorities(state.matcher_state, state.contrib,
                                    state.aoi)
        masked = jnp.where(state.avail > 0.5, lam, -jnp.inf)
        _, idx = jax.lax.top_k(masked, self.cfg.n_sched)
        return jnp.sort(idx).astype(jnp.int32)

    def _bind(self, frozen):
        """``(loss_fn, proxy_loss_fn)`` with the model's frozen weights bound
        as their last argument (``run``'s ``frozen`` operand)."""
        if frozen is None:
            return self.loss_fn, self.proxy_loss_fn
        proxy = self.proxy_loss_fn
        return (lambda p, x, y: self.loss_fn(p, x, y, frozen),
                None if proxy is None else (lambda flat: proxy(flat, frozen)))

    # ----------------------------------------------------------------- round
    def _round_impl(
        self,
        state: SparseFLState,
        client_x: jnp.ndarray,     # (N, n, ...) device-resident datasets
        client_y: jnp.ndarray,     # (N, n)
        key: jax.Array,
        env: Any = None,
        frozen: Any = None,
        *,
        gather: Tuple[str, str],
    ) -> Tuple[SparseFLState, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        n, m = cfg.n_clients, cfg.n_sched
        if env is None:
            env = self.env
        loss_fn, proxy_loss_fn = self._bind(frozen)
        k_env, k_sel = jax.random.split(key)
        t = state.t

        # ---- Select: top-M schedulable clients --------------------------
        sel = self._select(state)                       # (M,) ascending
        avail_sel = jnp.take(state.avail, sel)
        # carried slot rows: each selected client's previous slot (or -1)
        prev_slot = jnp.take(state.slot_of, sel)
        carry_ok = prev_slot >= 0
        src = jnp.clip(prev_slot, 0, m - 1)
        carried = jnp.where(carry_ok[:, None],
                            jnp.take(state.buffers, src, axis=0), 0.0)
        cb = state.contrib_buf
        carried_cb = ContributionBuffer(
            grads=jnp.where(carry_ok[:, None],
                            jnp.take(cb.grads, src, axis=0), 0.0),
            params=jnp.where(carry_ok[:, None],
                             jnp.take(cb.params, src, axis=0), 0.0),
            fresh=jnp.where(carry_ok, jnp.take(cb.fresh, src), 0.0),
        )

        # ---- Gather: on-device mini-batches for the scheduled clients ---
        k_data = jax.random.fold_in(key, _DATA_TAG)
        idx = client_batch_indices(k_data, sel, int(client_y.shape[1]),
                                   cfg.local_epochs, cfg.batch_size)
        batches_x, batches_y = gather_client_batches(
            client_x, client_y, sel, idx, gather)

        # ---- Steps 1-2: local training for granted clients in S_{t-1} ---
        def one_client(bx, by):
            g_tree, *rest = local_sgd(loss_fn, state.params, bx, by,
                                      cfg.client_lr)
            return (tree_flatten_concat(g_tree), *rest)

        fresh_updates, local_losses, *counters = jax.vmap(one_client)(
            batches_x, batches_y)

        if self.faults is not None:
            k_fault = jax.random.fold_in(key, _FAULT_TAG)
            fresh_updates, dropped, fault_state = self.faults.inject_sched(
                k_fault, t, fresh_updates, state.fault_state)
        else:
            dropped = jnp.zeros((m,), jnp.float32)
            fault_state = state.fault_state

        # Eq. 6 on the slot rows (`where`, not lerp — see the dense round);
        # an unavailable-but-granted client (availability-scarce rounds)
        # neither trains nor transmits
        active = jnp.where(avail_sel > 0.5,
                           jnp.take(state.last_success, sel) * (1.0 - dropped),
                           0.0)
        buffers = jnp.where(active[:, None] > 0.5, fresh_updates, carried)
        has_update = jnp.maximum(jnp.take(state.has_update, sel), active)
        stale_sel = jnp.where(active > 0.5, 1.0,
                              jnp.take(state.staleness, sel) + 1.0)

        # ---- Step 3: schedule + match + transmit ------------------------
        aoi_sel = jnp.take(state.aoi, sel)
        contrib_sel = jnp.take(state.contrib, sel)
        channels, aux = self.scheduler.select(state.sched_state, t, k_sel,
                                              aoi_sel)
        matcher = AdaptiveMatcher(cfg.matcher_beta)
        if cfg.use_matching:
            scores = matcher_scores(self.scheduler, state.sched_state, t, env)
            assignment, matcher_state = matcher.match(
                state.matcher_state, channels, scores, contrib_sel, aoi_sel)
        else:
            assignment = channels
            _, matcher_state = matcher.priorities(
                state.matcher_state, contrib_sel, aoi_sel)
        ch_states = env.sample_dyn(t, k_env, state.env_state)
        sched_mask = jnp.zeros((cfg.n_channels,), jnp.float32)
        sched_mask = sched_mask.at[assignment].set(1.0)
        env_state = env.interact_step(state.env_state, t, sched_mask)
        success = (ch_states[assignment] > 0.5).astype(jnp.float32)
        success = success * has_update
        success = success * (1.0 - dropped)
        success = jnp.where(avail_sel > 0.5, success, 0.0)

        # ---- Step 4: quarantine gate + aggregate (Eq. 7) ----------------
        if cfg.quarantine:
            row_ok = jnp.all(jnp.isfinite(buffers), axis=1)
            if cfg.max_update_norm > 0.0:
                row_ok = row_ok & (
                    jnp.linalg.norm(buffers, axis=1) <= cfg.max_update_norm)
            row_ok = row_ok.astype(jnp.float32)
        else:
            row_ok = jnp.ones((m,), jnp.float32)
        if cfg.staleness_cap > 0:
            fresh_ok = (stale_sel <= float(cfg.staleness_cap)).astype(jnp.float32)
        else:
            fresh_ok = jnp.ones((m,), jnp.float32)
        agg_mask = success * row_ok * fresh_ok
        n_succ = jnp.sum(agg_mask)

        zeta = (jnp.take(state.zeta, sel) if cfg.use_zeta
                else jnp.full((m,), 1.0 / m))
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
        last_success_sel = jnp.maximum(agg_mask,
                                       jnp.maximum(bad_row, stale_reject))

        # ---- contribution / zeta on the slot rows -----------------------
        rewards = ch_states[assignment]
        sched_state = self.scheduler.update(state.sched_state, t, assignment,
                                            rewards, aux)
        params_flat = tree_flatten_concat(params)
        contrib_buf = update_buffer(
            carried_cb, agg_mask > 0.5, agg_buffers,
            jnp.broadcast_to(params_flat, buffers.shape))
        contrib_rows = marginal_contribution(contrib_buf, zeta,
                                             proxy_loss_fn)
        zeta_rows = aggregation_weights(contrib_rows)

        # ---- Scatter: per-client scalars + slot ownership turnover ------
        active_full = jnp.zeros((n,), jnp.float32).at[sel].set(active)
        agg_full = jnp.zeros((n,), jnp.float32).at[sel].set(agg_mask)
        aoi = update_aoi(state.aoi, agg_full > 0.5)
        staleness = jnp.where(active_full > 0.5, 1.0, state.staleness + 1.0)
        staleness = staleness.at[sel].set(stale_sel)

        # slot ownership: the pool turns over to this round's selection
        clear_idx = jnp.where(state.slot_clients >= 0, state.slot_clients, n)
        slot_of = state.slot_of.at[clear_idx].set(-1, mode="drop")
        slot_of = slot_of.at[sel].set(jnp.arange(m, dtype=jnp.int32))
        # eviction: previous owners not re-selected lose their buffered G~
        # and re-enter S_t so their next grant retrains (starvation-free)
        prev = state.slot_clients
        still = jnp.where(prev >= 0,
                          jnp.take(slot_of, jnp.clip(prev, 0, n - 1)) >= 0,
                          True)
        evicted = (prev >= 0) & ~still
        evict_ids = jnp.where(evicted, prev, n)

        has_update_full = state.has_update.at[sel].set(has_update)
        has_update_full = has_update_full.at[evict_ids].set(0.0, mode="drop")
        last_success = state.last_success.at[sel].set(last_success_sel)
        last_success = last_success.at[evict_ids].set(1.0, mode="drop")
        contrib_full = state.contrib.at[sel].set(contrib_rows)
        zeta_full = state.zeta.at[sel].set(zeta_rows)

        # ---- availability state machine: advance on this round's grants -
        if self.availability is not None:
            k_avail = jax.random.fold_in(key, _AVAIL_TAG)
            grant_full = jnp.zeros((n,), jnp.float32).at[sel].set(
                jnp.where(avail_sel > 0.5, 1.0, 0.0))
            avail_state, avail = self.availability.step(
                k_avail, t, state.avail_state, grant_full)
        else:
            avail_state, avail = state.avail_state, state.avail

        new_state = SparseFLState(
            params=params,
            buffers=buffers,
            slot_clients=sel,
            contrib_buf=contrib_buf,
            slot_of=slot_of,
            has_update=has_update_full,
            last_success=last_success,
            aoi=aoi,
            staleness=staleness,
            contrib=contrib_full,
            zeta=zeta_full,
            avail=avail,
            avail_state=avail_state,
            sched_state=sched_state,
            matcher_state=matcher_state,
            t=t + 1,
            env_state=env_state,
            fault_state=fault_state,
        )
        metrics = {
            "local_loss": mean_local_loss(local_losses, active),
            "n_success": n_succ,
            "mean_aoi": jnp.mean(aoi),
            "aoi_var": aoi_variance(aoi),
            "beta_t": matcher_state.beta_t,
            "zeta_max": jnp.max(zeta_rows),
            "n_evicted": jnp.sum(evicted.astype(jnp.float32)),
            "n_available": jnp.sum(state.avail),
        }
        for c in counters:
            metrics.update(jax.tree_util.tree_map(
                lambda a: jnp.sum(a, axis=0), c))
        return new_state, metrics

    @functools.partial(jax.jit, static_argnames=("self", "gather"))
    def _round_jit(self, state, client_x, client_y, key, env, gather):
        return self._round_impl(state, client_x, client_y, key, env,
                                gather=gather)

    def round(self, state, client_x, client_y, key):
        return self._round_jit(state, client_x, client_y, key, self.env,
                               gather_backends(client_x, client_y))

    # ------------------------------------------------------------------- run
    def _run_impl(self, state, client_x, client_y, keys, env=None,
                  frozen=None, *, gather):
        def step(st, k):
            return self._round_impl(st, client_x, client_y, k, env, frozen,
                                    gather=gather)

        return jax.lax.scan(step, state, keys)

    def _run_vmapped(self, states, client_x, client_y, keys,
                     envs=None, env_axis=None, frozen=None, *, gather):
        """Seed-batched round scan; client datasets broadcast across seeds.

        The one traced program both entry points share (``run`` at batch 1)
        — same bitwise-parity rationale as the dense
        ``AsyncFLTrainer._run_vmapped``.  The gather kernel takes every
        seed's scheduled ids in one call over the shared dataset.
        """
        if envs is None:
            envs, env_axis = self.env, None

        def one(state, ks, env):
            return self._run_impl(state, client_x, client_y, ks, env, frozen,
                                  gather=gather)

        return jax.vmap(one, in_axes=(0, 0, env_axis))(states, keys, envs)

    @functools.partial(jax.jit, static_argnames=("self", "gather"))
    def _run_plain(self, state, client_x, client_y, keys, env, gather,
                   frozen=None):
        lift = functools.partial(jax.tree_util.tree_map, lambda x: x[None])
        out = self._run_vmapped(lift(state), client_x, client_y, keys[None],
                                envs=env, frozen=frozen, gather=gather)
        return jax.tree_util.tree_map(lambda x: x[0], out)

    def run(
        self,
        state: SparseFLState,
        client_x: jnp.ndarray,     # (N, n, ...) full per-client datasets
        client_y: jnp.ndarray,     # (N, n)
        keys: jnp.ndarray,         # (R,) per-round PRNG keys
        frozen: Any = None,        # the model's frozen weights, if any
    ) -> Tuple[SparseFLState, Dict[str, jnp.ndarray]]:
        """Fuse R sparse FL rounds into one ``lax.scan`` XLA program.

        Unlike the dense ``run``, round data is not an (R, M, ...) operand:
        each round draws its scheduled clients' batches on device from the
        resident (N, n, ...) datasets, so host memory never scales with
        R · N.

        Layout contract: the datasets are read where and as they are
        stored, and never copied.  A uint8 or int32 array on one TPU stored
        client-minor (the client axis most minor, as a TPU lays out
        u8[100000, 64, 784]) has its scheduled rows read in place by the
        ``client_gather`` kernel; any other layout, a dataset sharded over
        devices, or a CPU takes XLA's gather.  The choice is read from the
        arrays here (``repro.data.pipeline.gather_backends``) and compiled
        in; the bytes each round trains on are the same either way.

        ``frozen``: a pytree of weights the model reads and nobody trains,
        such as the base of LoRA fine-tuning, where ``state.params`` holds
        the adapters.  It is an operand of the compiled program and is
        bound inside the trace: the local steps call ``loss_fn(params, x,
        y, frozen)`` and the contribution estimate ``proxy_loss_fn(flat,
        frozen)``.  Closed over instead, JAX would embed the arrays in the
        executable as constants.  With ``frozen=None`` (the default) the
        two take their usual arguments and the program is the one
        compiled without the operand.
        """
        return self._run_plain(state, client_x, client_y, keys, self.env,
                               gather_backends(client_x, client_y), frozen)

    # ------------------------------------------------- served (SchedServer)
    def _served_pre_impl(self, state, client_x, client_y, key, env, gather):
        """Select + Gather + Steps 1-2 + the Eq.-6 slot carry + channel
        realization — ``_round_impl``'s pre-decision dataflow, verbatim."""
        cfg = self.cfg
        m = cfg.n_sched
        k_env, _ = jax.random.split(key)
        t = state.t

        sel = self._select(state)
        avail_sel = jnp.take(state.avail, sel)
        prev_slot = jnp.take(state.slot_of, sel)
        carry_ok = prev_slot >= 0
        src = jnp.clip(prev_slot, 0, m - 1)
        carried = jnp.where(carry_ok[:, None],
                            jnp.take(state.buffers, src, axis=0), 0.0)
        cb = state.contrib_buf
        carried_cb = ContributionBuffer(
            grads=jnp.where(carry_ok[:, None],
                            jnp.take(cb.grads, src, axis=0), 0.0),
            params=jnp.where(carry_ok[:, None],
                             jnp.take(cb.params, src, axis=0), 0.0),
            fresh=jnp.where(carry_ok, jnp.take(cb.fresh, src), 0.0),
        )

        k_data = jax.random.fold_in(key, _DATA_TAG)
        idx = client_batch_indices(k_data, sel, int(client_y.shape[1]),
                                   cfg.local_epochs, cfg.batch_size)
        batches_x, batches_y = gather_client_batches(
            client_x, client_y, sel, idx, gather)

        def one_client(bx, by):
            g_tree, loss = local_sgd(self.loss_fn, state.params, bx, by,
                                     cfg.client_lr)[:2]
            return tree_flatten_concat(g_tree), loss

        fresh_updates, local_losses = jax.vmap(one_client)(batches_x, batches_y)
        if self.faults is not None:
            k_fault = jax.random.fold_in(key, _FAULT_TAG)
            fresh_updates, dropped, fault_state = self.faults.inject_sched(
                k_fault, t, fresh_updates, state.fault_state)
        else:
            dropped = jnp.zeros((m,), jnp.float32)
            fault_state = state.fault_state
        active = jnp.where(avail_sel > 0.5,
                           jnp.take(state.last_success, sel) * (1.0 - dropped),
                           0.0)
        buffers = jnp.where(active[:, None] > 0.5, fresh_updates, carried)
        has_update = jnp.maximum(jnp.take(state.has_update, sel), active)
        stale_sel = jnp.where(active > 0.5, 1.0,
                              jnp.take(state.staleness, sel) + 1.0)
        ch_states = env.sample_dyn(t, k_env, state.env_state)
        return _SparseServedPre(
            sel=sel, avail_sel=avail_sel, carried_cb=carried_cb,
            buffers=buffers, has_update=has_update, stale_sel=stale_sel,
            active=active, dropped=dropped, local_losses=local_losses,
            ch_states=ch_states, aoi_sel=jnp.take(state.aoi, sel),
            contrib_sel=jnp.take(state.contrib, sel),
            fault_state=fault_state)

    def _served_post_impl(self, state, pre, assignment, matcher_state, key,
                          env):
        """Steps 3 (post-decision) + 4 + scatter + availability, given the
        server's assignment and post-step matcher row; the trainer's
        ``sched_state`` leaf is carried unchanged (the server owns it)."""
        cfg = self.cfg
        n, m = cfg.n_clients, cfg.n_sched
        t = state.t
        sel, avail_sel = pre.sel, pre.avail_sel
        buffers, has_update, stale_sel = (pre.buffers, pre.has_update,
                                          pre.stale_sel)
        active, dropped = pre.active, pre.dropped

        sched_mask = jnp.zeros((cfg.n_channels,), jnp.float32)
        sched_mask = sched_mask.at[assignment].set(1.0)
        env_state = env.interact_step(state.env_state, t, sched_mask)
        success = (pre.ch_states[assignment] > 0.5).astype(jnp.float32)
        success = success * has_update
        success = success * (1.0 - dropped)
        success = jnp.where(avail_sel > 0.5, success, 0.0)

        if cfg.quarantine:
            row_ok = jnp.all(jnp.isfinite(buffers), axis=1)
            if cfg.max_update_norm > 0.0:
                row_ok = row_ok & (
                    jnp.linalg.norm(buffers, axis=1) <= cfg.max_update_norm)
            row_ok = row_ok.astype(jnp.float32)
        else:
            row_ok = jnp.ones((m,), jnp.float32)
        if cfg.staleness_cap > 0:
            fresh_ok = (stale_sel <= float(cfg.staleness_cap)).astype(jnp.float32)
        else:
            fresh_ok = jnp.ones((m,), jnp.float32)
        agg_mask = success * row_ok * fresh_ok
        n_succ = jnp.sum(agg_mask)

        zeta = (jnp.take(state.zeta, sel) if cfg.use_zeta
                else jnp.full((m,), 1.0 / m))
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
        last_success_sel = jnp.maximum(agg_mask,
                                       jnp.maximum(bad_row, stale_reject))

        params_flat = tree_flatten_concat(params)
        contrib_buf = update_buffer(
            pre.carried_cb, agg_mask > 0.5, agg_buffers,
            jnp.broadcast_to(params_flat, buffers.shape))
        contrib_rows = marginal_contribution(contrib_buf, zeta,
                                             self.proxy_loss_fn)
        zeta_rows = aggregation_weights(contrib_rows)

        active_full = jnp.zeros((n,), jnp.float32).at[sel].set(active)
        agg_full = jnp.zeros((n,), jnp.float32).at[sel].set(agg_mask)
        aoi = update_aoi(state.aoi, agg_full > 0.5)
        staleness = jnp.where(active_full > 0.5, 1.0, state.staleness + 1.0)
        staleness = staleness.at[sel].set(stale_sel)

        clear_idx = jnp.where(state.slot_clients >= 0, state.slot_clients, n)
        slot_of = state.slot_of.at[clear_idx].set(-1, mode="drop")
        slot_of = slot_of.at[sel].set(jnp.arange(m, dtype=jnp.int32))
        prev = state.slot_clients
        still = jnp.where(prev >= 0,
                          jnp.take(slot_of, jnp.clip(prev, 0, n - 1)) >= 0,
                          True)
        evicted = (prev >= 0) & ~still
        evict_ids = jnp.where(evicted, prev, n)

        has_update_full = state.has_update.at[sel].set(has_update)
        has_update_full = has_update_full.at[evict_ids].set(0.0, mode="drop")
        last_success = state.last_success.at[sel].set(last_success_sel)
        last_success = last_success.at[evict_ids].set(1.0, mode="drop")
        contrib_full = state.contrib.at[sel].set(contrib_rows)
        zeta_full = state.zeta.at[sel].set(zeta_rows)

        if self.availability is not None:
            k_avail = jax.random.fold_in(key, _AVAIL_TAG)
            grant_full = jnp.zeros((n,), jnp.float32).at[sel].set(
                jnp.where(avail_sel > 0.5, 1.0, 0.0))
            avail_state, avail = self.availability.step(
                k_avail, t, state.avail_state, grant_full)
        else:
            avail_state, avail = state.avail_state, state.avail

        new_state = SparseFLState(
            params=params,
            buffers=buffers,
            slot_clients=sel,
            contrib_buf=contrib_buf,
            slot_of=slot_of,
            has_update=has_update_full,
            last_success=last_success,
            aoi=aoi,
            staleness=staleness,
            contrib=contrib_full,
            zeta=zeta_full,
            avail=avail,
            avail_state=avail_state,
            sched_state=state.sched_state,
            matcher_state=matcher_state,
            t=t + 1,
            env_state=env_state,
            fault_state=pre.fault_state,
        )
        metrics = {
            "local_loss": mean_local_loss(pre.local_losses, active),
            "n_success": n_succ,
            "mean_aoi": jnp.mean(aoi),
            "aoi_var": aoi_variance(aoi),
            "beta_t": matcher_state.beta_t,
            "zeta_max": jnp.max(zeta_rows),
            "n_evicted": jnp.sum(evicted.astype(jnp.float32)),
            "n_available": jnp.sum(state.avail),
        }
        return new_state, metrics

    @functools.partial(jax.jit, static_argnames=("self", "gather"))
    def _served_pre_jit(self, state, client_x, client_y, key, env, gather):
        lift = functools.partial(jax.tree_util.tree_map, lambda x: x[None])

        def one(s, k):
            return self._served_pre_impl(s, client_x, client_y, k, env,
                                         gather)

        out = jax.vmap(one)(lift(state), key[None])
        return jax.tree_util.tree_map(lambda x: x[0], out)

    @functools.partial(jax.jit, static_argnames=("self",))
    def _served_post_jit(self, state, pre, assignment, matcher_state, key,
                         env):
        lift = functools.partial(jax.tree_util.tree_map, lambda x: x[None])

        def one(s, p, a, ms, k):
            return self._served_post_impl(s, p, a, ms, k, env)

        out = jax.vmap(one)(lift(state), lift(pre), assignment[None],
                            lift(matcher_state), key[None])
        return jax.tree_util.tree_map(lambda x: x[0], out)

    def run_served(
        self,
        state: SparseFLState,
        client_x: jnp.ndarray,     # (N, n, ...) full per-client datasets
        client_y: jnp.ndarray,     # (N, n)
        keys: jnp.ndarray,         # (R,) per-round PRNG keys
        server,                    # a repro.sim.SchedServer
        tenant,
    ) -> Tuple[SparseFLState, Dict[str, jnp.ndarray]]:
        """Run R sparse rounds consuming schedules from ``server``.

        The trainer selects its top-M clients, trains them, and posts the
        realized channel vector, round key, the SELECTED clients'
        contributions and AoI (the (M,) slices the fused round feeds the
        scheduler/matcher) — the server answers with the (M,) assignment
        and matcher row.  ``tenant`` must be joined with this trainer's
        scheduler init key/hp; the served trajectory then reproduces the
        standalone ``run()`` bitwise (``tests/test_fl_served.py``), with
        the policy state living in the server's tenant row.
        """
        # the dense trainer's validation logic applies verbatim — the
        # server's client dim must equal the slot count M = n_sched
        from repro.fl.round import AsyncFLTrainer
        AsyncFLTrainer._validate_server(self, server,
                                        n_clients=self.cfg.n_sched)
        from repro.sim.serve import ServeRequest   # deferred: sim imports fl

        r = int(keys.shape[0])
        gather = gather_backends(client_x, client_y)
        metrics_rounds = []
        for i in range(r):
            k = keys[i]
            pre = self._served_pre_jit(state, client_x, client_y, k, self.env,
                                       gather)
            dec = server.serve_decisions([ServeRequest(
                tenant, rewards=np.asarray(pre.ch_states),
                key=np.asarray(k), contrib=np.asarray(pre.contrib_sel),
                aoi=np.asarray(pre.aoi_sel))])[0]
            mstate = MatcherState(
                v_max=jnp.asarray(dec.matcher_state.v_max),
                a_max=jnp.asarray(dec.matcher_state.a_max),
                beta_t=jnp.asarray(dec.matcher_state.beta_t))
            state, mets = self._served_post_jit(
                state, pre, jnp.asarray(dec.assignment), mstate, k, self.env)
            metrics_rounds.append(mets)
        metrics = {k2: jnp.stack([mm[k2] for mm in metrics_rounds])
                   for k2 in metrics_rounds[0]}
        return state, metrics
