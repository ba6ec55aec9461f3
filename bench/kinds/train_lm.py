"""Cells of federated LoRA fine-tuning: ``SparseAsyncFLTrainer.run`` rounds
over a model of the stack (``repro.models``), its base frozen.

Set-up resolves the architecture (``get_config``) before anything else,
then makes, on the device (one jitted call each), the weights with the
reference's ``init`` (the frozen base from the deployment's
``base_seed``, the adapters from the seed; the program is handed them in
the checkpoint's flat-dict layout, checked against its own), the clients'
token rows and the server's proxy rows from the deployment's
``data_seed`` and ``proxy_seed``, and the channels and round keys from
the seed.  It builds the trainer with ``repro.models.lora.fl_loss_fns``
and the mix's aggregator, and drives it through its first
``COMPARED_ROUNDS`` rounds, the base passed as ``run``'s ``frozen``
operand.  The window calls ``run`` back to back, never more than two calls
in flight, keeps each call's MoE counters on the device and blocks on the
state at the end.  Afterwards the plain reference follows those first
rounds from the same inputs and is compared as ``bench/kinds/train.py``
compares the CNN's (``loss_gap``, ``update_gap``, ``change_gap``), and
every round's dropped-token counter is checked (``dropped_tokens``).  Two
rounds, so that the first round's contribution estimate (the leave-one-out
proxy losses) decides the second round's selection, channel matching and
aggregation weights, and the second round carries the first one's slot
buffers.  The reference follows each call from the program's estimate at
its start and compares its own with the program's (``contrib_gap``): the
selection and the matching rank clients by the estimate, and rounding in a
sound program can swap two near-equal priorities, after which the two runs
would schedule different clients for good.

Tokens: each client holds ``sequences_per_client`` rows of
``tokens_per_sequence`` ids drawn from a Zipf law over the vocabulary
slice, mapped through a permutation of its own (its topic), so that
routing is uneven and differs between clients.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import glob
import json
import math
import os
import shutil
import tempfile
import time

import numpy as np

from bench import harness
from bench.kinds.train import (_buffer_leaves, _key, compare, events,
                               fault_half_batch, look, round_keys)

COUNTERS = ("moe_routed", "moe_absent", "moe_dropped")
COMPARED_ROUNDS = 2


def model_config(cfg):
    """The stack's ``ModelConfig`` for the configuration: the registered
    architecture at its published widths, cut to the file's depth, held
    experts and vocabulary slice, with the file's LoRA.  Every published
    number the file repeats must agree with the registry's."""
    from repro.configs import get_config
    base = get_config(cfg["arch"])
    same = {"hidden_size": base.d_model, "num_attention_heads": base.n_heads,
            "intermediate_size": base.d_ff, "moe_intermediate_size": base.d_expert,
            "kv_lora_rank": base.kv_lora_rank, "qk_nope_head_dim": base.qk_nope_dim,
            "qk_rope_head_dim": base.qk_rope_dim, "v_head_dim": base.v_head_dim,
            "n_shared_experts": base.n_shared_experts,
            "num_experts_per_tok": base.experts_per_token,
            "first_k_dense_replace": base.first_k_dense,
            "norm_topk_prob": base.norm_topk_prob,
            "routed_scaling_factor": base.routed_scaling_factor,
            "rope_theta": base.rope_theta, "rms_norm_eps": base.norm_eps}
    bad = {k: (cfg[k], v) for k, v in same.items() if cfg[k] != v}
    held = cfg["held_experts"]
    if bad or held["routed_over"] != base.n_experts:
        raise ValueError(f"configuration disagrees with {cfg['arch']}: {bad}")
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], vocab_size=cfg["vocab_size"],
        held_first=held["first"], held_count=cfg["n_routed_experts"],
        router_aux_weight=cfg["router_aux_weight"],
        lora_rank=cfg["lora"]["rank"], lora_alpha=float(cfg["lora"]["alpha"]))


def _zipf_rows(key, n_rows, shape, vocab, s):
    """``n_rows`` blocks of ``shape`` ids, Zipf(s) ranks over ``vocab`` ids
    mapped through a permutation per block."""
    import jax
    import jax.numpy as jnp
    cdf = jnp.cumsum(jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -s)
    cdf = cdf / cdf[-1]

    def one(k):
        kp, ku = jax.random.split(k)
        perm = jax.random.permutation(kp, vocab).astype(jnp.int32)
        u = jax.random.uniform(ku, shape, jnp.float32)
        rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
        return perm[rank]

    return jax.vmap(one)(jax.random.split(key, n_rows))


def _as_layout(name, given, wanted):
    """``given`` in ``wanted``'s dtypes; raise unless it has exactly
    ``wanted``'s paths and shapes."""
    got = {k: tuple(v.shape) for k, v in given.items()}
    want = {k: tuple(v.shape) for k, v in wanted.items()}
    if got != want:
        bad = sorted(k for k in set(got) | set(want) if got.get(k) != want.get(k))
        raise ValueError(f"{name}: the reference's layout differs from the "
                         f"program's at {bad[:8]}")
    return {k: v.astype(wanted[k].dtype) for k, v in given.items()}


def make_inputs(cfg, seed, model, reference):
    """Clients' tokens and (unused) labels, proxy tokens, channels, the
    base weights and the adapters, on the device.  The base is the
    deployment's pretrained model and the tokens its clients' corpus, the
    same in every run (``base_seed``, ``data_seed``, as the proxy set comes
    from ``proxy_seed``); the adapters, channels and round keys come from
    ``seed``."""
    import jax
    import jax.numpy as jnp
    from repro.models import lora
    pop, rnd, chan = cfg["population"], cfg["round"], cfg["channels"]
    n, rows, t = (pop["n_clients"], pop["sequences_per_client"],
                  pop["tokens_per_sequence"])
    v, s = cfg["vocab_size"], pop["zipf_s"]
    key = _key(seed)
    client_x = jax.jit(lambda k: _zipf_rows(k, n, (rows, t), v, s))(
        _key(cfg["data_seed"]))
    client_y = jnp.zeros((n, rows), jnp.int32)
    proxy_x = jax.jit(lambda k: _zipf_rows(k, 1, (rnd["proxy_sequences"], t),
                                           v, s)[0])(_key(cfg["proxy_seed"]))
    like = jax.eval_shape(lambda k: model.init(k)[0], key)
    like_adapters = jax.eval_shape(functools.partial(
        lora.init_adapters, cfg=model.cfg), like, key=key)

    @jax.jit
    def weights(base_key, adapter_key):
        frozen, adapters = reference.init(cfg, base_key, adapter_key)
        return (_as_layout("base", frozen, like),
                _as_layout("adapters", adapters, like_adapters))
    frozen, adapters = weights(_key(cfg["base_seed"]), jax.random.fold_in(key, 4))
    rng = np.random.default_rng([seed, 0xC4A])
    means = rng.uniform(chan["mean_low"], chan["mean_high"],
                        (chan["segments"], cfg["scheduler"]["n_channels"]))
    breaks = np.arange(1, chan["segments"]) * chan["segment_rounds"]
    return {"client_x": client_x, "client_y": client_y, "proxy_x": proxy_x,
            "means": jnp.asarray(means, jnp.float32),
            "breaks": jnp.asarray(breaks, jnp.int32),
            "frozen": frozen, "adapters": adapters,
            "keys": jax.random.fold_in(key, 3)}


def build_trainer(cfg, mix, inputs, model):
    from repro.core.aggregation import make_aggregator
    from repro.core.availability import MarkovChurn
    from repro.core.bandits import GLRCUCB
    from repro.core.channels import segment_env
    from repro.fl.sparse import SparseAsyncFLTrainer, SparseFLConfig
    from repro.models import lora
    s, rnd, av = cfg["scheduler"], cfg["round"], cfg["availability"]
    loss_fn, proxy_fn = lora.fl_loss_fns(model, inputs["proxy_x"],
                                         inputs["adapters"], rnd["proxy_chunk"])
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
        scheduler=sched, env=segment_env(inputs["means"], inputs["breaks"]),
        loss_fn=loss_fn, proxy_loss_fn=proxy_fn,
        availability=MarkovChurn(p_drop=av["p_drop"], p_rejoin=av["p_rejoin"]),
        aggregator=agg)


class Rounds:
    """The trainer, its state, the rounds it has run and their counters."""

    def __init__(self, cfg, mix, seed, reference, log=None):
        import jax
        from repro.models import build_model
        model = build_model(model_config(cfg), remat="full")
        t = time.perf_counter()
        self.inputs = make_inputs(cfg, seed, model, reference)
        jax.block_until_ready(self.inputs)
        self.split = {"inputs_s": time.perf_counter() - t}
        self.trainer = build_trainer(cfg, mix, self.inputs, model)
        self.per_call = cfg["round"]["rounds_per_call"]
        self.params0 = jax.tree_util.tree_map(np.asarray, self.inputs["adapters"])
        self.state = self.trainer.init(self.inputs["adapters"],
                                       jax.random.fold_in(self.inputs["keys"], 0))
        self.calls = 0
        self._keys = round_keys(jax.random.fold_in(self.inputs["keys"], 1),
                                1024, self.per_call)
        self.counted = []
        t = time.perf_counter()
        self.first_calls = -(-COMPARED_ROUNDS // self.per_call)
        metrics, estimates = [], []
        for _ in range(self.first_calls):
            metrics.append(self.call())
            estimates += [None] * (self.per_call - 1) + [
                (np.asarray(self.state.contrib), np.asarray(self.state.zeta))]
        jax.block_until_ready(self.state)
        self.split["first_calls_s"] = time.perf_counter() - t
        self.first = {
            "estimates": estimates,
            "local_loss": np.concatenate(
                [np.asarray(m["local_loss"]) for m in metrics]),
            "n_success": np.concatenate(
                [np.asarray(m["n_success"]) for m in metrics]),
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
                jax.random.fold_in(self.inputs["keys"], 2 + c), 1024,
                self.per_call)])
        inp = self.inputs
        self.state, metrics = self.trainer.run(
            self.state, inp["client_x"], inp["client_y"], self._keys[c],
            frozen=inp["frozen"])
        self.calls += 1
        self.counted.append({k: metrics[k] for k in COUNTERS + ("local_loss",)})
        return metrics

    def window(self, seconds, on_window=None):
        """Calls back to back for ``seconds``; returns rounds and seconds
        from the window's start to the last round's completion."""
        import jax
        t0 = time.perf_counter()
        if on_window:
            on_window(True)
        calls0, prev = self.calls, None
        self.window_from = len(self.counted)
        while time.perf_counter() - t0 < seconds:
            self.call()
            if prev is not None:
                jax.block_until_ready(prev)
            prev = self.state.t
        if on_window:
            on_window(False)
        jax.block_until_ready(self.state)
        return (self.calls - calls0) * self.per_call, time.perf_counter() - t0

    def counters(self, start=0):
        """The MoE counters summed over the calls from ``start`` on:
        ``routed`` per held expert, ``absent``, ``dropped``; and each
        round's mean local loss."""
        got = [{k: np.asarray(v) for k, v in c.items()}
               for c in self.counted[start:]]
        return {"routed": np.sum([c["moe_routed"].sum(0) for c in got],
                                 axis=0).tolist(),
                "absent": int(sum(c["moe_absent"].sum() for c in got)),
                "dropped": int(sum(c["moe_dropped"].sum() for c in got)),
                "rounds": len(got) * self.per_call,
                "local_loss": [float(x) for c in got
                               for x in np.ravel(c["local_loss"])]}

    def free(self):
        self.state = self.trainer = None
        gc.collect()


def replay(rounds, cfg, reference, dtype):
    """The reference over the program's first calls' rounds, each call
    followed from the program's contribution estimate at its start."""
    inp = rounds.inputs
    keys = [k for c in rounds._keys[:rounds.first_calls] for k in c]
    return reference.replay(
        inp["adapters"], inp["frozen"],
        (inp["client_x"], inp["proxy_x"], inp["means"], inp["breaks"]),
        keys, cfg, dtype, estimates=rounds.first["estimates"])


def contrib_gap(rows, ref_rows):
    """The worst compared round's gap between two contribution estimates
    for the clients the reference selected: norm of the difference over
    the reference's norm.  ``rows`` and ``ref_rows`` hold, per round, the
    selected clients and the estimate for them, or None where a round is
    not compared."""
    gaps = [np.linalg.norm(r[1].astype(np.float64) - q[1]) /
            max(np.linalg.norm(q[1].astype(np.float64)), 1e-30)
            for r, q in zip(rows, ref_rows) if r is not None]
    return float(max(gaps))


def program_rows(first, ref_rows):
    """The program's estimate after each compared round, for the clients
    the reference selected in it."""
    return [None if e is None else (sel, e[0][sel])
            for e, (sel, _) in zip(first["estimates"], ref_rows)]


def run(ctx):
    """One run of a federated LoRA cell; see ``bench/run.py`` for ``ctx``."""
    import jax
    import jax.numpy as jnp
    cfg, mix = ctx.cfg, ctx.mix
    model_config(cfg)            # an unknown architecture fails here, at once
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
               "rounds": done, "elapsed_s": secs,
               "counters": rounds.counters(rounds.window_from)}
        if trace_dir is not None:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            from bench.tracing import Trace
            obs["trace"] = Trace.from_file(glob.glob(
                os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0])
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    everything = rounds.counters()
    ctx.log(f"window: {done} rounds in {secs} s, {counter.count} compiles; "
            f"first rounds' n_success {rounds.first['n_success'].tolist()}; "
            f"counters {json.dumps(obs['counters'])}")
    device = harness.device_record(ctx.devices)
    state_free = rounds.state
    rounds.state = None
    del state_free
    gc.collect()
    ref = replay(rounds, cfg, reference, jnp.float32)
    numbers = compare(rounds.first, ref, rounds.params0)
    numbers["contrib_gap"] = contrib_gap(program_rows(rounds.first, ref[5]),
                                         ref[5])
    numbers["dropped_tokens"] = everything["dropped"]
    checks = {k: {"value": v, "limit": cfg["check"][f"{k}_limit"]}
              for k, v in numbers.items()}
    return {"e2e": {"setup_s": setup_s, "fl_rounds_per_s": done / secs},
            "obs": obs, "checks": checks, "device": device,
            "attempted": done, "failed": 0}


def control(ctx, seeds):
    """Readings for the limits, one line per seed: the program against
    the reference (the lower readings) and the reference in bfloat16 in
    the program's place (the control)."""
    import jax
    import jax.numpy as jnp
    reference = ctx.bench.reference(ctx.cfg["name"])
    rows = []
    for seed in seeds:
        rounds = Rounds(ctx.cfg, ctx.mix, seed, reference)
        rounds.first["events"] = events(rounds.state)
        dropped = rounds.counters()["dropped"]
        rounds.state = None
        gc.collect()
        ref = replay(rounds, ctx.cfg, reference, jnp.float32)
        low = replay(rounds, ctx.cfg, reference, jnp.bfloat16)
        as_program = {"local_loss": np.asarray(low[0]), "params": low[1],
                      "buffers": jax.tree_util.tree_leaves(low[2]),
                      "n_success": np.asarray(low[3]), "events": low[4]}
        program = compare(rounds.first, ref, rounds.params0)
        program["contrib_gap"] = contrib_gap(
            program_rows(rounds.first, ref[5]), ref[5])
        program["dropped_tokens"] = dropped
        control_bf16 = compare(as_program, ref, rounds.params0)
        control_bf16["contrib_gap"] = contrib_gap(
            [o if e is not None else None
             for o, e in zip(low[5], rounds.first["estimates"])], ref[5])
        rows.append({"seed": seed, "program": program,
                     "control_bf16": control_bf16,
                     "look": look(rounds.first, ref[:5], rounds.params0),
                     "look_control": look(as_program, ref[:5], rounds.params0)})
        ctx.log(json.dumps(rows[-1]))
        del rounds
        gc.collect()
    return rows


# Faults of the model, planted in the program underneath the timed path:
# for the tests, and for the readings that bound the limits.
@contextlib.contextmanager
def fault_topk_renormalised():
    """The top-6 router weights are renormalised to sum to one."""
    from unittest import mock
    import repro.models.moe as moe
    route = moe.route

    def renormalised(x, router, cfg):
        return route(x, router, dataclasses.replace(cfg, norm_topk_prob=True))
    with mock.patch.object(moe, "route", renormalised):
        yield


@contextlib.contextmanager
def fault_alpha_over_r_left_out():
    """The adapters' low-rank path is not scaled by alpha / r."""
    from unittest import mock
    import repro.models.lora as lora
    with mock.patch.object(lora, "scale", lambda cfg: 1.0):
        yield


@contextlib.contextmanager
def fault_mscale_left_out():
    """MLA's softmax scale lacks YaRN's mscale^2."""
    from unittest import mock
    import repro.models.attention as attention
    with mock.patch.object(
            attention, "mla_softmax_scale",
            lambda cfg: 1.0 / (cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5):
        yield


@contextlib.contextmanager
def fault_capacity():
    """The held experts drop assignments past a GShard capacity of 1.25:
    per sequence, an expert's assignments past ``ceil(S * k * 1.25 / E)``
    get router weight 0."""
    from unittest import mock
    import jax
    import jax.numpy as jnp
    import repro.models.moe as moe
    route = moe.route

    def capped(x, router, cfg):
        probs, topw, topi = route(x, router, cfg)
        b, s, k = topi.shape
        cap = math.ceil(s * k * 1.25 / cfg.n_experts)
        onehot = jax.nn.one_hot(topi, cfg.n_experts, dtype=jnp.int32)
        pos = jnp.cumsum(onehot.reshape(b, s * k, -1), axis=1).reshape(
            onehot.shape)
        over = jnp.sum(onehot * (pos > cap), axis=-1) > 0
        return probs, jnp.where(over, 0.0, topw), topi
    with mock.patch.object(moe, "route", capped):
        yield


@contextlib.contextmanager
def fault_proxy_left_out():
    """The contribution estimate gets no proxy loss: its error term is 1."""
    from unittest import mock
    import repro.models.lora as lora
    fns = lora.fl_loss_fns

    def no_proxy(*args, **kw):
        return fns(*args, **kw)[0], None
    with mock.patch.object(lora, "fl_loss_fns", no_proxy):
        yield


FAULTS = {"topk_renormalised": fault_topk_renormalised,
          "mscale_left_out": fault_mscale_left_out,
          "capacity": fault_capacity,
          "alpha_over_r_left_out": fault_alpha_over_r_left_out,
          "proxy_left_out": fault_proxy_left_out,
          "half_batch": fault_half_batch}
