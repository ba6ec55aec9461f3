"""Model facade: ``build_model(config) -> Model`` with init / apply / loss /
cache / decode entry points shared by every assigned architecture.

Parameter layout is a flat ``{path: array}`` dict plus a parallel
``{path: logical_spec}`` dict (see layers.ParamBuilder).  Homogeneous
layer stacks live under ``blocks/`` with a leading layer axis and execute
as one ``lax.scan``; heterogeneous layers (hybrid patterns, leading dense
MoE layers) live under ``layers/NN/`` and unroll.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import kvcache, ssm as ssm_mod, rglru as rglru_mod
from repro.models.act_sharding import constrain
from repro.models.layers import ParamBuilder, rms_norm
from repro.models.transformer import (
    add_block_params,
    block_decode,
    block_forward,
    scanned_decode,
    scanned_forward,
    _ffn_is_moe,
)

Params = Dict[str, jnp.ndarray]


def _subtree(params: Params, prefix: str) -> Params:
    pre = prefix + "/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def _sinusoidal_pe(seq: int, d: int, dtype) -> jnp.ndarray:
    """Absolute PE for the encoder path (stands in for hubert's conv-pos stub)."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return pe.astype(dtype)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    remat: str = "full"          # none | full | dots (activation-checkpoint policy)
    ce_chunk: int = 0            # >0: compute the CE loss in sequence chunks of
                                 # this size (rematerialized) so (B, S, V)
                                 # logits never hit HBM — the §Perf fix for
                                 # the unembed/CE traffic term at 100k+ vocab
    seq_shard: bool = False      # sequence-parallel residual stream between
                                 # blocks (Megatron-SP): divides remat-saved
                                 # scan carries by the tensor-axis size

    # ------------------------------------------------------------------ layout
    def _is_hybrid(self) -> bool:
        return bool(self.cfg.layer_pattern)

    def _scanned_layers(self) -> int:
        if self._is_hybrid():
            return 0
        return self.cfg.n_layers - self.cfg.first_k_dense

    def _unrolled(self):
        """Indices of unrolled layers (hybrid: all; else the leading dense ones)."""
        if self._is_hybrid():
            return list(range(self.cfg.n_layers))
        return list(range(self.cfg.first_k_dense))

    # ------------------------------------------------------------------ init
    def param_specs(self) -> Tuple[Params, Dict[str, tuple]]:
        """(ShapeDtypeStruct dict, logical-spec dict) — no allocation."""
        return self._build(None, meta=True)

    def init(self, key: jax.Array) -> Tuple[Params, Dict[str, tuple]]:
        return self._build(key, meta=False)

    def _build(self, key, meta: bool) -> Tuple[Params, Dict[str, tuple]]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        pb = ParamBuilder(key, dtype=dtype, meta=meta)
        if cfg.is_decoder or cfg.vocab_size:
            pb.add("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="embed")
        if not cfg.tie_embeddings:
            pb.add("unembed", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
        pb.add("final_norm", (cfg.d_model,), (None,), init="ones")

        for i in self._unrolled():
            kind = cfg.layer_kind(i)
            add_block_params(
                pb, f"layers/{i:02d}/b", cfg, kind, _ffn_is_moe(cfg, i), stacked=0)
        n_scan = self._scanned_layers()
        if n_scan:
            i0 = cfg.first_k_dense
            kind = cfg.layer_kind(i0)
            add_block_params(
                pb, "blocks/b", cfg, kind, _ffn_is_moe(cfg, i0), stacked=n_scan)
        return pb.params, pb.specs

    # ------------------------------------------------------------------ embedding
    def _embed_inputs(self, params: Params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.arch_type == "audio":
            x = batch["frames"]
            return x + _sinusoidal_pe(x.shape[1], cfg.d_model, x.dtype)[None]
        tok = params["embed"][batch["tokens"]]
        if cfg.arch_type == "vlm" and "vision_embeds" in batch:
            # stub frontend carve-out: pre-computed patch embeddings, prepended
            x = jnp.concatenate([batch["vision_embeds"].astype(tok.dtype), tok], axis=1)
        else:
            x = tok
        return constrain(x, "batch", None, None)

    # ------------------------------------------------------------------ forward
    def apply(
        self, params: Params, batch: Dict[str, jnp.ndarray],
        last_only: bool = False,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-sequence forward.  Returns (logits (B,S,V), moe_aux).

        ``last_only`` unembeds just the final position — the serving-prefill
        path, which avoids materializing (B, S, V) logits at 32k context."""
        x, stats = self._forward_hidden(params, batch)
        aux = stats["balance"]
        if last_only:
            x = x[:, -1:]
        x = constrain(x, "batch", None, None)
        w_out = self._unembed_matrix(params)
        logits = jnp.einsum("bsd,dv->bsv", x, w_out)
        return constrain(logits, "batch", None, "model"), aux

    def _unembed_matrix(self, params: Params) -> jnp.ndarray:
        return params["embed"].T if self.cfg.tie_embeddings else params["unembed"]

    def _forward_hidden(
        self, params: Params, batch: Dict[str, jnp.ndarray]
    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """All blocks + final norm; returns (hidden (B,S,d), stats summed
        over the layers: ``balance``, the MoE load-balance loss, and the
        dropless MoE layers' counters)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        aux: Dict[str, jnp.ndarray] = {}

        def add(a, b):
            return {k: a.get(k, 0) + b.get(k, 0) for k in {**a, **b}}

        for i in self._unrolled():
            kind = cfg.layer_kind(i)
            window = cfg.local_attn_window if kind == "attn" else 0
            sub = _subtree(params, f"layers/{i:02d}")
            x, a = block_forward(sub, "b", x, cfg, kind, _ffn_is_moe(cfg, i), window)
            aux = add(aux, a)

        n_scan = self._scanned_layers()
        if n_scan:
            i0 = cfg.first_k_dense
            kind = cfg.layer_kind(i0)
            window = cfg.local_attn_window if kind == "attn" else 0
            stacked = _subtree(params, "blocks")
            x, a = scanned_forward(
                stacked, x, cfg, kind, _ffn_is_moe(cfg, i0), window, self.remat,
                seq_shard=self.seq_shard)
            aux = add(aux, a)

        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux

    # ------------------------------------------------------------------ loss
    def loss(
        self, params: Params, batch: Dict[str, jnp.ndarray],
        example_weights: Optional[jnp.ndarray] = None,
        adapters: Optional[Params] = None,
    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Mean loss + metrics.  ``example_weights`` (B,) scales per-example
        loss — this is how the FL round folds the transmission mask and the
        zeta aggregation weights (Eq. 7) into one backward pass.

        ``adapters`` (``repro.models.lora``) are trainable LoRA factors on a
        frozen base ``params``: differentiate with respect to them alone.
        With dropless MoE layers (``capacity_factor`` 0) the metrics also
        carry their counters, summed over layers (``moe_routed`` per held
        expert, ``moe_absent``, ``moe_dropped``)."""
        cfg = self.cfg
        if adapters is not None:
            params = {**params, **adapters}
        if cfg.arch_type == "audio":
            hidden, stats = self._forward_hidden(params, batch)
            hid, labels = hidden, batch["labels"]
        else:
            # predict token t+1 from position (offset + t); the last token is
            # only a label, so the T - 1 positions that predict run (causal:
            # their hidden states are those of the whole sequence)
            tokens = batch["tokens"]
            hidden, stats = self._forward_hidden(
                params, {**batch, "tokens": tokens[:, :-1]})
            offset = cfg.frontend_tokens if cfg.arch_type == "vlm" else 0
            hid = hidden[:, offset:]
            labels = tokens[:, 1:]
        aux = stats["balance"]
        w_out = self._unembed_matrix(params)

        nll = self._nll(hid, w_out, labels)            # (B, T)

        if cfg.arch_type == "audio":
            mask = batch["mask"].astype(jnp.float32)
            per_example = jnp.sum(nll * mask, axis=1) / jnp.maximum(mask.sum(1), 1.0)
        else:
            per_example = jnp.mean(nll, axis=1)

        w = example_weights if example_weights is not None else jnp.ones_like(per_example)
        loss = jnp.sum(per_example * w) / jnp.maximum(jnp.sum(w), 1e-9)
        total = loss + cfg.router_aux_weight * aux if cfg.router_aux_weight else loss
        metrics = {"loss": loss, "moe_aux": aux, "per_example": per_example}
        metrics.update({f"moe_{k}": v for k, v in stats.items() if k != "balance"})
        return total, metrics

    def _nll(self, hid: jnp.ndarray, w_out: jnp.ndarray,
             labels: jnp.ndarray) -> jnp.ndarray:
        """Per-position NLL (B, T), optionally in rematerialized seq chunks.

        Cross-entropy via logsumexp minus a one-hot select: both terms reduce
        *over* the vocab axis, so vocab-sharded logits never need an
        all-gather.  With ``ce_chunk`` the (B, C, V) logits of one chunk are
        (re)computed per chunk and never persist — HBM sees the hidden
        states and the unembed matrix only."""

        def nll_dense(h, lab):
            lg = jnp.einsum("btd,dv->btv", h, w_out).astype(jnp.float32)
            lg = constrain(lg, "batch", None, "model")
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            onehot = jax.nn.one_hot(lab, lg.shape[-1], dtype=lg.dtype)
            picked = jnp.sum(lg * onehot, axis=-1)
            return lse - picked

        t = hid.shape[1]
        c = self.ce_chunk
        if c <= 0 or t <= c:
            return nll_dense(hid, labels)
        pad = (-t) % c
        if pad:
            hid = jnp.pad(hid, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
        n = hid.shape[1] // c
        hs = hid.reshape(hid.shape[0], n, c, -1).transpose(1, 0, 2, 3)
        ls = labels.reshape(labels.shape[0], n, c).transpose(1, 0, 2)
        body = jax.checkpoint(nll_dense)
        nll = jax.lax.map(lambda args: body(*args), (hs, ls))
        return nll.transpose(1, 0, 2).reshape(hid.shape[0], -1)[:, :t]

    # ------------------------------------------------------------------ caches
    def init_cache(
        self, batch: int, seq_len: int, window: Optional[int] = None,
        dtype=jnp.bfloat16,
    ) -> Dict[str, Any]:
        """Decode cache for every layer.  ``window`` overrides cfg.sliding_window
        (the serve-time ring-cache option for long contexts)."""
        cfg = self.cfg
        if cfg.is_encoder:
            raise ValueError(f"{cfg.name} is encoder-only: no decode cache")
        win = cfg.sliding_window if window is None else window
        cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}

        def one(kind: str, n_layers: int = 0, local: int = 0):
            w = local or win
            if kind == "attn":
                if cfg.attention == "mla":
                    return kvcache.init_mla_cache(
                        batch, seq_len, cfg.kv_lora_rank, cfg.qk_rope_dim,
                        window=w, n_layers=n_layers, dtype=dtype)
                return kvcache.init_gqa_cache(
                    batch, cfg.n_kv_heads, seq_len, cfg.resolved_head_dim,
                    window=w, n_layers=n_layers, dtype=dtype)
            if kind == "ssm":
                return ssm_mod.init_ssm_cache(batch, cfg, n_layers, dtype)
            return rglru_mod.init_rglru_cache(batch, cfg, n_layers, dtype)

        for i in self._unrolled():
            kind = cfg.layer_kind(i)
            local = cfg.local_attn_window if kind == "attn" else 0
            cache[f"layers/{i:02d}"] = one(kind, 0, local)
        n_scan = self._scanned_layers()
        if n_scan:
            kind = cfg.layer_kind(cfg.first_k_dense)
            cache["blocks"] = one(kind, n_scan)
        return cache

    # ------------------------------------------------------------------ decode
    def decode_step(
        self, params: Params, cache: Dict[str, Any], tokens: jnp.ndarray,
        window: Optional[int] = None,
    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """One serve step: tokens (B,) -> (logits (B,V), cache')."""
        cfg = self.cfg
        win = cfg.sliding_window if window is None else window
        pos = cache["pos"]
        x = params["embed"][tokens][:, None]               # (B,1,d)
        new_cache: Dict[str, Any] = {"pos": pos + 1}

        for i in self._unrolled():
            kind = cfg.layer_kind(i)
            local = cfg.local_attn_window if kind == "attn" else 0
            sub = _subtree(params, f"layers/{i:02d}")
            x, nc = block_decode(
                sub, "b", x, cfg, kind, _ffn_is_moe(cfg, i),
                cache[f"layers/{i:02d}"], pos, window=local or win)
            new_cache[f"layers/{i:02d}"] = nc

        n_scan = self._scanned_layers()
        if n_scan:
            i0 = cfg.first_k_dense
            kind = cfg.layer_kind(i0)
            stacked = _subtree(params, "blocks")
            x, nc = scanned_decode(
                stacked, x, cfg, kind, _ffn_is_moe(cfg, i0), cache["blocks"], pos,
                window=win)
            new_cache["blocks"] = nc

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        w_out = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.einsum("bsd,dv->bsv", x, w_out)[:, 0].astype(jnp.float32)
        return logits, new_cache


def build_model(cfg: ModelConfig, remat: str = "full") -> Model:
    return Model(cfg=cfg, remat=remat)
