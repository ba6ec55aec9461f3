"""Work of federated LoRA fine-tuning of DeepSeek-V2-Lite's first stage,
from the configuration's widths (``bench/configs/dsv2lite-fedlora.json``)
and the held experts' routed-token counter.  FLOPs are 2 per
multiply-add of the matmuls; norms, softmax, rotary and the router's
top-k are left out.  Attention scores count the whole T x T product the
model computes (causal masking halves what is needed)."""


def _attn_proj(c):
    """Multiply-adds a token of one layer's MLA projections (no query
    low-rank) and LoRA paths, and of its scores against T positions."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
    shapes = [(d, h * (dn + dr)), (d, r + dr), (r, h * (dn + dv)), (h * dv, d)]
    base = sum(a * b for a, b in shapes)
    lora = sum(c["lora"]["rank"] * (a + b) for a, b in shapes)
    return base, lora


def _tokens(c):
    """(positions a sequence runs, trained tokens a round, proxy tokens a
    round): the leave-one-out estimate runs the proxy set once per
    scheduled client's model."""
    t = c["population"]["tokens_per_sequence"] - 1
    rnd = c["round"]
    m = rnd["n_sched"]
    return (t, m * rnd["local_steps"] * rnd["batch_size"] * t,
            m * rnd["proxy_sequences"] * t)


def expert_flops(c):
    """FLOPs of one routed assignment through one expert's SwiGLU."""
    return 2 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def forward_flops(c, routed_per_token):
    """FLOPs of one token's forward pass, its routed held-expert
    assignments (summed over the MoE layers) given as a mean."""
    d, v, t = c["hidden_size"], c["vocab_size"], _tokens(c)[0]
    h = c["num_attention_heads"]
    dqk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    base, lora = _attn_proj(c)
    scores = h * t * (dqk + c["v_head_dim"])
    layers = c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    moe = layers - dense
    macs = layers * (base + lora + scores)
    macs += dense * 3 * d * c["intermediate_size"]
    macs += moe * (d * c["held_experts"]["routed_over"]
                   + 3 * d * c["n_shared_experts"] * c["moe_intermediate_size"])
    macs += d * v
    return 2 * macs + routed_per_token * expert_flops(c)


def lora_grad_flops(c):
    """FLOPs a token of the adapters' weight gradients (dA and dB)."""
    return 2 * c["num_hidden_layers"] * _attn_proj(c)[1]


def round_flops(c, routed_per_round):
    """Model FLOPs of one round: 2x forward per trained token (the frozen
    weights take no weight gradient, so the backward is one forward's
    work) plus the adapters' weight gradients, and 1x forward per proxy
    token per leave-one-out model; ``routed_per_round`` is the training
    passes' routed held-expert assignments, and the proxy tokens are
    given the same mean."""
    _, trained, proxy = _tokens(c)
    per_token = routed_per_round / trained
    f = forward_flops(c, per_token)
    return trained * (2 * f + lora_grad_flops(c)) + proxy * f


def held_experts_work(c, routed_per_round):
    """``(flops, bytes)`` a round of the held experts' grouped matmuls:
    each trained assignment's three matmuls forward and three for its
    input's gradient, each proxy assignment's three forward (the proxy
    given the training passes' mean); the rows read and written once a
    matmul, in bfloat16, and the held experts' weights once a call (the
    clients of a step, and the leave-one-out models of a proxy chunk, run
    as one call a matmul a layer)."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    _, trained, proxy = _tokens(c)
    rnd = c["round"]
    proxy_routed = routed_per_round * proxy / trained
    mm = 6 * routed_per_round + 3 * proxy_routed
    flops = mm * expert_flops(c) / 3
    row_bytes = 2 * (d + f) * mm
    moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    calls = moe * (6 * rnd["local_steps"]
                   + 3 * rnd["proxy_sequences"] // rnd["proxy_chunk"])
    weight_bytes = 2 * c["n_routed_experts"] * d * f * calls
    return flops, row_bytes + weight_bytes
