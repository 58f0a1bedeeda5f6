"""Operations and bytes a Kimi-K2 configuration needs, from its sizes
alone: the yardstick of `decode_step_roofline.kimi`,
`decode_attn_latent_roofline` and `prefill_attn_full_roofline.kimi`.  As
in `flops.py`: what the model requires of real tokens, not what a
compiler or a kernel spends; a multiply-add is two operations.  Sizes
are read under the source's keys, as `references/kimi_k2.py` reads
them."""


def expert_params(config):
    """One expert, routed or shared: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def attention_params(config):
    """W_dq, W_uq, W_dkv, W_ukv, W_o and the two latent norms' gains."""
    C, H = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    return (C * rq + rq + rq * H * (dn + dr) + C * (rkv + dr) + rkv
            + rkv * H * (dn + dv) + H * dv * C)


def non_expert_params(config):
    """Everything a decode step reads once whatever the routing: every
    layer's attention and two gains, layer 0's dense SwiGLU, each expert
    layer's router with its correction bias and its shared expert, the
    final gain and the head.  The embedding row a token looks up is left
    out."""
    C = config["hidden_size"]
    E = config.get("router_experts", config["n_routed_experts"])
    L = config["num_hidden_layers"]
    return (L * (attention_params(config) + 2 * C)
            + 3 * C * config["intermediate_size"]
            + (L - 1) * (E * C + E
                         + config["n_shared_experts"] * expert_params(config))
            + C + config["vocab_size"] * C)


def latent_width(config):
    """Elements the cache holds of a position and layer: the latent and
    the rotated shared key."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def decode_step_bytes(config, itemsize, context_lengths, experts_hit):
    """Bytes one decode step has to read: the non-expert weights once,
    the routed experts its tokens were sent to (``experts_hit``: mean
    distinct held experts a step, an expert layer), and for each live
    row of length n, a layer, its n cached positions."""
    L = config["num_hidden_layers"]
    cache = L * sum(int(n) for n in context_lengths) * latent_width(config)
    return (non_expert_params(config)
            + (L - 1) * experts_hit * expert_params(config)
            + cache) * itemsize


def decode_step_flops(config, rows, pairs, positions):
    """Operations of one decode step of ``rows`` live rows whose tokens
    made ``pairs`` assignments to held routed experts (all layers
    together) and attend to ``positions`` cached positions (all rows and
    layers together)."""
    per_row = non_expert_params(config) - config["hidden_size"] \
        - 2 * config["num_hidden_layers"] * config["hidden_size"]
    return 2 * (rows * per_row + pairs * expert_params(config)) \
        + latent_attn_flops(config, positions)


def latent_attn_bytes(config, positions, itemsize):
    """The absorbed path's reads: each attended position's cache entry,
    once for all heads."""
    return positions * latent_width(config) * itemsize


def latent_attn_flops(config, positions):
    """The absorbed path's operations: each (query, cached position)
    pair costs every head a score over the latent and the rotated key
    and a value product over the latent."""
    return 2 * positions * config["num_attention_heads"] \
        * (latent_width(config) + config["kv_lora_rank"])


def attn_full_flops(config, positions):
    """The expanded path's operations: each (query, key) pair costs
    every head a score over the unrotated and rotated dimensions and a
    value product."""
    return 2 * positions * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])
