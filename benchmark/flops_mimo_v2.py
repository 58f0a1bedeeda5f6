"""Operations and bytes a MiMo-V2 configuration needs, from its sizes
alone: the yardstick of `decode_step_roofline.moe` and
`prefill_moe_experts_roofline`.  As in `flops.py`: what the model
requires, not what a compiler spends; a multiply-add is two operations.
Sizes are read under the source's keys, as `references/mimo_v2.py`
reads them."""


def _kinds(config):
    """[(kv heads, is window, is expert layer)] per layer."""
    return [(config["swa_num_key_value_heads"] if t
             else config["num_key_value_heads"], bool(t), bool(m))
            for t, m in zip(config["hybrid_layer_pattern"],
                            config["moe_layer_freq"])]


def expert_params(config):
    """One expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def attention_params(config, kv_heads):
    C, Hq = config["hidden_size"], config["num_attention_heads"]
    dqk, dv = config["head_dim"], config["v_head_dim"]
    return C * (Hq * dqk + kv_heads * dqk + kv_heads * dv) + Hq * dv * C


def non_expert_params(config):
    """Everything a decode step reads once whatever the routing: each
    layer's attention, its two gains, the sink logits, the dense
    feed-forward or the router with its correction bias; the final gain
    and the head.  The embedding row a token looks up is left out."""
    C = config["hidden_size"]
    E = config.get("router_experts", config["n_routed_experts"])
    total = C + config["vocab_size"] * C
    for kv, window, moe in _kinds(config):
        total += attention_params(config, kv) + 2 * C
        total += config["num_attention_heads"] if window else 0
        total += (E * C + E) if moe \
            else 3 * C * config["intermediate_size"]
    return total


def decode_step_bytes(config, itemsize, context_lengths, experts_hit):
    """Bytes one decode step has to read: the non-expert weights once,
    the experts its tokens were routed to (``experts_hit``: mean
    distinct held experts a step, per expert layer), and of the caches
    the keys and values each live row attends to: a full layer to the
    row's length, a window layer to ``min(length, window)``."""
    per_pos = config["head_dim"] + config["v_head_dim"]
    window = config["sliding_window"]
    layers = _kinds(config)
    cache = 0
    for n in context_lengths:
        for kv, is_window, _ in layers:
            cache += kv * per_pos * (min(int(n), window) if is_window
                                     else int(n))
    experts = sum(1 for _, _, moe in layers if moe) * experts_hit \
        * expert_params(config)
    return (non_expert_params(config) + experts + cache) * itemsize


def decode_step_flops(config, rows, pairs):
    """Operations of one decode step of ``rows`` live rows whose tokens
    made ``pairs`` assignments to held experts (all expert layers
    together); attention over the caches is left out (small beside the
    weights at these lengths)."""
    C = config["hidden_size"]
    E = config.get("router_experts", config["n_routed_experts"])
    per_row = config["vocab_size"] * C
    for kv, _, moe in _kinds(config):
        per_row += attention_params(config, kv)
        per_row += E * C if moe else 3 * C * config["intermediate_size"]
    return 2 * (rows * per_row + pairs * expert_params(config))


def expert_flops(config, pairs):
    """Operations of the held experts for ``pairs`` (token, expert)
    assignments: three products of hidden x expert width each."""
    return 2 * pairs * expert_params(config)
