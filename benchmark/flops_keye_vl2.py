"""Operations and bytes a Keye-VL-2.0 configuration needs, from its sizes
alone: the yardstick of `decode_step_roofline.keye`,
`prefill_attn_sparse_roofline` and `prefill_attn_index_roofline`.  As in
`flops.py`: what the model requires of real tokens, not what a compiler
or a kernel spends; a multiply-add is two operations.  Sizes are read
under the source's keys, as `references/keye_vl2.py` reads them."""


def expert_params(config):
    """One expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config):
    """A layer without its experts: attention with its two QK-norm gains,
    the indexer with its LayerNorm, the two RMSNorm gains, the router."""
    C, d = config["hidden_size"], config["head_dim"]
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    sa = config["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    E = config.get("router_experts", config["num_experts"])
    attention = C * (Hq + 2 * Hkv) * d + Hq * d * C + 2 * d
    indexer = C * (Hi * di + di + Hi) + 2 * di
    return attention + indexer + 2 * C + E * C


def non_expert_params(config):
    """Everything a decode step reads once whatever the routing: every
    layer without its experts, the final gain and the head.  The
    embedding row a token looks up is left out."""
    C = config["hidden_size"]
    return config["num_hidden_layers"] * layer_params(config) \
        + C + config["vocab_size"] * C


def decode_step_bytes(config, itemsize, context_lengths, experts_hit):
    """Bytes one decode step has to read: the non-expert weights once,
    the experts its tokens were routed to (``experts_hit``: mean
    distinct held experts a step, a layer), and for each live row of
    length n, a layer: the indexer's keys to n and the keys and values
    of the ``min(n, topk)`` positions it selects."""
    sa = config["sa_config"]
    kv = 2 * config["num_key_value_heads"] * config["head_dim"]
    L = config["num_hidden_layers"]
    cache = sum(int(n) * sa["indexer_head_dim"]
                + min(int(n), sa["topk"]) * kv for n in context_lengths)
    return (non_expert_params(config)
            + L * (experts_hit * expert_params(config) + cache)) * itemsize


def decode_step_flops(config, rows, pairs):
    """Operations of one decode step of ``rows`` live rows whose tokens
    made ``pairs`` assignments to held experts (all layers together);
    the indexer's scores and attention over the selection are left out
    (small beside the weights at one query a row)."""
    per_row = non_expert_params(config) - config["hidden_size"]
    return 2 * (rows * per_row + pairs * expert_params(config))


def attn_sparse_flops(config, selected_pairs):
    """Attention over the selection: each (query, selected key) pair
    costs a score and a value product of ``head_dim`` for every query
    head."""
    return 4 * selected_pairs * config["num_attention_heads"] \
        * config["head_dim"]


def attn_index_flops(config, live_pairs):
    """The indexer's scores: each (query, live key) pair costs one
    product of ``indexer_head_dim`` for every indexer head (the
    threshold search has no least work of its own: it is charged to the
    scores it ranks)."""
    sa = config["sa_config"]
    return 2 * live_pairs * sa["indexer_num_heads"] * sa["indexer_head_dim"]
