"""Operations and bytes a Command A+ (``cohere2_moe``) configuration
needs, from its sizes alone: the yardstick of `decode_step_roofline.cmda`,
`decode_attn_window_roofline`, `prefill_attn_window_roofline` and
`prefill_attn_full_roofline.cmda`.  As in `flops.py`: what the model
requires of real tokens, not what a compiler or a kernel spends; a
multiply-add is two operations.  Sizes are read under the source's keys,
as `references/cohere2_moe.py` reads them."""


def expert_params(config):
    """One expert, routed or shared: gate, up and down."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def attention_params(config):
    """W_q, W_k, W_v and W_o."""
    C, D = config["hidden_size"], config["head_dim"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    return C * (H + 2 * K) * D + H * D * C


def windows(config):
    """A layer's window: ``sliding_window`` or None for a full layer."""
    return [config["sliding_window"] if t == "sliding_attention" else None
            for t in config["layer_types"]]


def non_expert_params(config):
    """Everything a decode step reads once whatever the routing: every
    layer's attention, its one gain, its router and its shared experts;
    the final gain and the tied head (the embedding read whole as the
    head; the row a token looks up is left out)."""
    C = config["hidden_size"]
    E = config.get("router_experts", config["num_experts"])
    per_layer = (attention_params(config) + C + E * C
                 + config["num_shared_experts"] * expert_params(config))
    return config["num_hidden_layers"] * per_layer + C \
        + config["vocab_size"] * C


def position_width(config):
    """Elements the caches hold of a position and layer: a key and a
    value for every key/value head."""
    return 2 * config["num_key_value_heads"] * config["head_dim"]


def positions_read(config, length):
    """Cached positions a decode step reads for one row of ``length``
    positions, all layers: a ring to ``min(length, window)``, a full
    layer to the length."""
    return sum(int(length) if w is None else min(int(length), w)
               for w in windows(config))


def decode_step_bytes(config, itemsize, context_lengths, experts_hit):
    """Bytes one decode step has to read: the non-expert weights once,
    the routed experts its tokens were sent to (``experts_hit``: mean
    distinct held experts a step, a layer), and each live row's cached
    positions."""
    cache = sum(positions_read(config, n) for n in context_lengths) \
        * position_width(config)
    return (non_expert_params(config)
            + config["num_hidden_layers"] * experts_hit
            * expert_params(config) + cache) * itemsize


def decode_step_flops(config, rows, pairs, positions):
    """Operations of one decode step of ``rows`` live rows whose tokens
    made ``pairs`` assignments to held routed experts (all layers
    together) and attend to ``positions`` cached positions (all rows and
    layers together)."""
    per_row = non_expert_params(config) \
        - (config["num_hidden_layers"] + 1) * config["hidden_size"]
    return 2 * (rows * per_row + pairs * expert_params(config)) \
        + attn_flops(config, positions)


def attn_flops(config, pairs):
    """Each (query, key) pair costs every query head a score and a value
    product over the head's width."""
    return 2 * pairs * config["num_attention_heads"] * 2 * config["head_dim"]


def attn_bytes(config, pairs, itemsize):
    """A decode step's reads for ``pairs`` (query, cached position)
    pairs: the position's keys and values, once for all the query heads
    of a key head."""
    return pairs * position_width(config) * itemsize
