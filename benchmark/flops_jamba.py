"""Operations and bytes a Jamba configuration needs, from its sizes
alone: the yardstick of `decode_step_roofline.jamba`,
`prefill_ssm_scan_roofline` and `decode_ssm_update_roofline`.  As in
`flops.py`: what the model requires of real tokens and live rows, the
same whatever implements it, not what a compiler or a kernel spends; a
multiply-add is two operations.  Sizes are read under the source's
keys, as `references/jamba.py` reads them.

A row's memory is of two kinds: a cached position in each attention
layer (keys and values), and in each Mamba layer a state of
``mamba_d_state x (mamba_expand x hidden_size)`` float32 and a tail of
``mamba_d_conv - 1`` inputs, whatever the row's length.  A decode step
reads and writes a live row's states and tails whole."""

STATE_ITEMSIZE = 4      # the state is float32 (the configuration's `assumed`)
# a state element's update: an exponential, three products, two sums
OPS_PER_STATE_ELEMENT = 6


def kinds(config):
    return ["attn" if i % config["attn_layer_period"]
            == config["attn_layer_offset"] else "ssm"
            for i in range(config["num_hidden_layers"])]


def ssm_layers(config):
    return kinds(config).count("ssm")


def attn_layers(config):
    return kinds(config).count("attn")


def inner(config):
    """Channels of a Mamba mixer."""
    return config["mamba_expand"] * config["hidden_size"]


def ssm_mixer_params(config):
    """in_proj, the convolution and its bias, x_proj, dt_proj and its
    bias, A_log, D, the three inner gains, out_proj."""
    C, E = config["hidden_size"], inner(config)
    N, R, k = (config["mamba_d_state"], config["mamba_dt_rank"],
               config["mamba_d_conv"])
    return (C * 2 * E + E * k + E + E * (R + 2 * N) + R * E + E + E * N + E
            + R + 2 * N + E * C)


def attn_mixer_params(config):
    C, H = config["hidden_size"], config["num_attention_heads"]
    K = config.get("num_key_value_heads", H)
    return 2 * C * C + 2 * K * (C // H) * C


def mlp_params(config):
    """The SwiGLU's three matrices and a layer's two gains."""
    C = config["hidden_size"]
    return 3 * C * config["intermediate_size"] + 2 * C


def total_params(config):
    """Every parameter once: the layers, the tied embedding, the final
    gain."""
    C = config["hidden_size"]
    return (ssm_layers(config) * (ssm_mixer_params(config)
                                  + mlp_params(config))
            + attn_layers(config) * (attn_mixer_params(config)
                                     + mlp_params(config))
            + config["vocab_size"] * C + C)


def token_flops(config):
    """A token's matrix products through all layers and the tied head
    (gains, biases, the convolution, the recurrence and attention
    apart)."""
    C, E = config["hidden_size"], inner(config)
    N, R = config["mamba_d_state"], config["mamba_dt_rank"]
    ssm = C * 2 * E + E * (R + 2 * N) + R * E + E * C
    mlp = 3 * C * config["intermediate_size"]
    return 2 * (ssm_layers(config) * (ssm + mlp)
                + attn_layers(config) * (attn_mixer_params(config) + mlp)
                + config["vocab_size"] * C)


def position_bytes(config, itemsize):
    """A cached position: keys and values in every attention layer."""
    H = config["num_attention_heads"]
    K = config.get("num_key_value_heads", H)
    return attn_layers(config) * 2 * K * (config["hidden_size"] // H) \
        * itemsize


def row_state_bytes(config, itemsize):
    """What a row carries whatever its length: every Mamba layer's
    state (float32) and tail."""
    E = inner(config)
    return ssm_layers(config) * (
        config["mamba_d_state"] * E * STATE_ITEMSIZE
        + (config["mamba_d_conv"] - 1) * E * itemsize)


def attn_flops(config, pairs):
    """Attention's operations for ``pairs`` (query, key) pairs, the
    attention layers together: every head a score over the head's width
    and a value product over it."""
    return 2 * pairs * 2 * config["hidden_size"] * attn_layers(config)


# -- the recurrence ------------------------------------------------------------

def scan_ops(config, positions):
    """Operations of the recurrence over ``positions`` (position,
    Mamba layer) pairs: every state element moved on once, and the sum
    over the states that makes y."""
    return positions * inner(config) * config["mamba_d_state"] \
        * OPS_PER_STATE_ELEMENT


def scan_bytes(config, positions, itemsize):
    """What the recurrence over ``positions`` (position, Mamba layer)
    pairs has to move when the states stay on chip: c and dt in, y out
    (a channel each), B and C in (a state each), in the serving type."""
    return positions * (3 * inner(config) + 2 * config["mamba_d_state"]) \
        * itemsize


def update_bytes(config, row_layers, itemsize):
    """What one position of ``row_layers`` (live row, Mamba layer) pairs
    has to move: the state in and out (float32) and `scan_bytes`'s
    operands."""
    return row_layers * 2 * config["mamba_d_state"] * inner(config) \
        * STATE_ITEMSIZE + scan_bytes(config, row_layers, itemsize)


# -- a decode step -------------------------------------------------------------

def decode_step_bytes(config, itemsize, context_lengths):
    """Bytes one decode step has to move: every parameter once (the
    embedding is the head; the row a token looks up is left out), and
    for each live row of length n its states and tails in and out and
    its n cached positions in."""
    rows = len(context_lengths)
    return (total_params(config) * itemsize
            + rows * 2 * row_state_bytes(config, itemsize)
            + sum(int(n) for n in context_lengths)
            * position_bytes(config, itemsize))


def decode_step_flops(config, context_lengths):
    """Operations of one decode step of live rows that attend to
    ``context_lengths`` cached positions each."""
    rows = len(context_lengths)
    return (rows * token_flops(config)
            + scan_ops(config, rows * ssm_layers(config))
            + attn_flops(config, sum(int(n) for n in context_lengths)))


def prefill_flops(config, tokens, pairs):
    """Operations of a prefill of ``tokens`` real tokens whose rows make
    ``pairs`` causal (query, key) pairs a layer; the head is applied to
    one position a row, left out here."""
    C = config["hidden_size"]
    return (tokens * (token_flops(config) - 2 * config["vocab_size"] * C)
            + scan_ops(config, tokens * ssm_layers(config))
            + attn_flops(config, pairs))
