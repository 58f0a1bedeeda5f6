"""Operations and bytes an Ouro configuration needs, from its sizes
alone: the yardstick of `decode_step_roofline.ouro`,
`decode_attn_roofline.ouro` and `prefill_attn_full_roofline.ouro`.  As
in `flops.py`: what the model requires of real tokens, not what a
compiler or a kernel spends; a multiply-add is two operations.  Sizes
are read under the source's keys, as `references/ouro.py` reads them.

The loop is part of the model: pass ``t + 1`` of the stack starts from
pass ``t``'s whole output, so a token needs the layers' weights
``total_ut_steps`` times, and a cached position is read in every
(loop step, layer) slot."""


def layer_params(config):
    """One layer: the query, key and value projections, W_o, the SwiGLU's
    three matrices and the four gains."""
    C, d = config["hidden_size"], config["head_dim"]
    H = config["num_attention_heads"]
    K = config.get("num_key_value_heads", H)
    return ((H + 2 * K) * d * C + H * d * C
            + 3 * C * config["intermediate_size"] + 4 * C)


def slots(config):
    """Cache slots a position is written to and read from: one a (loop
    step, layer)."""
    return config["total_ut_steps"] * config["num_hidden_layers"]


def slot_bytes(config, itemsize):
    """A cached position in one slot: its keys and its values."""
    K = config.get("num_key_value_heads", config["num_attention_heads"])
    return 2 * K * config["head_dim"] * itemsize


def pass_params(config):
    """What one pass of the stack reads: the layers, the final gain and
    the exit gate."""
    C = config["hidden_size"]
    return config["num_hidden_layers"] * layer_params(config) + 2 * C + 1


def step_params(config):
    """Parameters a decode step reads, each as often as it is read: the
    passes' ``total_ut_steps`` times, the head once.  The embedding row
    a token looks up is left out."""
    return config["total_ut_steps"] * pass_params(config) \
        + config["vocab_size"] * config["hidden_size"]


def decode_step_bytes(config, itemsize, context_lengths):
    """Bytes one decode step has to read: the weights as `step_params`
    counts them, and for each live row of length n its n cached
    positions in every slot."""
    cache = sum(int(n) for n in context_lengths) * slots(config) \
        * slot_bytes(config, itemsize)
    return step_params(config) * itemsize + cache


def attn_flops(config, pairs):
    """Attention's operations for ``pairs`` (query, key) pairs, all
    slots together: every head a score over ``head_dim`` and a value
    product over ``head_dim``."""
    return 2 * pairs * config["num_attention_heads"] \
        * 2 * config["head_dim"]


def token_flops(config):
    """A token's matrix products through all passes and the head (the
    gains, the gate and attention apart)."""
    C = config["hidden_size"]
    return 2 * (slots(config) * (layer_params(config) - 4 * C)
                + config["vocab_size"] * C)


def decode_step_flops(config, rows, positions):
    """Operations of one decode step of ``rows`` live rows that attend
    to ``positions`` cached positions (all rows and slots together)."""
    return rows * token_flops(config) + attn_flops(config, positions)


def decode_attn_bytes(config, positions, itemsize):
    """The decode attention's reads: each attended position's keys and
    values (``positions`` counts a position once a slot)."""
    return positions * slot_bytes(config, itemsize)


def prefill_flops(config, tokens, pairs):
    """Operations of a prefill of ``tokens`` real tokens whose rows make
    ``pairs`` causal (query, key) pairs (all slots together); the head
    is applied to one position a row, left out here."""
    C = config["hidden_size"]
    return 2 * tokens * slots(config) * (layer_params(config) - 4 * C) \
        + attn_flops(config, pairs)
