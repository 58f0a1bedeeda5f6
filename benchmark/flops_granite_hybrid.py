"""Operations and bytes a Granite 4.0-H configuration needs, from its
sizes alone: the yardstick of `decode_step_roofline.granite`,
`prefill_ssd_scan_roofline`, `decode_ssd_update_roofline` and
`prefill_moe_experts_roofline.granite`.  As in `flops.py`: what the
model requires of real tokens and live rows, the same whatever
implements it, not what a compiler or a kernel spends; a multiply-add is
two operations.  Sizes are read under the source's keys, as
`references/granite_hybrid.py` reads them.

A row's memory is of two kinds: a cached position in each attention
layer (keys and values), and in each Mamba-2 layer the heads' states,
``mamba_n_heads x mamba_d_head x mamba_d_state`` float32, and a tail of
``mamba_d_conv - 1`` inputs of the convolution's ``E + 2 N`` channels,
whatever the row's length.  A decode step reads and writes a live row's
states and tails whole.

The readers that take a module's name (`readers/counter_roofline.py`,
`readers/decode_step_roofline_of.py`) call ``decode_step_bytes(config,
itemsize, context_lengths, counters)`` and ``decode_step_flops(config,
context_lengths, counters)``, ``counters`` being the served group's own
(the engine's timings) with ``decode_steps``; a count's functions take
``(config, count)`` and ``(config, count, itemsize)``."""

STATE_ITEMSIZE = 4      # the state is float32 (the configuration's `assumed`)
# a state element a position: a product and a sum to move it on (the
# decay's product with it, plus dt x B), a product and a sum to read it
# out, and the outer product's own.  The chunked matrix form spends
# more, (2 Q + 4 N) / (5 N) of this at chunks of Q positions: 1.2 times
# at 128
OPS_PER_STATE_ELEMENT = 5


def kinds(config):
    return ["attn" if t == "attention" else "ssm"
            for t in config["layer_types"]]


def ssm_layers(config):
    return kinds(config).count("ssm")


def attn_layers(config):
    return kinds(config).count("attn")


def inner(config):
    """Channels of a Mamba-2 mixer: heads x their width."""
    return config["mamba_n_heads"] * config["mamba_d_head"]


def conv_dim(config):
    """The convolution's channels: x, B and C."""
    return inner(config) + 2 * config["mamba_n_groups"] \
        * config["mamba_d_state"]


def ssm_mixer_params(config):
    """in_proj, the convolution and its bias, dt_bias, A_log and D, the
    gated norm's gain, out_proj."""
    C, E, W = config["hidden_size"], inner(config), conv_dim(config)
    H = config["mamba_n_heads"]
    return C * (E + W + H) + W * config["mamba_d_conv"] + W + 3 * H + E \
        + E * C


def attn_mixer_params(config):
    C, H = config["hidden_size"], config["num_attention_heads"]
    K = config.get("num_key_value_heads", H)
    return 2 * C * C + 2 * K * (C // H) * C


def expert_params(config):
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def layer_rest_params(config):
    """What every layer has beside its mixer and its routed experts: the
    shared expert, the router over all the experts, two gains."""
    C = config["hidden_size"]
    E = config.get("router_experts", config["num_local_experts"])
    return 3 * C * config["shared_intermediate_size"] + E * C + 2 * C


def experts_held(config):
    return config.get("experts_held", (0, config["num_local_experts"]))[1]


def non_expert_params(config):
    """Everything a decode step reads once whatever the routing: every
    layer's mixer, shared expert, router and gains, the tied embedding
    (it is the head; the row a token looks up is left out) and the final
    gain."""
    C = config["hidden_size"]
    return (ssm_layers(config) * ssm_mixer_params(config)
            + attn_layers(config) * attn_mixer_params(config)
            + config["num_hidden_layers"] * layer_rest_params(config)
            + config["vocab_size"] * C + C)


def total_params(config):
    """Every parameter once, the held experts among them."""
    return non_expert_params(config) + config["num_hidden_layers"] \
        * experts_held(config) * expert_params(config)


def position_bytes(config, itemsize):
    """A cached position: keys and values in every attention layer."""
    H = config["num_attention_heads"]
    K = config.get("num_key_value_heads", H)
    return attn_layers(config) * 2 * K * (config["hidden_size"] // H) \
        * itemsize


def row_state_bytes(config, itemsize):
    """What a row carries whatever its length: every Mamba-2 layer's
    states (float32) and tail."""
    return ssm_layers(config) * (
        inner(config) * config["mamba_d_state"] * STATE_ITEMSIZE
        + (config["mamba_d_conv"] - 1) * conv_dim(config) * itemsize)


def attn_flops(config, pairs):
    """Attention's operations for ``pairs`` (query, key) pairs, the
    attention layers together: every head a score over the head's width
    and a value product over it."""
    return 2 * pairs * 2 * config["hidden_size"] * attn_layers(config)


# -- the recurrence ------------------------------------------------------------

def scan_ops(config, positions):
    """Operations of the recurrence over ``positions`` (position,
    Mamba-2 layer) pairs: every state element moved on once and read out
    once."""
    return positions * inner(config) * config["mamba_d_state"] \
        * OPS_PER_STATE_ELEMENT


def scan_bytes(config, positions, itemsize):
    """What the recurrence over ``positions`` (position, Mamba-2 layer)
    pairs has to move when the states stay on chip: x in and y out (a
    channel each), B and C (a state each) and dt (a head), in the
    serving type."""
    return positions * (2 * inner(config) + 2 * config["mamba_d_state"]
                        + config["mamba_n_heads"]) * itemsize


def update_bytes(config, row_layers, itemsize):
    """What one position of ``row_layers`` (live row, Mamba-2 layer)
    pairs has to move: the heads' states in and out (float32) and
    `scan_bytes`'s operands."""
    return row_layers * 2 * inner(config) * config["mamba_d_state"] \
        * STATE_ITEMSIZE + scan_bytes(config, row_layers, itemsize)


# -- the experts ---------------------------------------------------------------

def expert_flops(config, pairs):
    """Operations of the held experts for ``pairs`` (token, expert)
    assignments: three products of hidden x expert width each."""
    return 2 * pairs * expert_params(config)


# -- a decode step -------------------------------------------------------------

def _pairs_a_step(counters):
    return counters.get("moe_pairs_decode", 0) \
        / max(1, counters.get("decode_steps", 1))


def decode_step_bytes(config, itemsize, context_lengths, counters):
    """Bytes one decode step has to move: the non-expert weights once,
    the held experts its tokens were routed to
    (``moe_experts_hit_per_step``: mean distinct held experts a step, a
    layer), and for each live row of length n its states and tails in
    and out and its n cached positions in."""
    rows = len(context_lengths)
    hit = float(counters.get("moe_experts_hit_per_step",
                             experts_held(config)))
    return ((non_expert_params(config) + config["num_hidden_layers"] * hit
             * expert_params(config)) * itemsize
            + rows * 2 * row_state_bytes(config, itemsize)
            + sum(int(n) for n in context_lengths)
            * position_bytes(config, itemsize))


def decode_step_flops(config, context_lengths, counters):
    """Operations of one decode step of live rows that attend to
    ``context_lengths`` cached positions each and whose tokens made
    ``moe_pairs_decode / decode_steps`` assignments to held experts."""
    rows = len(context_lengths)
    return (2 * rows * non_expert_params(config)
            + expert_flops(config, _pairs_a_step(counters))
            + scan_ops(config, rows * ssm_layers(config))
            + attn_flops(config, sum(int(n) for n in context_lengths)))
