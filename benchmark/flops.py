"""Operations and bytes the algorithms need, from shapes alone.

These are the yardstick of `train_mfu_pct.*` and `decode_step_roofline`:
what the model requires, not what a compiler spends (no recomputation,
no padding, no copies).  A multiply-add counts as two operations.  An
unknown family raises: there is no default.
"""


def gpt2_forward_flops_per_token(config, seq_len):
    """GPT-2, one token of a causal sequence of ``seq_len``, forward.

    Per layer the products are c_attn (C x 3C), c_proj (C x C) and the
    two of the MLP (C x F, F x C): 2 * (4 C^2 + 2 C F) operations.  The
    head, tied to the embedding, is 2 * V * C.  Attention is Q K^T and
    P V, each 2 * T * C over all heads for a full square, and half of
    that under the causal mask: 2 * T * C per layer in all.  Embedding
    look-ups, LayerNorm, softmax and GELU are left out (no products).
    """
    C, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    F = config.get("n_inner") or 4 * C
    return L * (2 * (4 * C * C + 2 * C * F) + 2 * seq_len * C) + 2 * V * C


def gpt2_train_flops_per_token(config, seq_len):
    """Forward plus backward: the backward pass makes two products for
    each of the forward's (one for the input, one for the weight)."""
    return 3 * gpt2_forward_flops_per_token(config, seq_len)


# ResNet-50 v1 (He et al. 2015, table 1): (blocks, mid channels, out
# channels, output side at 224 px)
_RESNET50_STAGES = ((3, 64, 256, 56), (4, 128, 512, 28),
                    (6, 256, 1024, 14), (3, 512, 2048, 7))


def resnet50_v1_forward_flops_per_image(config):
    """ResNet-50 v1 at ``image_size`` px, forward, convolutions and the
    classifier only: each convolution is 2 * k^2 * C_in * C_out per
    output pixel.  The model zoo's v1 bottleneck strides in its first
    1x1 convolution, as the paper's does, so that 1x1 is counted at the
    block's output side.  BatchNorm, ReLU and pooling are left out."""
    side = config["image_size"]
    if side % 32:
        raise ValueError("resnet50_v1: image_size must divide by 32")
    scale = (side / 224.0) ** 2
    total = 2 * 7 * 7 * 3 * 64 * 112 * 112            # the stem
    c_in = 64
    for blocks, mid, out, s in _RESNET50_STAGES:
        for b in range(blocks):
            px = s * s
            total += 2 * c_in * mid * px               # 1x1 (strided)
            total += 2 * 9 * mid * mid * px            # 3x3
            total += 2 * mid * out * px                # 1x1
            if b == 0:
                total += 2 * c_in * out * px           # projection
            c_in = out
    return total * scale + 2 * 2048 * config["num_classes"]


def resnet50_v1_train_flops_per_image(config):
    return 3 * resnet50_v1_forward_flops_per_image(config)


_TRAIN = {
    "gpt2": lambda config, traffic: gpt2_train_flops_per_token(
        config, traffic["seq_len"]),
    "resnet50_v1": lambda config, traffic:
        resnet50_v1_train_flops_per_image(config),
}


def train_flops_per_unit(config, traffic):
    """Required forward+backward operations per unit of work (token or
    image) of a training cell."""
    family = config["reference"]
    if family not in _TRAIN:
        raise KeyError(f"flops: no operation count for {family!r}")
    return _TRAIN[family](config, traffic)


def gpt2_weight_bytes(config, itemsize):
    """Bytes of every weight a decode step reads once: the blocks, the
    final LayerNorm and the tied head (the embedding row and the
    position row a token looks up are a few KB and left out)."""
    C, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    F = config.get("n_inner") or 4 * C
    per_layer = 4 * C * C + 2 * C * F + (3 * C + C + F + C) + 4 * C
    return (L * per_layer + 2 * C + V * C) * itemsize


def gpt2_decode_step_bytes(config, itemsize, context_lengths):
    """Bytes one decode step has to read: the weights once, and of the
    cache the keys and values of every position each live row attends
    to (``context_lengths``: one entry per row that still wants a
    token; finished rows and padding need nothing)."""
    C, L = config["n_embd"], config["n_layer"]
    cache = sum(2 * L * int(n) * C * itemsize for n in context_lengths)
    return gpt2_weight_bytes(config, itemsize) + cache


def gpt2_decode_step_flops(config, rows):
    """Operations of one decode step of ``rows`` live rows, attention
    left out (it is small beside the weights at these lengths)."""
    C, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    F = config.get("n_inner") or 4 * C
    return rows * (L * 2 * (4 * C * C + 2 * C * F) + 2 * V * C)
