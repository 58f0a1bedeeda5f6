"""Seeded training data, the same for the program and the reference.

Every row differs.  ``permutation_corpus`` is the learnable corpus of
``chip_smoke._build`` (tok[t+1] = perm[tok[t]]), made on the host;
``seeded_images`` is made on the device.
"""

import numpy as np


def permutation_corpus(seed, batches, batch, traffic, config):
    """(batches, batch, seq_len) int32 token ids."""
    vocab, seq = config["vocab_size"], traffic["seq_len"]
    rng = np.random.default_rng([int(seed), 4])
    perm = rng.permutation(vocab)
    first = rng.choice(vocab, size=batches * batch, replace=False)
    cols = [first]
    for _ in range(seq - 1):
        cols.append(perm[cols[-1]])
    ids = np.stack(cols, axis=1).astype(np.int32)
    return ids.reshape(batches, batch, seq), None


def seeded_images(seed, batches, batch, traffic, config):
    """(batches, batch, 3, side, side) float32 normal images and
    (batches, batch) int32 labels."""
    import jax

    from benchmark import weights

    side, classes = config["image_size"], config["num_classes"]
    kx, ky = jax.random.split(weights.key_for(seed + 5))
    x = jax.jit(lambda k: jax.random.normal(
        k, (batches, batch, 3, side, side), "float32"))(kx)
    y = jax.jit(lambda k: jax.random.randint(
        k, (batches, batch), 0, classes, "int32"))(ky)
    return x, y


GENERATORS = {"permutation_corpus": permutation_corpus,
              "seeded_images": seeded_images}
