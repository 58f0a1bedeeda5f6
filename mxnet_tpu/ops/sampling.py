"""The rule a next token is picked by, below both of its users: the
model zoo's host decoders (`gluon/model_zoo/gpt.py`: ``generate``,
``CachedDecoder``) and `serving/engine.py`, which samples on the host by
it and traces its greedy branch into the serving programs."""

from __future__ import annotations


def _sample(last, temperature, rng):
    """Pick next tokens from (B, vocab) logits: greedy, or softmax
    sampling at the given temperature (one home for both decode paths).
    The greedy branch uses array methods only, so it also traces: the
    serving programs pick their token with it on the device
    (serving/engine.py::_make_step)."""
    import numpy as np

    if temperature:
        z = last / temperature
        z = z - z.max(axis=-1, keepdims=True)
        p = np.exp(z) / np.exp(z).sum(axis=-1, keepdims=True)
        rng = rng or np.random.default_rng()
        return np.stack([rng.choice(p.shape[-1], p=row)
                         for row in p]).astype(np.int32)
    return last.argmax(axis=-1).astype(np.int32)
