"""Autoregressive decode throughput: KV-cache step vs full recompute.

Prints one JSON line per config, on whatever backend jax has (a CPU
timing is not a device number).  The cached path
is the inference story for the GPT family: O(W) per token at one
compiled shape vs the recompute path's O(W²) trunk per token.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main(batch=8, seed_len=16, new_tokens=48, units=256, layers=4,
         heads=8, window=256, vocab=32000):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import gpt

    net = gpt.GPTModel(vocab_size=vocab, units=units,
                       num_layers=layers, num_heads=heads,
                       max_length=window, dropout=0.0)
    net.initialize(init=mx.init.Xavier())
    ids = nd.array(np.random.RandomState(0)
                   .randint(0, vocab, (batch, seed_len))
                   .astype(np.float32))
    net(ids)

    dec = gpt.CachedDecoder(net)
    dec_bf16 = gpt.CachedDecoder(net, dtype="bfloat16")
    # warm all paths (compiles)
    dec.decode(ids, max_new_tokens=2)
    dec_bf16.decode(ids, max_new_tokens=2)
    gpt.generate(net, ids, max_new_tokens=2)

    t0 = time.perf_counter()
    out = dec.decode(ids, max_new_tokens=new_tokens)
    np.asarray(out._data)
    dt_cache = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = dec_bf16.decode(ids, max_new_tokens=new_tokens)
    np.asarray(out._data)
    dt_bf16 = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = gpt.generate(net, ids, max_new_tokens=new_tokens)
    np.asarray(out._data)
    dt_full = time.perf_counter() - t0

    tps_cache = batch * new_tokens / dt_cache
    tps_bf16 = batch * new_tokens / dt_bf16
    tps_full = batch * new_tokens / dt_full
    print(json.dumps({
        "bench": "gpt_decode",
        "config": {"batch": batch, "units": units, "layers": layers,
                   "window": window, "vocab": vocab,
                   "new_tokens": new_tokens},
        "kv_cache_tokens_per_sec": round(tps_cache, 1),
        "kv_cache_bf16_tokens_per_sec": round(tps_bf16, 1),
        "recompute_tokens_per_sec": round(tps_full, 1),
        "speedup": round(tps_cache / tps_full, 2),
        "bf16_speedup_over_f32_cache": round(tps_bf16 / tps_cache, 2),
    }))


if __name__ == "__main__":
    main()
