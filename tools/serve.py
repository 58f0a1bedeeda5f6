#!/usr/bin/env python
"""Serve N requests through the low-latency serving tier, from the CLI.

The smallest end-to-end exercise of mxnet_tpu/serving/: build a model
zoo decoder on the TPU when the process has one (else on the host CPU,
and it says which), AOT-warm the bucketed programs, optionally hot-load
the newest committed AsyncCheckpointer manifest, push N random requests
through the continuous batcher from C concurrent clients, and print a
latency summary (p50/p99 per stage, tokens/sec, bucket usage).

Stdlib argparse only — the jax-facing imports happen after parsing, so
``--help`` works anywhere.

Usage:
    python tools/serve.py [--model gpt_tiny] [--ckpt DIR]
                          [--requests 16] [--clients 4]
                          [--new-tokens 8] [--buckets 1,2,4]
                          [--max-delay-ms 2.0] [--seed 0]
"""

import argparse
import os
import sys
import threading
import time


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Serve N requests through mxnet_tpu/serving/")
    ap.add_argument("--model", default="gpt_tiny",
                    choices=["gpt_tiny", "gpt2_small", "gpt2_medium"],
                    help="model zoo decoder to serve")
    ap.add_argument("--ckpt", default=None,
                    help="AsyncCheckpointer directory; the newest "
                         "committed manifest is hot-loaded before "
                         "serving (default: fresh random weights)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--buckets", default="1,2,4",
                    help="comma-separated batch buckets")
    ap.add_argument("--max-delay-ms", type=float, default=2.0,
                    help="batcher coalescing deadline")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import checkpoint, serving
    from mxnet_tpu.gluon.model_zoo import gpt

    np.random.seed(args.seed)
    mx.random.seed(args.seed)
    # a model lives where its ctx says; the engine serves from there
    ctx = mx.tpu(0) if mx.num_tpus() > 0 else mx.cpu(0)
    net = getattr(gpt, args.model)(scan_layers=True, dropout=0.0)
    net.initialize(init=mx.init.Xavier(), ctx=ctx)
    vocab = net._vocab
    net(mx.nd.array(np.random.randint(0, vocab, (1, 8))
                    .astype(np.float32), ctx=ctx))
    dev = ctx.jax_device
    print(f"serving {args.model} from {ctx}: {dev.device_kind} "
          f"({dev.platform})")

    buckets = tuple(sorted({int(b) for b in args.buckets.split(",")}))
    engine = serving.ServingEngine(net, batch_buckets=buckets)
    t0 = time.perf_counter()
    engine.warmup()
    warm_ms = (time.perf_counter() - t0) * 1e3
    print(f"warmup: {engine.program_count()} AOT programs in "
          f"{warm_ms:.0f} ms (buckets {buckets} x prefill "
          f"{engine.prefill_buckets} + decode)")

    if args.ckpt:
        step = checkpoint.latest_manifest_step(args.ckpt)
        if step is None:
            sys.stderr.write(
                f"error: no committed manifest under {args.ckpt}\n")
            return 2
        ck = checkpoint.AsyncCheckpointer(args.ckpt, rank=0,
                                          world_size=1)
        engine.reload_from_state(ck.restore(step=step), step=step)
        print(f"loaded checkpoint step {step} "
              f"(generation {engine.generation})")

    rng = np.random.RandomState(args.seed + 1)
    window = engine.prefill_buckets[-1]
    max_prompt = max(2, min(16, window - args.new_tokens))
    prompts = [rng.randint(0, vocab, rng.randint(2, max_prompt + 1))
               .tolist() for _ in range(args.requests)]

    batcher = serving.ContinuousBatcher(
        engine, max_delay_ms=args.max_delay_ms, max_batch=buckets[-1])
    results = [None] * args.requests
    lock = threading.Lock()

    def client(idx):
        for j in range(idx, args.requests, args.clients):
            t1 = time.perf_counter()
            rec = batcher.submit(prompts[j], args.new_tokens).result(
                timeout=300)
            rec["total_us"] = (time.perf_counter() - t1) * 1e6
            with lock:
                results[j] = rec

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(max(1, args.clients))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    batcher.close()

    done = [r for r in results if r is not None]
    if len(done) != args.requests:
        sys.stderr.write(f"error: {args.requests - len(done)} of "
                         f"{args.requests} requests never resolved\n")
        return 1

    def pctl(key, q):
        vals = sorted(r[key] for r in done)
        return vals[min(len(vals) - 1,
                        max(0, int(round(q / 100 * len(vals))) - 1))]

    print(f"served {len(done)} requests from {args.clients} clients "
          f"in {wall * 1e3:.0f} ms "
          f"({len(done) * args.new_tokens / wall:.0f} tokens/sec)")
    print(f"  {'stage':<16}{'p50 us':>12}{'p99 us':>12}")
    for key, label in (("queue_us", "queue"), ("prefill_us", "prefill"),
                       ("decode_us_per_token", "decode/token"),
                       ("total_us", "total")):
        print(f"  {label:<16}{pctl(key, 50):>12.1f}"
              f"{pctl(key, 99):>12.1f}")
    hist = {}
    for r in done:
        key = f"{r['bucket'][0]}x{r['bucket'][1]}"
        hist[key] = hist.get(key, 0) + 1
    print("  buckets (batch x seq): " +
          "  ".join(f"{k}:{hist[k]}" for k in sorted(hist)))
    print(f"  mean padded_fraction "
          f"{sum(r['padded_fraction'] for r in done) / len(done):.4f}"
          f"  retraces_after_warmup "
          f"{serving.trace_count() - engine.program_count()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
