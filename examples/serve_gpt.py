"""Train → checkpoint → serve → hot-reload, end to end in miniature.

The serving-tier story (mxnet_tpu/serving/, docs/serving.md) on one
box: pretrain a character GPT for a few steps, commit its weights with
AsyncCheckpointer, stand up a ReplicaServer (AOT bucketed programs +
continuous batcher + checkpoint poller), serve concurrent requests,
then keep training and commit a newer checkpoint — the replica
hot-swaps the new weights between batches, without dropping a request
and without a single retrace.

Run: python examples/serve_gpt.py [--steps 30] [--requests 8]
"""

import argparse
import codecs
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, checkpoint, gluon, nd, serving
from mxnet_tpu.gluon.model_zoo import gpt


def corpus():
    import contextlib
    import io

    with contextlib.redirect_stdout(io.StringIO()):
        import this as this_mod

    return codecs.decode(this_mod.s, "rot13")


def train(net, loss_fn, trainer, data, steps, rs, ctx, seq_len=16,
          batch=16):
    last = None
    for _ in range(steps):
        starts = rs.randint(0, len(data) - seq_len - 1, batch)
        ids = nd.array(np.stack([data[s:s + seq_len] for s in starts])
                       .astype(np.float32), ctx=ctx)
        with autograd.record():
            loss = loss_fn(net(ids), ids)
        loss.backward()
        trainer.step(batch)
        last = float(loss.asnumpy())
    return last


def main(steps=30, requests=8, new_tokens=8, seed=0):
    mx.random.seed(seed)
    rs = np.random.RandomState(seed)
    text = corpus()
    vocab = sorted(set(text))
    stoi = {c: i for i, c in enumerate(vocab)}
    itos = dict(enumerate(vocab))
    data = np.array([stoi[c] for c in text], np.int32)

    # scan_layers=True: the scanned trunk is both the fast training
    # layout and the serving checkpoint convention (docs/serving.md)
    net = gpt.gpt_tiny(vocab_size=len(vocab), max_length=16,
                       scan_layers=True)
    # a model lives where its ctx says: the TPU when the process has
    # one, and training batches and the serving engine follow it there
    ctx = mx.tpu(0) if mx.num_tpus() > 0 else mx.cpu(0)
    dev = ctx.jax_device
    print(f"training and serving on {ctx}: {dev.device_kind} "
          f"({dev.platform})")
    net.initialize(init=mx.init.Xavier(), ctx=ctx)
    loss_fn = gpt.GPTLMLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})

    loss0 = train(net, loss_fn, trainer, data, steps, rs, ctx)
    print(f"trained {steps} steps, loss {loss0:.3f}")

    ckdir = tempfile.mkdtemp(prefix="serve_gpt_")
    ck = checkpoint.AsyncCheckpointer(ckdir, rank=0, world_size=1)
    ck.save(1, serving.state_for_serving(net))
    ck.wait()

    engine = serving.ServingEngine(net, batch_buckets=(1, 2, 4))
    t0 = time.perf_counter()
    engine.warmup()
    print(f"warmup: {engine.program_count()} AOT programs in "
          f"{(time.perf_counter() - t0) * 1e3:.0f} ms")
    traces = serving.trace_count()

    replica = serving.ReplicaServer(engine, ckpt_dir=ckdir, poll_ms=25,
                                    max_delay_ms=2)

    def serve_round(tag):
        prompts = [[stoi[c] for c in "the "],
                   [stoi[c] for c in "beauti"],
                   [stoi[c] for c in "simple "],
                   [stoi[c] for c in "error"]][:requests]
        while len(prompts) < requests:
            prompts.append(list(rs.randint(0, len(vocab), 5)))
        t1 = time.perf_counter()
        futs = [replica.submit(p, new_tokens) for p in prompts]
        recs = [f.result(timeout=300) for f in futs]
        ms = (time.perf_counter() - t1) * 1e3
        gens = sorted({r["generation"] for r in recs})
        text0 = "".join(itos[int(t)] for t in recs[0]["tokens"])
        print(f"{tag}: {len(recs)} requests in {ms:.0f} ms "
              f"(generation {gens}); 'the ' -> {text0!r}")
        return recs

    serve_round("serve v1")

    # keep training; commit; the replica hot-swaps between batches
    loss1 = train(net, loss_fn, trainer, data, steps, rs, ctx)
    ck.save(2, serving.state_for_serving(net))
    ck.wait()
    ck.close()
    print(f"trained {steps} more steps, loss {loss1:.3f}; "
          f"committed step 2")
    deadline = time.monotonic() + 30
    while replica.loaded_step != 2 and time.monotonic() < deadline:
        serve_round("serving while reloading")
        time.sleep(0.05)
    assert replica.loaded_step == 2, "hot reload never landed"
    recs = serve_round("serve v2 (hot-reloaded)")
    assert all(len(r["tokens"]) == new_tokens for r in recs)
    retraces = serving.trace_count() - traces
    print(f"hot reloads applied: {replica.reloads}; "
          f"retraces after warmup: {retraces}")
    assert retraces == 0
    replica.close()
    print("ok")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    main(steps=args.steps, requests=args.requests,
         new_tokens=args.new_tokens, seed=args.seed)
