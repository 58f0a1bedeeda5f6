#!/usr/bin/env python
"""Profile the compiled ResNet-50 / BERT training step on the attached chip.

The instrument behind BASELINE.md's MFU notes: times the whole-step program
host-readback terminated (on the installed runtime block_until_ready alone
waits too — chip_smoke.py's sync phase, docs/perf.md), then dissects the
optimized HLO: op-category histogram from XLA's cost analysis, transpose/copy
counts (layout pressure), conv shapes, and the biggest fusions.

Usage:
    python tools/profile_step.py resnet50 --batch 256 --steps 10
    python tools/profile_step.py bert --batch 32 --seq 512
    python tools/profile_step.py resnet50 --xplane /tmp/trace  # full trace
"""

import argparse
import collections
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_resnet(args):
    import numpy as np
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    net = getattr(vision, args.model)(classes=1000)
    net.initialize(init=mx.init.Xavier())
    net.cast("bfloat16")
    mesh = parallel.data_parallel_mesh(1)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}, mesh=mesh)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.standard_normal(
        (args.batch, 3, args.image, args.image)), jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, 1000, args.batch).astype("float32"))
    flops = 3.0 * 2 * 4.089e9 * (args.image / 224.0) ** 2 * args.batch
    return trainer, (x, y), flops


def build_bert(args):
    import numpy as np
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import bert as bert_zoo

    net = bert_zoo.bert_base(dropout=0.0, max_length=args.seq,
                             scan_layers=not args.no_scan,
                             attention_impl=args.attn)
    net.initialize(init=mx.init.Xavier())
    net.cast("bfloat16")
    mesh = parallel.data_parallel_mesh(1)
    trainer = parallel.ShardedTrainer(
        net, bert_zoo.BERTPretrainLoss(), "adamw",
        {"learning_rate": 1e-4, "wd": 0.01}, mesh=mesh)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 30000, (args.batch, args.seq)),
                         jnp.int32)
    mlm = np.full((args.batch, args.seq), -1, np.int32)
    pos = rng.rand(args.batch, args.seq) < 0.15
    mlm[pos] = rng.randint(0, 30000, int(pos.sum()))
    nsp = jnp.asarray(rng.randint(0, 2, (args.batch,)), jnp.int32)
    y = (jnp.asarray(mlm), nsp)
    attn = 12 * 2 * 2 * args.seq * 768
    flops = 3.0 * (2 * 110e6 + attn) * args.batch * args.seq
    return trainer, (tokens, y), flops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model", nargs="?", default="resnet50_v1")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--attn", default="flash")
    ap.add_argument("--no-scan", action="store_true",
                    help="unstacked per-layer blocks (slow compile)")
    ap.add_argument("--xplane", default=None,
                    help="directory to dump a jax.profiler trace into")
    ap.add_argument("--hlo-out", default=None,
                    help="write full optimized HLO text here")
    args = ap.parse_args()
    if args.model == "resnet50":
        args.model = "resnet50_v1"

    import numpy as np
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})", file=sys.stderr)
    from mxnet_tpu import engine

    engine.ensure_compile_cache()

    if args.model.startswith("bert"):
        trainer, (x, y), flops = build_bert(args)
    else:
        trainer, (x, y), flops = build_resnet(args)

    # compile + drain
    t0 = time.perf_counter()
    np.asarray(trainer.step(x, y)._data)
    print(f"first step (compile): {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    np.asarray(trainer.step(x, y)._data)

    if args.xplane:
        with jax.profiler.trace(args.xplane):
            for _ in range(3):
                out = trainer.step(x, y)
            np.asarray(out._data)
        print(f"xplane trace in {args.xplane}", file=sys.stderr)

    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = trainer.step(x, y)
    np.asarray(out._data)
    dt = time.perf_counter() - t0
    step_ms = dt / args.steps * 1e3
    peak = {"v5e": 197e12, "v5 lite": 197e12, "v5p": 459e12,
            "v6": 918e12, "v4": 275e12}
    pk = next((v for k, v in peak.items()
               if k in dev.device_kind.lower()), None)
    mfu = flops / (dt / args.steps) / pk if pk else None

    # -- HLO dissection --------------------------------------------------------
    lowered = trainer._step_fn.lower(
        trainer._param_vals, trainer._opt_state, trainer._aux_vals,
        x, y, jax.random.PRNGKey(0),
        np.float32(0.1), np.float32(1.0))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(hlo)

    ops = collections.Counter()
    conv_lines = []
    for m in re.finditer(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ (\w+)\(", hlo,
                         re.M):
        ops[m.group(1)] += 1
    for ln in hlo.splitlines():
        if " convolution(" in ln and "fusion" not in ln:
            shape = re.search(r"= (\S+) convolution", ln)
            win = re.search(r"window={([^}]*)}", ln)
            dnums = re.search(r"dim_labels=(\S+?)[,}]", ln)
            conv_lines.append((shape and shape.group(1),
                               dnums and dnums.group(1),
                               win and win.group(1)[:40]))

    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
    except Exception:
        cost = {}

    result = {
        "model": args.model,
        "batch": args.batch,
        "step_ms": round(step_ms, 2),
        "mfu": round(mfu, 4) if mfu else None,
        "samples_per_sec": round(args.batch / (dt / args.steps), 1),
        "hlo_op_histogram": dict(ops.most_common(20)),
        "transposes": ops.get("transpose", 0),
        "copies": ops.get("copy", 0),
        "convs": len(conv_lines),
        "flops_analytic": flops,
        "flops_xla": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
    }
    print(json.dumps(result, indent=2))
    print("\nconv dim_labels (first 30):", file=sys.stderr)
    for c in conv_lines[:30]:
        print("  ", c, file=sys.stderr)


if __name__ == "__main__":
    main()
