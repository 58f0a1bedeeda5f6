#!/usr/bin/env python
"""Diagnose the runtime environment (reference: tools/diagnose.py —
the script users attach to bug reports: platform, versions, hardware,
feature flags, and a tiny timed op)."""

import os
import platform
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    print("----------Python Info----------")
    print(f"Version      : {platform.python_version()}")
    print(f"Compiler     : {platform.python_compiler()}")
    print(f"Platform     : {platform.platform()}")

    print("----------System Info----------")
    print(f"machine      : {platform.machine()}")
    print(f"processor    : {platform.processor() or 'n/a'}")
    try:
        print(f"cpu count    : {os.cpu_count()}")
    except Exception:
        pass

    print("----------MXNet-TPU Info----------")
    t0 = time.time()
    import mxnet_tpu as mx
    print(f"Version      : {mx.__version__}")
    print(f"Import time  : {time.time() - t0:.2f}s")
    from importlib import metadata

    import jax
    import jaxlib
    print(f"jax          : {jax.__version__}")
    print(f"jaxlib       : {jaxlib.__version__}")
    try:
        print(f"libtpu       : {metadata.version('libtpu')}")
    except metadata.PackageNotFoundError:
        print("libtpu       : not installed")
    devs = jax.devices()
    print(f"Platform     : {devs[0].platform}")
    print(f"Device kind  : {devs[0].device_kind}")
    print(f"Devices      : {[str(d) for d in devs]}")
    print(f"num_tpus     : {mx.num_tpus()}")
    print(f"Compile cache: {mx.engine.ensure_compile_cache()}")

    print("----------Features----------")
    for feat in mx.runtime.Features().values():
        print(f"  {feat!r}")

    print("----------Timed op----------")
    a = mx.nd.ones((256, 256))
    t0 = time.time()
    b = (a @ a).sum()
    val = float(b.asnumpy())
    print(f"(256,256) matmul+sum: {time.time() - t0 :.3f}s "
          f"(= {val:.0f})")

    print("----------Environment----------")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXNET_", "JAX_", "XLA_")):
            print(f"{k}={v}")


if __name__ == "__main__":
    main()
