"""mxnet_tpu — a TPU-native deep learning framework with the MXNet API surface.

A from-scratch rebuild of the capabilities of apache/incubator-mxnet
(reference: Mooonside/incubator-mxnet) designed TPU-first on jax/XLA/Pallas:

- ``NDArray`` keeps MXNet's asynchronous, mutable array semantics
  (reference: include/mxnet/ndarray.h, src/ndarray/ndarray.cc) but is backed
  by immutable ``jax.Array`` buffers — mutation is handle-swapping with a
  version counter; "async engine" scheduling (reference: src/engine/) is
  delegated to XLA/PJRT's already-asynchronous dispatch, with
  ``wait_to_read()`` mapping to ``block_until_ready()``.
- The operator library (reference: src/operator/) is a registry of pure JAX
  functions; the ``mx.nd.*`` / ``mx.np``-style wrappers are generated from the
  registry at import time, mirroring python/mxnet/ndarray/register.py.
- ``gluon`` keeps Block/HybridBlock/Parameter/Trainer semantics; ``hybridize()``
  compiles the whole step with ``jax.jit`` (the CachedOp analog,
  reference: src/imperative/cached_op.cc).
- ``kvstore`` maps push/pull onto XLA collectives over the ICI mesh
  (reference: src/kvstore/).
- ``parallel`` is new, TPU-first: device meshes, data/tensor/pipeline/sequence
  parallelism via jax.sharding + shard_map, ring attention over ppermute.
"""

import time as _time

_T_IMPORT = _time.perf_counter()    # the `startup.import` span opens here

__version__ = "0.1.0"

# memory-pool env knobs must hit the XLA client env BEFORE jax loads
# (reference analog: pool env read at Storage::Get())
from . import storage
storage.apply_env()

from . import base
from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import engine
from . import ops
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import random
from .random import seed

# MXNet-compatible top-level saves (mx.nd.save / mx.nd.load are the canonical
# entry points; these mirror python/mxnet/ndarray/utils.py).
from .ndarray import save, load

# Frontend layers: imported when present (they land milestone by milestone;
# once the build is complete these are all unconditional).
import importlib as _importlib

for _mod in ("initializer", "optimizer", "metric", "callback", "kvstore",
             "gluon", "io", "recordio", "image", "profiler", "runtime",
             "parallel", "test_utils", "util", "visualization", "operator",
             "symbol", "model", "module", "lr_scheduler", "distributed",
             "amp", "checkpoint", "contrib", "rtc", "image_detection",
             "subgraph", "attribute", "monitor", "resilience", "numerics",
             "telemetry", "serving", "autotune", "embedding"):
    try:
        globals()[_mod] = _importlib.import_module(f".{_mod}", __name__)
    except ModuleNotFoundError as _e:
        # only tolerate the module itself not existing yet, not its bugs
        if _e.name != f"{__name__}.{_mod}":
            raise
del _importlib, _mod

if "kvstore" in globals():
    kv = globals()["kvstore"]
    KVStore = kv.KVStore
if "initializer" in globals():
    init = globals()["initializer"]
if "optimizer" in globals():
    lr_scheduler = optimizer.lr_scheduler
if "symbol" in globals():
    sym = globals()["symbol"]
if "module" in globals():
    mod = globals()["module"]
if "visualization" in globals():
    viz = globals()["visualization"]
if "attribute" in globals():
    AttrScope = attribute.AttrScope
if "monitor" in globals():
    mon = globals()["monitor"]  # reference alias: mx.mon.Monitor

# the first spans of the process's start-up timeline (`profiler` is not
# there yet at the first line: clock reads and `keep_span`): what the
# process did before this package's first line (the interpreter, and
# whatever the caller imported and started first: under a caller that
# has imported jax and asked for its devices, jax and the client), and
# the import of this package, with that of jax beneath it where the
# caller left it
_now = _time.perf_counter()
_age = telemetry.process_age()
if _age is not None and _now - _age < _T_IMPORT:
    telemetry.keep_span("startup.before_import", _now - _age, _T_IMPORT)
telemetry.keep_span("startup.import", _T_IMPORT, _now)
del _time, _T_IMPORT, _now, _age
