"""Execution-engine shim.

Reference parity: src/engine/ (ThreadedEnginePerDevice / NaiveEngine,
Engine::WaitForAll, async exception propagation re-thrown at WaitToRead).

TPU-first design: XLA/PJRT dispatch is already asynchronous with dataflow
ordering, so there is no hand-built dependency engine.  What remains here is
the *policy* surface the reference exposes:

- ``MXNET_ENGINE_TYPE=NaiveEngine`` → every op blocks until complete
  (bisecting async bugs, reference: src/engine/naive_engine.cc);
- ``wait_all()`` → drain all in-flight device work
  (reference: Engine::WaitForAll);
- deferred errors: JAX raises device errors at block time, matching the
  reference's re-throw-at-WaitToRead semantics (tests/python/unittest/
  test_exc_handling.py is mirrored by tests/test_engine.py).
"""

from __future__ import annotations

import os
import re
import threading
import time

from . import telemetry

_NAIVE = os.environ.get("MXNET_ENGINE_TYPE", "").lower() == "naiveengine"


def is_naive() -> bool:
    return _NAIVE


def set_engine_type(name: str) -> None:
    """'NaiveEngine' → synchronous; anything else → async (default)."""
    global _NAIVE
    _NAIVE = name.lower() == "naiveengine"


_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
_CACHE_CONFIGURED = False


def ensure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache — the one place this
    repository configures it (idempotent; returns the directory).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here.  Otherwise the directory is
    ``<checkout>/.jax_cache``: a fixed path, because the path is part
    of the cache key and a directory that moves never hits.  There the
    CPU backend persists nothing: its compiles are short, and XLA:CPU
    (jaxlib 0.9.0) logs a machine-feature error on every cache load.
    Set the variable to cache CPU programs too.

    The whole-step capture (`gluon.captured`) compiles ONE large XLA
    program per training configuration; a restarted process re-traces
    it but deserializes the executable instead of recompiling.
    Thresholds are zeroed so small programs (parameter init, the eager
    oracle's per-group updates) persist too.
    """
    global _CACHE_CONFIGURED
    import jax

    if _CACHE_CONFIGURED:
        return jax.config.jax_compilation_cache_dir
    watch_compiles()
    from .profiler import scope

    # the first call here that needs the client: in a process that has
    # not touched a device yet, this span holds the client's start
    with scope("startup.backend"):
        backend = jax.default_backend()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          _CHECKOUT_CACHE_DIR)
        if backend == "cpu":
            jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache module latches its enabled/dir decision at the FIRST
    # compile; anything already compiled (parameter init ops before the
    # Trainer existed) froze it — reset so the next compile re-reads
    # the config and starts persisting
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    _CACHE_CONFIGURED = True
    return jax.config.jax_compilation_cache_dir


# -- compile events by program ---------------------------------------------------

# JAX's event of each interval -> the span it becomes
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# what JAX makes a module's name of (`mlir.sanitize_name`)
_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")
_WATCHING = False
_COMPILING = threading.local()     # this thread's backend compile, so far


def _on_cache_event(event, **_):
    # JAX records both on the compiling thread, inside the
    # backend-compile interval whose end consumes them
    if event == _CACHE_ASKED:
        _COMPILING.cache = "miss"
    elif event == _CACHE_HIT:
        _COMPILING.cache = "hit"


def _on_compile_span(event, start_time, end_time, fun_name=None, **_):
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    # JAX reads `time.time()`; the span goes on `perf_counter`, the
    # clock of every other span.  Its end is now, on this thread (JAX
    # calls its listeners as the interval closes), so only the length
    # crosses clocks: an offset between the two taken once (as
    # `obs.spans.wall` has it) drifts by milliseconds over a minute,
    # more than the gap between a compile and the scope around it.
    t1 = time.perf_counter()
    secs = end_time - start_time
    program = str(fun_name)
    if name != "compile.trace":
        # JAX passes ``jit(serve_decode)`` here; the module it lowers
        # and compiles, and every device trace, say ``jit_serve_decode``
        program = _NOT_IN_A_MODULE_NAME.sub("_", program).rstrip("_")
    if name != "compile.backend":
        telemetry.keep_span(name, t1 - secs, t1, program=program)
        return
    cache = getattr(_COMPILING, "cache", "off")
    _COMPILING.cache = "off"
    telemetry.keep_span(name, t1 - secs, t1, program=program, cache=cache)
    telemetry.count("compile.programs")
    telemetry.count("compile.seconds", secs)
    if cache != "off":
        telemetry.count("compile.cache_hits" if cache == "hit"
                        else "compile.cache_misses")
    telemetry.event("compile", program=program, cache=cache,
                    secs=round(secs, 6))


def watch_compiles() -> None:
    """Turn JAX's own compile events into the program's spans, by
    program name (idempotent; `ensure_compile_cache` and
    `ServingEngine.__init__` call it; it turns on no cache).

    Every trace, lowering and backend compile of the process from here
    on becomes a ``compile.trace`` / ``compile.lower`` /
    ``compile.backend`` span of the start-up timeline
    (`telemetry.startup_spans`) with its ``program``: the traced
    function's name on a trace, the module's name on the other two
    (``jit_serve_decode``, ``jit_train_step``, an eager op's own); a
    backend compile also says whether the persistent cache gave it
    (``cache``: ``hit``, ``miss``, or ``off`` where no cache was
    asked), moves the counters ``compile.programs``,
    ``compile.seconds``, ``compile.cache_hits`` /
    ``compile.cache_misses``, and goes out as a ``compile`` event: a
    count of those that moves after warm-up is a recompile, and the
    record says of which program."""
    global _WATCHING
    if _WATCHING:
        return
    _WATCHING = True
    from jax import monitoring

    monitoring.register_event_listener(_on_cache_event)
    monitoring.register_event_time_span_listener(_on_compile_span)


def maybe_sync(arr):
    """Block on an array if NaiveEngine mode is on. Returns the array."""
    if _NAIVE and hasattr(arr, "block_until_ready"):
        arr.block_until_ready()
    return arr


def wait_all() -> None:
    """Block until all asynchronously dispatched work has completed."""
    import jax

    # PJRT exposes no global barrier; syncing every live array is the
    # equivalent drain.  jax.live_arrays() covers everything dispatched.
    # Donated buffers (the fused trainer step's inputs) stay in the live
    # list until GC but cannot be blocked on — skip them.
    for a in jax.live_arrays():
        try:
            if a.is_deleted():
                continue
            a.block_until_ready()
        except RuntimeError:
            continue   # deleted between the check and the block


def bulk(size: int | None = None):
    """Reference compat: engine bulking (MXNET_EXEC_BULK_EXEC_*).

    XLA fuses within a jit region, so bulking is a no-op context manager kept
    for API compatibility with mx.engine.bulk.
    """
    import contextlib

    return contextlib.nullcontext()


_BULK_SIZE = 15  # reference default engine bulking window


def set_bulk_size(size):
    """Reference: mx.engine.set_bulk_size (MXEngineSetBulkSize) — sets
    the async-engine op-bulking window and returns the previous value.
    Under XLA the whole jitted step IS one bulk (CachedOp compiles the
    full graph), so the knob has nothing to tune: accepted for API
    compatibility, returns the previous (nominal) value."""
    global _BULK_SIZE
    prev = _BULK_SIZE
    _BULK_SIZE = int(size)
    return prev
