"""Profiler.

Reference parity: src/profiler/profiler.cc + python/mxnet/profiler.py —
set_config / set_state('run'|'stop') / pause / resume / dump /
aggregate stats, chrome://tracing JSON output, env autostart
(MXNET_PROFILER_AUTOSTART).

TPU-first: the host-side tracer records per-op dispatch spans from the
NDArray invoke layer (the analog of ThreadedEngine::ExecuteOprBlock hooks);
device-side time belongs to XLA's own profiler — ``start_xla_trace`` /
``stop_xla_trace`` wrap ``jax.profiler`` so one call captures an xplane
trace alongside the chrome dump (open either in Perfetto).  `scope` is
the one span primitive: its spans show in every ``jax.profiler``
session, these wrappers' or anyone else's.  With
``profile_sync=True`` every traced op blocks on completion, so spans are
true op latencies (NaiveEngine-style measurement).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import defaultdict

from jax.profiler import TraceAnnotation as _TraceMe

from . import telemetry as _telemetry

_LOCK = threading.Lock()


class _State:
    running = False
    sync = False
    filename = "profile.json"
    events: list = []
    aggregate: dict = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
    xla_dir = None


_S = _State()


def is_running() -> bool:
    return _S.running


def set_config(profile_all=False, profile_symbolic=False,
               profile_imperative=False, profile_memory=False,
               profile_api=False, filename="profile.json",
               continuous_dump=False, profile_sync=False, **kwargs):
    """Reference: mx.profiler.set_config (MXSetProcessProfilerConfig)."""
    _S.filename = filename
    _S.sync = profile_sync


def set_state(state="stop", profile_process="worker"):
    """'run' starts collection; 'stop' ends it (reference:
    MXSetProcessProfilerState)."""
    if state == "run":
        _S.running = True
    elif state == "stop":
        _S.running = False
    else:
        raise ValueError("state must be 'run' or 'stop'")


def pause(profile_process="worker"):
    _S.running = False


def resume(profile_process="worker"):
    _S.running = True


def record_span(name, category, t_start, t_end):
    """Called from the dispatch layer for every op while running."""
    with _LOCK:
        _S.events.append({
            "name": name, "cat": category, "ph": "X",
            "ts": t_start * 1e6, "dur": (t_end - t_start) * 1e6,
            "pid": os.getpid(), "tid": threading.get_ident()})
        agg = _S.aggregate[name]
        agg[0] += 1
        dur = (t_end - t_start) * 1e3
        agg[1] += dur
        agg[2] = min(agg[2], dur)
        agg[3] = max(agg[3], dur)


class _OpSpan:
    """Context manager used by the invoke layer."""

    __slots__ = ("name", "t0")

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        record_span(self.name, "operator", self.t0, time.perf_counter())


def op_span(name):
    return _OpSpan(name)


def want_sync() -> bool:
    return _S.running and _S.sync


def dumps(reset=False):
    """Chrome-trace JSON string (reference: MXDumpProfile)."""
    with _LOCK:
        out = json.dumps({"traceEvents": list(_S.events),
                          "displayTimeUnit": "ms"})
        if reset:
            _S.events.clear()
    return out


def dump(finished=True, profile_process="worker"):
    with open(_S.filename, "w") as f:
        f.write(dumps())


def get_summary(reset=False):
    """Aggregate per-op stats table (reference:
    MXAggregateProfileStatsPrint)."""
    with _LOCK:
        lines = [f"{'Name':<40}{'Count':>8}{'Total(ms)':>12}"
                 f"{'Min(ms)':>10}{'Max(ms)':>10}{'Avg(ms)':>10}"]
        for name, (count, total, mn, mx) in sorted(
                _S.aggregate.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<40}{count:>8}{total:>12.3f}{mn:>10.3f}"
                         f"{mx:>10.3f}{total / count:>10.3f}")
        if reset:
            _S.aggregate.clear()
    return "\n".join(lines)


dump_profile = dump
profiler_set_config = set_config
profiler_set_state = set_state


# -- XLA device-side tracing (xplane) ------------------------------------------

def start_xla_trace(log_dir="/tmp/mxnet_tpu_xla_trace"):
    """Capture an XLA xplane trace (view in xprof/Perfetto/TensorBoard)."""
    import jax

    _S.xla_dir = log_dir
    jax.profiler.start_trace(log_dir)
    return log_dir


def stop_xla_trace():
    import jax

    jax.profiler.stop_trace()
    out, _S.xla_dir = _S.xla_dir, None
    return out


def annotate(name, **attrs):
    """Named phase marker for hot-path stages ("allreduce",
    "optimizer_update", "bucket_pack", ...): see `scope`."""
    return scope(name, **attrs)


class scope:
    """The one span primitive: a named interval of host work, on the
    device trace's clock (reference: profiler scopes / NVTX ranges).

    Every use enters a `jax.profiler.TraceAnnotation` (a ``TraceMe``),
    so the span shows in ANY live profiler session, whoever started it
    (`start_xla_trace`, a bare ``jax.profiler.start_trace``, the
    benchmark's `trace.record`, obs/collector.py's on-demand capture),
    beside the device's operations.  With no session live a ``TraceMe``
    is a no-op; the whole scope then costs about a microsecond
    (tests/test_tracing_spans.py holds it under 5).  ``attrs`` are flat
    scalars shown with the span (``scope("serve.decode.readback",
    step=j)``).  The host duration is always measured with
    ``perf_counter`` and forwarded to the telemetry step assembler
    (mxnet_tpu/telemetry.py), which is how StepStats gets its breakdown
    without a profiler on, and keeps the process's first spans as its
    start-up timeline (`telemetry.startup_spans`); ``t0`` and ``t1``
    keep the two clock reads for a caller that sums them (serving's
    ``timings``).
    """

    __slots__ = ("name", "attrs", "t0", "t1", "_jax")

    def __init__(self, name, **attrs):
        self.name = name
        self.attrs = attrs or None
        self._jax = _TraceMe(name, **attrs)

    def set(self, **attrs):
        """Attributes known only once the span is under way (a group's
        size); call before the span closes."""
        self.attrs = {**self.attrs, **attrs} if self.attrs else attrs
        self._jax.set_metadata(**attrs)

    def __enter__(self):
        self._jax.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = t1 = time.perf_counter()
        self._jax.__exit__(*exc)
        if _S.running:
            record_span(self.name, "scope", self.t0, t1)
        _telemetry.on_scope(self.name, t1 - self.t0)
        _telemetry.keep_scope(self)


if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    set_state("run")
