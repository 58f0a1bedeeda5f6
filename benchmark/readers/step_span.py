"""A percentile of what the captured step's own spans measured: the sum
of some buckets of ``breakdown_us`` in the program's step records
(``mxnet_tpu.telemetry.recent_steps(path="captured")``: ``dispatch`` is
the ``captured_step`` span, ``host_prep`` the ``captured_host_prep`` and
``captured_commit`` spans, ``data`` ``captured_data``, ``readback``
``guard_readback``), over the window's steps: the last n records, n the
number of steps the window completed.

params: ``buckets`` (summed per step), ``q``, ``scale``.  None where the
program keeps no such records or none has the buckets."""

from benchmark.readers import record_percentile


def read(run, params):
    try:
        from mxnet_tpu import telemetry

        steps = telemetry.recent_steps(path="captured")
    except Exception:       # a program without the store: nothing to read
        return None
    n = len(run["records"])
    steps = steps[-n:] if n else []
    # one definition of a percentile: `record_percentile`'s, over the
    # step records' buckets as its fields
    return record_percentile.read(
        {"records": [r.get("breakdown_us", {}) for r in steps]},
        {"fields": params["buckets"], "q": params["q"],
         "scale": params.get("scale", 1.0)})
