"""Recording a profiler trace of the measured window, and reducing it.

`record` wraps the window in ``jax.profiler`` and returns the
``.xplane.pb`` it wrote.  `reduce` turns that file into the few numbers
and lists the per-layer readers and the result line need, with nothing
but ``jax.profiler.ProfileData``:

- per chip, the union of the intervals in which an operation ran (the
  ``XLA Ops`` line of each ``/device:TPU:n`` plane): busy seconds;
- the idle gaps between them, each named by the innermost host event
  that covers it (``/host:CPU`` planes share the device planes' clock);
- seconds by operation name, and the executions of each compiled
  program (the ``XLA Modules`` line);
- time in collective operations, and the part of it during which no
  other operation ran on that chip (exposed).

The reduction is checked on the small recorded trace in ``tests/data``.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast")
# a gap shorter than this is launch latency, not something the host did
MIN_NAMED_GAP_S = 20e-6
# profiler and runtime bookkeeping that brackets everything and names
# nothing
HOST_NOISE = re.compile(r"^(\$|ThreadpoolListener|ThunkExecutor)")


def record(logdir, fn):
    """Run ``fn()`` under the profiler; (fn's result, xplane path)."""
    import jax

    # the Python tracer would record every call of the interpreter (some
    # 400,000 events a serving round) and slow the host it measures; the
    # runtime's own host events are what name the idle gaps
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"trace: the profiler wrote nothing under "
                           f"{logdir}")
    return out, files[-1]


def load(path):
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def short(name):
    """``%copy.50 = bf16[...] copy(...)`` is the operation ``copy.50``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
             short(e.name)) for e in line.events]


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged intervals ``a`` that merged ``b`` does not
    cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _name_gaps(gaps, host):
    """Seconds of idle gap by host event: each gap goes to the shortest
    (innermost) host event that covers at least half of it, or else to
    the event that covers most of it."""
    import bisect

    by_name = {}
    host = sorted(host)
    starts = [h[0] for h in host]
    for s, e in gaps:
        if e - s < MIN_NAMED_GAP_S:
            name = "(shorter gaps, not named)"
        else:
            inner, most, name = None, 0.0, "(no host event)"
            for hs, he, hn in host[:bisect.bisect_left(starts, e)]:
                cover = min(e, he) - max(s, hs)
                if cover <= 0:
                    continue
                if cover >= 0.5 * (e - s):
                    if inner is None or he - hs < inner[0]:
                        inner = (he - hs, hn)
                elif inner is None and cover > most:
                    most, name = cover, hn
            if inner is not None:
                name = inner[1]
        by_name[name] = by_name.get(name, 0.0) + (e - s)
    return sorted(by_name.items(), key=lambda kv: -kv[1])


def reduce(path, top=10):
    """The reduced trace: a dict (see the module's head)."""
    data = load(path)
    chips, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            chips.append((int(m.group(1)),
                          _events(lines[OPS_LINE]) if OPS_LINE in lines
                          else [],
                          _events(lines[MODULES_LINE])
                          if MODULES_LINE in lines else []))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(ev for ev in _events(ln)
                            if not HOST_NOISE.match(ev[2]))
    chips.sort()
    per_chip, ops, modules, first_gaps = [], {}, {}, None
    for idx, op_events, module_events in chips:
        busy = union((s, e) for s, e, _ in op_events)
        coll = union((s, e) for s, e, n in op_events
                     if COLLECTIVE.search(n))
        other = union((s, e) for s, e, n in op_events
                      if not COLLECTIVE.search(n))
        span = (busy[0][0], busy[-1][1]) if busy else (0.0, 0.0)
        if first_gaps is None:
            # gaps are named on the first chip alone: the host drives
            # all chips with the same calls
            first_gaps = subtract([span], busy) if busy else []
        per_chip.append({
            "chip": idx, "busy_s": length(busy),
            "first_s": span[0], "last_s": span[1],
            "collective_s": length(coll),
            "collective_exposed_s": length(subtract(coll, other))})
        for s, e, n in op_events:
            ops[n] = ops.get(n, 0.0) + (e - s)
        for s, e, n in module_events:
            modules.setdefault(n, []).append(e - s)
    n = max(1, len(chips))
    return {
        "chips": per_chip,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "collective_s": sum(c["collective_s"] for c in per_chip) / n,
        "collective_exposed_s":
            sum(c["collective_exposed_s"] for c in per_chip) / n,
        "device_ops": [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in
                      _name_gaps(first_gaps or [], host)[:top]],
        "modules": {k: sorted(v) for k, v in modules.items()},
        "host_events": len(host)}


def clean(name):
    """An operation or host event name fit for the result line."""
    return re.sub(r"[^A-Za-z0-9_.:\-]+", "_", name)[:80]


# -- a small recorded trace, for the reduction's test --------------------------

def extract(path, seconds, skip=0.0, max_events=400, min_duration_s=0.0):
    """Text-format XSpace of ``seconds`` of the trace at ``path``, from
    ``skip`` seconds after its first device operation: at most
    ``max_events`` events, none shorter than ``min_duration_s``, of each
    device line and of each host line that overlap it: small enough to
    keep beside the tests."""
    data = load(path)
    t0 = min((e.start_ns for p in data.planes
              if DEVICE_PLANE.match(p.name)
              for ln in p.lines if ln.name == OPS_LINE
              for e in ln.events), default=None)
    if t0 is None:
        raise RuntimeError("trace: no device operation to extract")
    t0 = t0 + skip * 1e9
    t1 = t0 + seconds * 1e9
    out, pid = [], 0
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev and not plane.name.startswith("/host:CPU"):
            continue
        pid += 1
        meta, lines = {}, []
        for lid, ln in enumerate(plane.lines):
            if dev and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [e for e in ln.events
                   if e.start_ns < t1 and e.start_ns + e.duration_ns > t0
                   and e.duration_ns >= min_duration_s * 1e9
                   and not (not dev and HOST_NOISE.match(e.name))]
            evs = evs[:max_events]
            if not evs:
                continue
            body = []
            for e in evs:
                mid = meta.setdefault(short(e.name), len(meta) + 1)
                body.append(
                    "events { metadata_id: %d offset_ps: %d duration_ps: "
                    "%d }" % (mid, round((e.start_ns - t0) * 1000),
                              round(e.duration_ns * 1000)))
            lines.append('lines { id: %d name: "%s" timestamp_ns: %d %s }'
                         % (lid + 1, ln.name.replace('"', "'"), 1000000,
                            " ".join(body)))
        if not lines:
            continue
        metas = " ".join(
            'event_metadata { key: %d value { id: %d name: "%s" } }'
            % (mid, mid, name.replace("\\", "/").replace('"', "'"))
            for name, mid in meta.items())
        out.append('planes { id: %d name: "%s" %s %s }'
                   % (pid, plane.name, " ".join(lines), metas))
    return "\n".join(out) + "\n"
