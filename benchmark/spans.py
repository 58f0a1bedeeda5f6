"""The program's own spans and scopes, read off the device trace.

`trace.py` reduces a trace to what the runtime names: operations such as
``while.12`` and host events such as ``np.asarray(jax.Array)``.  This
module reads the same ``.xplane.pb`` for what the PROGRAM names:

- its host spans (`mxnet_tpu.profiler.scope`: ``serve.collect``,
  ``serve.decode.readback``, ``train_step``, ``captured_step``,
  ``guard_readback``...),
  which any ``jax.profiler`` session records on the device trace's clock;
- its named scopes inside the compiled programs (``jax.named_scope``:
  ``serve.cache_write``, ``flash``, ``train.optimizer``...), and the
  programs' own names (``jit_serve_decode``, ``jit_train_step``).

`read(path)` gives, for the first chip:

(a) ``idle``: the device's idle seconds **split by intersection** with
    the program's spans.  At each instant the innermost open span (the
    one that started last) owns the instant; what it owns and no device
    operation covers is idle time under that span.  Idle time that no
    span owns is "unattributed": the reader takes it as the window's
    idle total less every span's part, so the parts always sum to
    ``device_idle_pct``.
(b) ``programs``: for each compiled program (the ``XLA Modules`` line),
    the device seconds of each of its operations, each operation counted
    for the time in which no operation nested in it runs (a ``while``
    holds its body's operations; only its own overhead is the
    ``while``'s), and ``paths``: by program, each operation's HLO
    ``op_name``, the ``/``-separated path that holds the named scopes.

**Where ``op_name`` comes from.**  The TPU runtime writes it as the stat
``tf_op`` of each operation's *event metadata*, which
``jax.profiler.ProfileData`` does not expose (it gives an event's own
stats only).  `_metadata_paths` therefore walks the serialized XSpace
itself, a few dozen lines of protobuf wire format, and touches nothing
but the planes' ``event_metadata`` and ``stat_metadata`` maps (the
millions of events are skipped unparsed).

A run carries only the reduced trace, so `find` looks the file up by
the layout ``run.py`` writes:
``<checkout>/benchmark_out/<cell>/seed*-trace1/trace/plugins/profile/*/
*.xplane.pb``, the newest, written during this process's life.  Without
one (a ``--trace 0`` run, a test's own out_root) it returns None and
every reader built on this module returns None.
"""

import bisect
import glob
import os
import re

from benchmark import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# host events that are the program's spans, by the start of their name
PROGRAM_SPANS = ("serve.", "train_step", "captured_", "guard_readback")
# the stat of an operation's event metadata that holds its HLO op_name
OP_NAME_STAT = "tf_op"
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
_PROGRAM = re.compile(r"^jit\((\w+)\)/")
_CACHE = {}


# -- finding the trace ---------------------------------------------------------

def _process_start():
    try:
        return os.stat("/proc/self").st_ctime
    except OSError:
        return 0.0


def find(cell_name, root=ROOT):
    """The xplane of this process's traced window of ``cell_name``, or
    None."""
    files = glob.glob(os.path.join(
        root, "benchmark_out", cell_name, "seed*-trace1", "trace",
        "plugins", "profile", "*", "*.xplane.pb"))
    born = _process_start()
    files = [f for f in files if os.path.getmtime(f) >= born]
    return max(files, key=os.path.getmtime) if files else None


def of_run(run):
    """`read` of the run's trace (``run["xplane"]`` where a caller gives
    the file), or None."""
    path = run.get("xplane") or find(run["cell"]["name"])
    return read(path) if path else None


# -- op_name of each operation, from the serialized XSpace ---------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as memoryviews, fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:
            val, i = None, i + (8 if wire == 1 else 4)
        yield key >> 3, val


def _metadata_paths(blob):
    """{program: {operation (short name): op_name}} from the event
    metadata of the device planes of a serialized XSpace.  Two programs
    may each hold a ``fusion.5``; an op_name starts with its program
    (``jit(serve_decode)/...`` is of ``jit_serve_decode``)."""
    out = {}
    for num, plane in _fields(memoryview(blob)):
        if num != 1:                        # XSpace.planes
            continue
        name, events, stats = "", [], {}
        for num, val in _fields(plane):
            if num == 2:                    # XPlane.name
                name = bytes(val).decode()
            elif num == 4:                  # event_metadata entry
                events.append(val)
            elif num == 5:                  # stat_metadata entry
                meta = dict(_fields(dict(_fields(val))[2]))
                stats[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not trace.DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, v in stats.items() if v == OP_NAME_STAT}
        for entry in events:
            op, path = None, None
            for num, val in _fields(dict(_fields(entry))[2]):
                if num == 2:                # XEventMetadata.name
                    op = trace.short(bytes(val).decode())
                elif num == 5:              # XEventMetadata.stats
                    stat = dict(_fields(val))
                    if stat.get(1) in wanted and 5 in stat:   # str_value
                        path = bytes(stat[5]).decode()
            owner = _PROGRAM.match(path or "")
            if op and owner:
                out.setdefault("jit_" + owner.group(1), {})[op] = path
    return out


def _serialized(path):
    if path.endswith(".txt"):
        from jax.profiler import ProfileData

        with open(path) as f:
            return ProfileData.text_proto_to_serialized_xspace(f.read())
    with open(path, "rb") as f:
        return f.read()


# -- reading -------------------------------------------------------------------

def _self_seconds(events):
    """{name: seconds} of sorted (start, end, name) events that nest:
    each event's duration less that of the events nested right in it."""
    out, stack = {}, []

    def close():
        s, e, name, inner = stack.pop()
        out[name] = out.get(name, 0.0) + (e - s) - inner
        if stack:
            stack[-1][3] += e - s

    for s, e, name in events:
        while stack and stack[-1][1] <= s:
            close()
        if stack:                   # an overhang counts to its parent's end
            e = min(e, stack[-1][1])
        stack.append([s, e, name, 0.0])
    while stack:
        close()
    return out


def read(path):
    """What the module's head describes, as a dict; cached by path."""
    if path in _CACHE:
        return _CACHE[path]
    data = trace.load(path)
    ops, modules, spans, paths = [], [], [], {}
    chip = None
    for plane in data.planes:
        dev = trace.DEVICE_PLANE.match(plane.name)
        if dev and (chip is None or int(dev.group(1)) < chip[0]):
            chip = (int(dev.group(1)), plane)
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                spans.extend(
                    (e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    for e in ln.events
                    if e.name.startswith(PROGRAM_SPANS))
    # a trace without one span of the program is of a program without
    # its names (the parent of the PR that brought them): its device
    # lines, millions of events, are then left unread
    if chip is not None and spans:
        short = {}                  # the HLO text of an operation, once
        for ln in chip[1].lines:
            if ln.name == trace.MODULES_LINE:
                modules = sorted(trace._events(ln))
            elif ln.name == trace.OPS_LINE:
                for e in ln.events:
                    raw = e.name
                    name = short.get(raw)
                    if name is None:
                        name = short[raw] = trace.short(raw)
                    ops.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, name))
        paths = _metadata_paths(_serialized(path))
    ops.sort(key=lambda ev: (ev[0], -ev[1]))
    # each operation belongs to the program in whose execution it starts
    starts = [m[0] for m in modules]
    by_program = {}
    for ev in ops:
        i = bisect.bisect_right(starts, ev[0]) - 1
        if i >= 0 and ev[0] < modules[i][1]:
            by_program.setdefault(modules[i][2].split("(")[0],
                                  []).append(ev)
    busy = trace.union((s, e) for s, e, _ in ops)
    out = {"busy": busy, "busy_s": trace.length(busy),
           "idle": idle_by_span(spans, busy),
           "programs": {k: _self_seconds(v)
                        for k, v in by_program.items()},
           "paths": paths, "spans": len(spans)}
    _CACHE[path] = out
    return out


def idle_by_span(spans, busy):
    """{span name: seconds} in which the span is the innermost open one
    and no device operation runs.  ``busy`` is merged and sorted."""
    points = sorted({t for s, e, _ in spans for t in (s, e)})
    spans = sorted(spans)
    busy_starts = [b[0] for b in busy]
    out, open_, j = {}, [], 0
    for lo, hi in zip(points, points[1:]):
        while j < len(spans) and spans[j][0] <= lo:
            open_.append(spans[j])
            j += 1
        open_ = [sp for sp in open_ if sp[1] > lo]
        if not open_:
            continue
        name = max(open_, key=lambda sp: (sp[0], -sp[1]))[2]
        covered = 0.0
        k = max(0, bisect.bisect_right(busy_starts, lo) - 1)
        while k < len(busy) and busy[k][0] < hi:
            covered += max(0.0, min(hi, busy[k][1]) - max(lo, busy[k][0]))
            k += 1
        out[name] = out.get(name, 0.0) + (hi - lo) - covered
    return out


# -- scopes --------------------------------------------------------------------

def under(path, scope):
    """Whether the op_name ``path`` lies under the named scope: one of
    its components is the scope, bare or wrapped by a transformation
    (``transpose(jvp(flash))``)."""
    for part in path.split("/"):
        while True:
            if part == scope:
                return True
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    return False


def scope_seconds(tr, program, scopes):
    """(seconds under each of ``scopes``, seconds under none of them,
    total) of the named program's device time."""
    ops = tr["programs"].get(program)
    if not ops:
        return None
    by_scope, none, total = dict.fromkeys(scopes, 0.0), 0.0, 0.0
    paths = tr["paths"].get(program, {})
    for op, secs in ops.items():
        path = paths.get(op, "")
        hit = [s for s in scopes if under(path, s)]
        for s in hit:
            by_scope[s] += secs
        if not hit:
            none += secs
        total += secs
    return by_scope, none, total
