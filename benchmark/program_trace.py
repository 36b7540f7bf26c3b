"""The program's own spans and scopes, read out of the profiler's trace.

PR 26 put spans inside the program (``paddle_tpu/core/profiler.record_event``
makes a ``jax.profiler`` annotation of each: ``executor.run`` and its
children, ``sharding.step``, the reader's feeder thread) and a
``<phase>/<op type>`` named scope around every Fluid op, so the device's
operations carry the Fluid op they belong to. This module reads both from
the run's ``.xplane.pb``; the readers ``program_span`` and
``scope_device_time`` and the notes they print are arithmetic on what it
returns, with ``trace.py``'s interval functions.

The file is read as protobuf wire format, with nothing but this module:
``jax.profiler.ProfileData`` shows an event's own stats (``step_num``,
``batch``) but not its metadata's, and on a TPU the scope of an operation is
there, as the stat ``tf_op`` (``jit(step_ps1)/bwd/mul_grad/dot_general:``)
of the event's metadata (looked at by hand on a v5e trace, PR 26; the
event's NAME is the HLO instruction's text, without its ``op_name``).
Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``.

The harness hands a reader no path to the trace, so ``load_run`` takes the
newest ``*.xplane.pb`` under ``<ROOT>/.bench_trace/``: where the harness gave
``run.trace`` it has just written one there (the readers ask for nothing
where it gave none, and their metric is then left out, as the device
readers' are on a CPU). The pure functions take events, not paths.
"""

import glob
import os
import struct

from benchmark import harness, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = ("fwd", "bwd", "opt")
STEP_SPANS = ("executor.run", "executor.run_prepared", "sharding.step")
PROGRAM_SPANS = STEP_SPANS + (
    "executor.feed", "executor.state", "executor.lookup", "executor.enqueue",
    "executor.writeback", "reader.pull", "reader.stage", "reader.put_wait",
    "reader.get_wait", "lod.pack")
UNCLAIMED = "(no scope)"
# the benchmark's own spans: their extent is the window, and an idle gap of
# the device falls in one of them
CLASSES = (harness.SPAN_FEED, harness.SPAN_CALL, harness.SPAN_WAIT)


# ------------------------------------------------------ protobuf wire format
def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field, bytes for a fixed
    one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value = bytes(buf[i:i + size])
            i += size
        else:
            raise ValueError(f"wire type {kind} in an xplane")
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key = value = None
    for f, v in fields(view):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(view, stat_names):
    """(name, value) of one XStat."""
    name = value = None
    for f, v in fields(view):
        if f == 1:
            name = stat_names.get(v, v)
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, v)
    return name, value


def _plane(view):
    """One XPlane: its name, ``{metadata id: (name, {stat: value})}`` and
    its lines, unparsed."""
    name, lines, metas, stat_names = "", [], [], {}
    for f, v in fields(view):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(_map_entry(v)[1])
        elif f == 5:
            key, meta = _map_entry(v)
            for g, w in fields(meta):
                if g == 2:
                    stat_names[key] = _text(w)
    events = {}
    for meta in metas:
        ident, ev_name, stats = 0, "", {}
        for g, w in fields(meta):
            if g == 1:
                ident = w
            elif g == 2:
                ev_name = _text(w)
            elif g == 5:
                k, val = _stat(w, stat_names)
                if k in ("tf_op", "program_id"):
                    stats[k] = val
        events[ident] = (ev_name, stats)
    return name, events, stat_names, lines


def _line(view, want_stats_of, stat_names):
    """(line name, [(metadata id, start_s, end_s, {stat: value})]) of one
    XLine; an event's own stats are read only where ``want_stats_of`` says
    so for its metadata id."""
    name, t0_ns, raw = "", 0, []
    for f, v in fields(view):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0_ns = v
        elif f == 4:
            raw.append(v)
    out = []
    for ev in raw:
        ident = offset_ps = duration_ps = 0
        stats = None
        for f, v in fields(ev):
            if f == 1:
                ident = v
            elif f == 2:
                offset_ps = v
            elif f == 3:
                duration_ps = v
            elif f == 4 and ident in want_stats_of:
                k, val = _stat(v, stat_names)
                stats = stats or {}
                stats[k] = val
        start = t0_ns * 1e-9 + offset_ps * 1e-12
        out.append((ident, start, start + duration_ps * 1e-12, stats or {}))
    return name, out


def load(path, span_names=PROGRAM_SPANS):
    """``{"host": [[(name, start_s, end_s, ids)] per thread line holding a
    span of ``span_names``], "devices": {n: [(name, start_s, end_s,
    op_name)] of the ``XLA Ops`` line}}``. Thread lines can share a name (a
    CPU trace calls them all ``python``), so they are kept apart by line."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    wanted = set(span_names)
    host, devices = [], {}
    for f, plane in fields(space):
        if f != 1:
            continue
        name, metadata, stat_names, lines = _plane(plane)
        m = trace.DEVICE_PLANE.match(name)
        if m:
            for view in lines:
                line_name, events = _line(view, (), stat_names)
                if line_name == trace.OPS_LINE:
                    devices[int(m.group(1))] = [
                        (metadata[i][0], a, b,
                         metadata[i][1].get("tf_op", ""))
                        for i, a, b, _ in events if i in metadata]
        elif not name.startswith("/device:"):
            ids = {i for i, (n, _) in metadata.items() if n in wanted}
            if not ids:
                continue
            for view in lines:
                _, events = _line(view, ids, stat_names)
                mine = sorted(((metadata[i][0], a, b, s)
                               for i, a, b, s in events if i in ids),
                              key=lambda e: (e[1], -e[2]))
                if mine:
                    host.append(mine)
    return {"host": host, "devices": devices}


_LOADED = {}


def load_run():
    """The newest trace under ``.bench_trace/`` (this run's, where the
    harness took one), loaded once, with what the readers share: the window (the extent of the
    benchmark's own spans, to which ``trace.reduce_trace`` cuts
    the device's events too), device 0's self seconds by scope and its idle
    gaps in the window. None where there is no trace."""
    files = glob.glob(os.path.join(
        ROOT, ".bench_trace", "*", "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    if path not in _LOADED:
        _LOADED.clear()
        loaded = _LOADED[path] = load(path, PROGRAM_SPANS + CLASSES)
        span = window(loaded["host"], CLASSES)
        if span and loaded["devices"]:
            events = loaded["devices"][min(loaded["devices"])]
            busy = trace.union(trace.clip(
                [(a, b) for _, a, b, _ in events], *span))
            loaded.update(window=span, gaps=trace.subtract([span], busy),
                          by_scope=scope_seconds(events, *span))
    return _LOADED[path]


# ------------------------------------------------------------- host spans
def occurrences(lines, names):
    """[(line index, event)] of the spans called one of ``names``."""
    return [(i, e) for i, line in enumerate(lines) for e in line
            if e[0] in names]


def span_seconds(lines, names, minus=()):
    """Seconds of each occurrence of a span of ``names``, less the spans of
    ``minus`` that lie inside it on its own thread line."""
    out = []
    for i, (_, lo, hi, _) in occurrences(lines, names):
        inner = sum(min(b, hi) - max(a, lo) for n, a, b, _ in lines[i]
                    if n in minus and a >= lo and b <= hi)
        out.append(hi - lo - inner)
    return out


def busy_share(lines, names):
    """Over the thread lines that hold a span of ``names`` (a feeder thread
    per pass over the data): the union of those spans over the extent of
    all of the line's spans. None where no line holds one."""
    busy = extent = 0.0
    for line in lines:
        mine = trace.union((a, b) for n, a, b, _ in line if n in names)
        if mine:
            busy += trace.measure(mine)
            extent += max(e[2] for e in line) - min(e[1] for e in line)
    return busy / extent if extent else None


def intersect(intervals, others):
    """The part of ``intervals`` inside ``others``; both disjoint, sorted."""
    return trace.subtract(intervals, trace.subtract(intervals, others))


def innermost(line):
    """{span: [intervals in which it is the innermost span of its thread
    line]}: its own interval less the spans nested in it."""
    out = {}
    for name, parts in trace.self_intervals([e[:3] for e in line]):
        out.setdefault(name, []).extend(parts)
    return {n: trace.union(p) for n, p in out.items()}


def lay_gaps(gaps, lines, classes=CLASSES):
    """Idle seconds of the device by what the host was doing: for each class
    (a span of the benchmark's own, on the main line) the idle time inside
    it, and per thread line the part of that under each innermost program
    span. ``{class: (seconds, [{span: seconds} per line])}``."""
    inner = [innermost([e for e in line if e[0] not in classes])
             for line in lines]
    out = {}
    for cls in classes:
        mine = intersect(gaps, trace.union(
            (a, b) for line in lines for n, a, b, _ in line if n == cls))
        per_line = []
        for spans in inner:
            laid = {n: trace.measure(intersect(mine, parts))
                    for n, parts in spans.items()}
            per_line.append({n: s for n, s in laid.items() if s >= 5e-7})
        out[cls] = (trace.measure(mine), per_line)
    return out


# ------------------------------------------------------- device scopes
def outermost_scope(op_name):
    """``bwd/mul_grad`` of ``jit(step_ps1)/bwd/mul_grad/dot_general:``: the
    first ``<phase>/<op type>`` pair on the path (a sub-block's ops nest
    under their control-flow op's pair), or None where there is none."""
    parts = op_name.rstrip(":").split("/")
    for i, part in enumerate(parts[:-1]):
        if part in PHASES:
            return f"{part}/{parts[i + 1]}"
    return None


def scope_seconds(events, lo, hi):
    """{scope: self seconds} of one device's operations, cut to the window
    ``lo..hi``: an operation's self time is its interval less the
    operations nested in it (a ``while`` and its body), so the values sum to
    the device's busy time. A fusion that spans two Fluid ops carries its
    root's ``op_name`` and is credited to that op; what carries no scope
    sums under ``UNCLAIMED`` (XLA's own copies, and the zero fills it hoists
    out of loops, have no ``op_name``)."""
    cut = [(outermost_scope(op) or UNCLAIMED, max(a, lo), min(b, hi))
           for _, a, b, op in events if b > lo and a < hi]
    out = {}
    for scope, parts in trace.self_intervals(cut):
        out[scope] = out.get(scope, 0.0) + trace.measure(parts)
    return out


def phase_seconds(by_scope):
    out = dict.fromkeys(PHASES + (UNCLAIMED,), 0.0)
    for scope, s in by_scope.items():
        out[scope.split("/", 1)[0] if scope != UNCLAIMED else scope] += s
    return out


def window(lines, names):
    """The extent of the spans of ``names``: what ``trace.reduce_trace``
    cuts the device's events to."""
    spans = [e for _, e in occurrences(lines, names)]
    if not spans:
        return None
    return min(e[1] for e in spans), max(e[2] for e in spans)
