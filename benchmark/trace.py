"""From the profiler's trace to numbers: the benchmark's own reducer.

A trace is read into plain lists of ``(name, start_s, end_s)`` events: per
device the operations XLA ran (the ``XLA Ops`` line of a ``/device:TPU:n``
plane) and, from the host's planes, the benchmark's own spans. Everything
else here is arithmetic on those lists, checked on a hand-built trace in
``testdata/`` by the benchmark's tests:

- busy time is the UNION of the intervals in which an operation runs (a
  ``while`` and the operations of its body overlap; a sum counts them twice);
- an operation's self time is its interval less its children's;
- an idle gap is laid at the host span that covers it;
- exposed collective time is time inside collective operations during which
  no other operation runs on that device.
"""

import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
COLLECTIVE = r"^(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"


def fresh_dir(path):
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    return path


# ----------------------------------------------------------------- reading
def load_xplane(directory, span_names):
    """{"devices": {n: [(name, start_s, end_s)]}, "host": [...]} from the
    newest ``.xplane.pb`` under ``directory``, read with JAX alone."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    wanted = set(span_names)
    devices, in_flight, host, planes = {}, {}, [], []
    for plane in data.planes:
        planes.append(plane.name)
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                into = devices if line.name == OPS_LINE else in_flight
                into[int(m.group(1))] = [
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
            elif not m:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.name in wanted)
    return {"devices": devices, "in_flight": in_flight,
            "host": sorted(host, key=lambda e: e[1]), "planes": planes}


# -------------------------------------------------------------- arithmetic
def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def measure(intervals):
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes):
    """``intervals`` less ``holes``; both disjoint and sorted. One sweep."""
    out, j, holes = [], 0, list(holes)
    for lo, hi in intervals:
        while j < len(holes) and holes[j][1] <= lo:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < hi:
            a, b = holes[k]
            if a > lo:
                out.append((lo, a))
            lo = max(lo, b)
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def self_intervals(events):
    """[(name, [intervals])]: each event's interval less the events nested
    in it (an event lies in another when it starts inside it)."""
    out, stack = [], []                   # stack of [name, end, cursor, parts]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cursor, parts = stack.pop()
            if end > cursor:
                parts.append((cursor, end))
            out.append((name, parts))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        close(lo)
        if stack:
            parent = stack[-1]
            if lo > parent[2]:
                parent[3].append((parent[2], lo))
            parent[2] = max(parent[2], lo)
            hi = min(hi, parent[1])
        stack.append([name, hi, lo, []])
    close(float("inf"))
    return out


def op_text(name):
    """An event's name as the TPU's trace writes it is the whole HLO
    instruction, ``%fusion.43 = bf16[...] fusion(...)``: without the ``%``."""
    return name.lstrip("%")


def strip_suffix(name):
    """XLA's operation name alone, without its trailing ``.N``."""
    return re.sub(r"\.\d+$", "", op_text(name).split(" = ", 1)[0])


def self_seconds_by_name(selfs):
    out = {}
    for name, parts in selfs:
        key = strip_suffix(name)
        out[key] = out.get(key, 0.0) + measure(parts)
    return sorted(out.items(), key=lambda kv: -kv[1])


def matching_seconds(selfs, pattern):
    """Self time of the operations whose HLO text matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(measure(p) for n, p in selfs if rx.search(op_text(n)))


def exposed_seconds(events, selfs, pattern):
    """(total, exposed) seconds of the operations matching ``pattern``:
    total is the union of their intervals (``events``: those on the
    operations' line and those in flight beside it), exposed the part of it
    during which no other operation runs (an enclosing ``while`` does not
    count as running while its body does)."""
    rx = re.compile(pattern)
    mine = union((a, b) for n, a, b in events if rx.search(op_text(n)))
    other = union(i for n, p in selfs if not rx.search(op_text(n))
                  for i in p)
    return measure(mine), measure(subtract(mine, other))


def attribute_gaps(gaps, host):
    """Idle seconds by the host span that covered them; what no span covers
    goes to ``between_spans``. ``gaps`` and ``host`` are sorted and each
    disjoint (the benchmark's spans do not nest). Longest first."""
    out, j = {}, 0
    for lo, hi in gaps:
        left = hi - lo
        while j < len(host) and host[j][2] <= lo:
            j += 1
        k = j
        while k < len(host) and host[k][1] < hi:
            name, a, b = host[k]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                left -= part
            k += 1
        if left > 1e-12:
            out["between_spans"] = out.get("between_spans", 0.0) + left
    return sorted(out.items(), key=lambda kv: -kv[1])


def reduce_trace(trace, chips):
    """The numbers the readers and the result line take from one trace. The
    window is the extent of the benchmark's own host spans; device events
    are cut to it."""
    host = trace["host"]
    if not host or not trace["devices"]:
        return None
    lo, hi = host[0][1], max(e[2] for e in host)
    ids = sorted(trace["devices"])[:chips]
    busy, per_device = [], {}
    for d in ids:
        events = [(n, max(a, lo), min(b, hi))
                  for n, a, b in trace["devices"][d] if b > lo and a < hi]
        selfs = self_intervals(events)
        merged = union((a, b) for _, a, b in events)
        flying = [(n, max(a, lo), min(b, hi))
                  for n, a, b in trace.get("in_flight", {}).get(d, ())
                  if b > lo and a < hi]
        per_device[d] = {"selfs": selfs, "busy": merged,
                         "events": events + flying}
        busy.append(measure(merged))
    first = per_device[ids[0]]
    gaps = subtract([(lo, hi)], first["busy"])
    return {"window_s": hi - lo,
            "busy_s": sum(busy) / len(busy),
            "busy_s_device0": busy[0],
            "device_ops": [[n, s] for n, s in
                           self_seconds_by_name(first["selfs"])],
            "idle_gaps": [[n, s] for n, s in attribute_gaps(gaps, host)],
            "selfs_device0": first["selfs"],
            "events_device0": first["events"],
            "planes": trace.get("planes", [])}


def reduce_run(directory, span_names, chips):
    trace = load_xplane(directory, span_names)
    return None if trace is None else reduce_trace(trace, chips)
