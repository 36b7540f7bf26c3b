"""Device trace, device 0: self time per traced step, in ms, of the
operations of one module of the program: those whose ``op_name`` carries
``module`` as the segment right after their ``<phase>/<op type>`` pair
(``jit(step_ps1)/fwd/mul/mtp/dot_general``: the scope the executor names
for an op a ``fluid.name_scope`` tagged, which its grad ops inherit). The
module's ops of every type count, forward and backward, and the same
operations still count under their ``<phase>/<op type>`` pair wherever
another metric reads that.

None where no operation carries the segment (a program without the module,
or one built before ``fluid.name_scope`` existed)."""

from benchmark import program_trace, trace


def module_of(op_name):
    """The segment after the first ``<phase>/<op type>`` pair of an
    ``op_name`` (a module's name where a name scope tagged the op, else
    what JAX named next, e.g. the primitive), or None."""
    parts = op_name.rstrip(":").split("/")
    for i, part in enumerate(parts[:-2]):
        if part in program_trace.PHASES:
            return parts[i + 2]
    return None


def module_seconds(events, lo, hi, module):
    """Self seconds, inside ``lo..hi``, of the operations of ``events``
    ([(name, start_s, end_s, op_name)], one device's) that carry
    ``module``; an operation's self time is its interval less the
    operations nested in it. None where none carries it."""
    cut = [(module_of(op) == module, max(a, lo), min(b, hi))
           for _, a, b, op in events if b > lo and a < hi]
    if not any(hit for hit, _, _ in cut):
        return None
    return trace.measure([p for hit, parts in trace.self_intervals(cut)
                          if hit for p in parts])


def read(params, run):
    if run.trace is None or not run.traced_steps:
        return None
    loaded = program_trace.load_run()
    if not loaded or not loaded.get("devices") or "window" not in loaded:
        return None
    events = loaded["devices"][min(loaded["devices"])]
    took = module_seconds(events, *loaded["window"], params["module"])
    return None if took is None else 1e3 * took / run.traced_steps
