"""Device trace, device 0: self time per traced step, in ms, of the
operations whose outermost ``<phase>/<op type>`` scope starts with
``scope`` (``fwd/``, ``bwd/``, ``opt/``: the named scope the executor puts
around each Fluid op, PR 26). A fusion that spans two Fluid ops carries its
root's scope and is credited to that op. With ``notes``, the table by
``phase/op_type`` and the share no scope claims go on an earlier line.

None where no operation carries a scope (a program from before PR 26, or a
compile cache that handed back its executables)."""

from benchmark import program_trace

TOP = 15


def _notes(by_scope, steps):
    total = sum(by_scope.values())
    phases = program_trace.phase_seconds(by_scope)
    rows = sorted(by_scope.items(), key=lambda kv: -kv[1])[:TOP]
    return [
        "device time by phase/op_type, ms per traced step (share of busy): "
        + "; ".join(f"{n} {1e3 * s / steps:.3f} ({100 * s / total:.1f}%)"
                    for n, s in rows),
        "device time by phase, ms per traced step: "
        + " + ".join(f"{n} {1e3 * s / steps:.3f}" for n, s in phases.items())
        + f" = {1e3 * total / steps:.3f} (device_busy_ms reads the same "
          f"union); no scope claims "
          f"{100 * phases[program_trace.UNCLAIMED] / total:.2f}%"]


def read(params, run):
    if run.trace is None or not run.traced_steps:
        return None
    loaded = program_trace.load_run()
    by_scope = (loaded or {}).get("by_scope")
    if not by_scope or set(by_scope) == {program_trace.UNCLAIMED}:
        return None
    if params.get("notes"):
        run.notes.extend(_notes(by_scope, run.traced_steps))
    seconds = sum(s for n, s in by_scope.items()
                  if n.startswith(params["scope"]))
    return 1e3 * seconds / run.traced_steps
