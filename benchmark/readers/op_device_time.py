"""Device trace, device 0: self time per traced step, in ms, of the
operations whose outermost ``<phase>/<op type>`` scope is one of ``scopes``
(the named scope the executor puts around each Fluid op, PR 26): one Fluid
op type's forward and gradient lowerings together. A fusion that spans two
Fluid ops carries its root's scope and is credited to that op.

None where the trace holds none of the scopes (a program without the op, or
from before PR 26)."""

from benchmark import program_trace


def seconds(params):
    """Seconds of the traced window under ``scopes``, or None."""
    loaded = program_trace.load_run()
    by_scope = (loaded or {}).get("by_scope") or {}
    found = [by_scope[s] for s in params["scopes"] if s in by_scope]
    return sum(found) if found else None


def read(params, run):
    if run.trace is None or not run.traced_steps:
        return None
    total = seconds(params)
    return None if total is None else 1e3 * total / run.traced_steps
