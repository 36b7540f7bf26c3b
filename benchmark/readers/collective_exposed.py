"""Device trace, device 0: ms per traced step inside operations matching
``pattern`` (the collectives) during which no other operation runs there."""

from benchmark import trace


def read(params, run):
    if run.trace is None or not run.traced_steps:
        return None
    total, exposed = trace.exposed_seconds(
        run.trace["events_device0"], run.trace["selfs_device0"],
        params["pattern"])
    run.notes.append(f"collective time on device 0: total "
                     f"{1e3 * total / run.traced_steps:.4f} ms/step, exposed "
                     f"{1e3 * exposed / run.traced_steps:.4f} ms/step")
    return 1e3 * exposed / run.traced_steps
