"""Device trace: the union of the intervals in which an operation runs on
device 0, per traced step, in ms."""


def read(params, run):
    if run.trace is None or not run.traced_steps:
        return None
    return 1e3 * run.trace["busy_s_device0"] / run.traced_steps
