"""Device trace: the share of device 0's busy time spent in operations whose
name matches ``pattern`` (self time), in %."""

from benchmark import trace


def read(params, run):
    if run.trace is None or not run.trace["busy_s_device0"]:
        return None
    matched = trace.matching_seconds(run.trace["selfs_device0"],
                                     params["pattern"])
    return 100.0 * matched / run.trace["busy_s_device0"]
