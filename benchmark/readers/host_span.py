"""A statistic of one of the benchmark's host spans.

params: ``span`` (its name), ``stat`` (``median`` or ``sum``), ``scale``
(seconds are multiplied by it: 1000 for ms)."""

import statistics


def read(params, run):
    seconds = run.spans.get(params["span"])
    if not seconds:
        return None
    stat = {"median": statistics.median, "sum": sum}[params["stat"]]
    return stat(seconds) * float(params.get("scale", 1.0))
