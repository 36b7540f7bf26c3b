"""A count the program keeps, read as it is.

params: ``names`` (program counters, summed), ``at``: ``setup_end`` (the
value when set-up ended) or ``window`` (the change over the window)."""


def read(params, run):
    end, begin = run.counters["window_end"], run.counters["setup_end"]
    total = 0
    for name in params["names"]:
        if params["at"] == "setup_end":
            total += begin.get(name, 0)
        elif params["at"] == "window":
            total += end.get(name, 0) - begin.get(name, 0)
        else:
            raise ValueError(f"unknown 'at': {params['at']!r}")
    return total
