"""A statistic of one of the PROGRAM's own spans, read from the profiler's
trace (``benchmark/program_trace.py``), where they lie on the profiler's
clock beside the device's events.

params: ``span`` (names; a step is ``executor.run`` on one chip and
``sharding.step`` on four), ``minus`` (spans inside each occurrence, on its
thread line, whose time is taken off), ``stat``: ``median`` over the
occurrences, one a traced step, or ``busy_share``: the union of the spans
over the extent of their thread line's spans; ``scale``. With ``notes``,
the span beside the benchmark's own ``step_call`` over the same traced
steps, and the idle gaps of the device by the innermost program span that
covered them on each thread line, go on earlier lines of the output.

None where the trace holds no such span (a program from before PR 26)."""

import statistics

from benchmark import harness, program_trace


def _notes(loaded, names, minus):
    def median_ms(spans):
        seconds = program_trace.span_seconds(loaded["host"], spans)
        return 1e3 * statistics.median(seconds) if seconds else float("nan")

    notes = [f"program spans, median ms over the traced steps: "
             f"{'|'.join(names)} {median_ms(names):.4f}, of which "
             f"{'+'.join(minus)} {median_ms(minus):.4f}; the benchmark's "
             f"{harness.SPAN_CALL} around the same calls "
             f"{median_ms((harness.SPAN_CALL,)):.4f}"]
    laid = program_trace.lay_gaps(loaded["gaps"], loaded["host"])
    for cls, (seconds, per_line) in laid.items():
        lines = "; ".join(
            f"line {i}: " + ", ".join(
                f"{n} {1e3 * s:.3f}"
                for n, s in sorted(spans.items(), key=lambda kv: -kv[1]))
            for i, spans in enumerate(per_line) if spans)
        notes.append(f"device idle under {cls}: {1e3 * seconds:.3f} ms, by "
                     f"the innermost program span on each thread line "
                     f"(ms): {lines or 'no program span covers it'}")
    return notes


def read(params, run):
    if run.trace is None or not run.traced_steps:
        return None
    loaded = program_trace.load_run()
    if loaded is None:
        return None
    names, scale = tuple(params["span"]), float(params.get("scale", 1.0))
    if params["stat"] == "busy_share":
        share = program_trace.busy_share(loaded["host"], names)
        return None if share is None else share * scale
    seconds = program_trace.span_seconds(loaded["host"], names,
                                         tuple(params.get("minus", ())))
    if not seconds:
        return None
    if params.get("notes") and "gaps" in loaded:
        run.notes.extend(_notes(loaded, names,
                                tuple(params.get("minus", ()))))
    return statistics.median(seconds) * scale
