"""The configuration's own training FLOPs of the traced steps over traced
wall time x chips x the peak bf16 FLOP/s of the benchmark's peaks table,
in %. Not a kernel's roofline share: it says nothing about idle time."""


def read(params, run):
    if not run.traced_steps or run.peaks is None or not run.traced_wall_s:
        return None
    return 100.0 * run.traced_flops / (
        run.traced_wall_s * run.chips * run.peaks["flops_s"])
