"""A Fluid op type's share of its roofline, in %: the least time the chip
could take for the op's work in the traced steps — the larger of its FLOPs
over the peak bf16 FLOP/s and its HBM bytes over the peak bytes/s
(``peaks.json``) — over the device time its forward and gradient scopes
took (``op_device_time``). The work is the configuration's own
``op_work(cfg, traffic)[op]`` of the cell ``workload``, a training step's,
counted from shapes and the same whatever implements the op. Which bound
holds goes on an earlier line.

None where the trace holds none of the scopes, the configuration has no
``op_work``, or the device has no peaks (a CPU)."""

from benchmark import harness
from benchmark.readers import op_device_time


def read(params, run):
    if run.trace is None or not run.traced_steps or run.peaks is None:
        return None
    took = op_device_time.seconds(params)
    if not took:
        return None
    cell = harness.load_cell(harness.load_manifest(), params["workload"])
    if not hasattr(cell.model, "op_work"):
        return None
    work = cell.model.op_work(cell.cfg, cell.traffic)[params["op"]]
    by_flops = work["flops"] / run.peaks["flops_s"]
    by_bytes = work["bytes"] / run.peaks["hbm_bytes_s"]
    least = max(by_flops, by_bytes)
    run.notes.append(
        f"roofline of {params['op']}: {work['flops'] / 1e9:.1f} GFLOP and "
        f"{work['bytes'] / 1e9:.3f} GB a step, at least "
        f"{1e3 * by_flops:.3f} ms by FLOPs and {1e3 * by_bytes:.3f} ms by "
        f"bytes ({'FLOPs' if by_flops >= by_bytes else 'bytes'} bound), "
        f"took {1e3 * took / run.traced_steps:.3f} ms a traced step")
    return 100.0 * least * run.traced_steps / took
