"""Per-kernel device timing of one dispatch of a target.

Argument parsing over ``obs.perf.profile``: runs the target's step under
``jax.profiler.trace`` and aggregates the device events (fusions,
convolutions, copies) by name — the dynamic analog of
tools/hlo_report.py's static traffic estimate, and the table the
roofline argument rests on. Default target is the flagship ResNet-50
training step exactly as bench.py runs it; ``--bundle DIR`` retargets
any ``save_inference_model`` export or registry version dir
(tools/profile_common.py is the shared scaffolding).

Usage: python tools/profile_step.py [--batch 256] [--steps 8] [--top 40]
                                    [--no-s2d] [--hlo-match DUMP.txt]
                                    [--bundle DIR]
"""

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import profile_common


def load_hlo_annotations(path):
    """Map instruction name -> (defining line, static traffic estimate)
    from an optimized-HLO dump (tools/hlo_report.py --dump), to annotate
    fusion names with their root op and a GB/s column."""
    from paddle_tpu.obs.perf import hlo_shape_bytes
    shapes, nbytes = {}, {}
    for ln in open(path):
        m = re.match(r"\s*%?([\w.\-]+) = (.+)", ln)
        if m:
            shapes[m.group(1)] = m.group(2)[:150]
            nbytes[m.group(1)] = hlo_shape_bytes(m.group(2))
    return shapes, nbytes


def main():
    ap = argparse.ArgumentParser()
    profile_common.add_target_args(ap)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--hlo-match", default=None,
                    help="optimized-HLO dump (tools/hlo_report.py --dump) "
                         "to annotate fusion names with their root op")
    args = ap.parse_args()

    shapes, nbytes = {}, {}
    if args.hlo_match and os.path.exists(args.hlo_match):
        shapes, nbytes = load_hlo_annotations(args.hlo_match)

    from paddle_tpu.core import compile_cache
    from paddle_tpu.obs import perf

    compile_cache.enable()
    target = profile_common.build_target(args)
    print(f"target: {target.label}")
    with target.ctx():
        res = perf.profile(target.step_fn(), steps=args.steps,
                           warmup=args.warmup, top=args.top)

    where = "device" if res["on_device"] else \
        "HOST (no device lanes in the trace — CPU backend)"
    print(f"wall: {res['wall_s_per_step']*1e3:.2f} ms/step   "
          f"{where} leaf total: {res['busy_us_per_step']/1e3:.2f} ms/step "
          f"over {res['steps']} steps")

    print("\nby kernel kind (trailing .N stripped):")
    for row in res["by_kind"][:15]:
        print(f"  {row['us_per_step']:10.1f} us {row['pct']:6.2f}% "
              f" {row['name']}")

    from paddle_tpu.parallel.planner import machine_rates
    hbm = machine_rates()["hbm_bytes_s"]
    peak = f"{hbm / 1e9:.0f}" if hbm else "n/a"
    print(f"\ntop {args.top} instances (GB/s = static operand+result bytes "
          f"over measured time; this device's HBM peak {peak}):")
    print(f"{'us/step':>10s} {'%':>6s} {'GB/s':>6s}  name | hlo")
    for row in res["top"]:
        us_step = row["us_per_step"]
        gbs = nbytes.get(row["name"], 0) / (us_step * 1e-6) / 1e9 \
            if us_step else 0
        print(f"{us_step:10.1f} {row['pct']:6.2f} {gbs:6.0f}  "
              f"{row['name']} | {shapes.get(row['name'], '')[:110]}")


if __name__ == "__main__":
    sys.exit(main())
