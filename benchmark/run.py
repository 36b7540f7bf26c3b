"""BENCHMARK.json's command: run one cell once, in this one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, when
traced, ``breakdown``. Everything else goes on earlier lines. Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result: nothing falls back to the CPU."""

import time

T_PROCESS_START = time.perf_counter()       # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform={dev.platform!r}); a "
              "cell is measured on the chip or not at all", file=sys.stderr)
        return 2

    from benchmark import harness

    try:
        result = harness.run_cell(harness.load_manifest(), args.workload,
                                  args.seed, args.seconds, bool(args.trace),
                                  T_PROCESS_START)
    except harness.CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
