#!/usr/bin/env python
"""Guard against metrics-vs-docs drift: every metric family registered
in the process-wide obs.metrics registry at import/wiring time must
appear as a row in the README's metrics table (a ``| `name` | ... |``
line) — the same ratchet shape as ``check_flags_doc.py``, so the metric
naming contract (``paddle_tpu_<subsystem>_<name>``, stable across
releases) stays enforceable.

Unlike the flags checker this one IMPORTS the wiring modules (metric
families are declared where their subsystems live — a regex over 16
files would rot); it therefore needs the package importable, and tier-1
runs it as a subprocess (tests/test_obs_plane.py).

Exit 0 when the docs cover every registered family (stale README rows
naming unregistered ``paddle_tpu_*`` metrics fail too — the ratchet cuts
both ways); exit 1 listing the drift.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(REPO, "README.md")
sys.path.insert(0, REPO)


def registered_metrics():
    """Import every module that declares metric families; return the
    registry's names. New wiring sites that register families at import
    time are picked up by importing their subsystem here."""
    import paddle_tpu  # noqa: F401  (core.executor families)
    import paddle_tpu.distributed.launch    # noqa: F401
    import paddle_tpu.distributed.param_server  # noqa: F401
    import paddle_tpu.distributed.rpc       # noqa: F401
    import paddle_tpu.obs.recorder          # noqa: F401
    import paddle_tpu.obs.slo               # noqa: F401
    import paddle_tpu.online.freezer        # noqa: F401
    import paddle_tpu.online.pool           # noqa: F401
    import paddle_tpu.online.rollout        # noqa: F401
    import paddle_tpu.online.trainer        # noqa: F401
    import paddle_tpu.ops.pallas            # noqa: F401
    import paddle_tpu.parallel.planner      # noqa: F401
    import paddle_tpu.reader.prefetch       # noqa: F401
    import paddle_tpu.serving.autoscale     # noqa: F401
    import paddle_tpu.serving.batcher       # noqa: F401
    import paddle_tpu.serving.engine        # noqa: F401
    import paddle_tpu.serving.generate.kvcache    # noqa: F401
    import paddle_tpu.serving.generate.kvstore    # noqa: F401
    import paddle_tpu.serving.generate.scheduler  # noqa: F401
    import paddle_tpu.serving.router        # noqa: F401
    import paddle_tpu.serving.server        # noqa: F401
    from paddle_tpu.obs import REGISTRY
    return REGISTRY.names()


def documented_metrics(readme_src):
    """paddle_tpu_* names with a markdown table row: | `name` | ... |"""
    return set(n for n in re.findall(r'^\|\s*`([A-Za-z0-9_]+)`\s*\|',
                                     readme_src, flags=re.MULTILINE)
               if n.startswith("paddle_tpu_"))


def main():
    names = registered_metrics()
    if not names:
        print("check_metrics_doc: registry is empty after wiring imports "
              "— the checker is broken, not the docs", file=sys.stderr)
        return 1
    with open(README) as f:
        documented = documented_metrics(f.read())
    missing = [n for n in names if n not in documented]
    stale = sorted(documented - set(names))
    if missing or stale:
        if missing:
            print("check_metrics_doc: metrics missing from the README "
                  f"metrics table ({len(missing)} of {len(names)}):",
                  file=sys.stderr)
            for n in missing:
                print(f"  | `{n}` | <type> | <labels> | <what it counts> |",
                      file=sys.stderr)
        if stale:
            print("check_metrics_doc: README rows naming metrics that are "
                  f"no longer registered ({len(stale)}):", file=sys.stderr)
            for n in stale:
                print(f"  | `{n}` | ...", file=sys.stderr)
        print("keep the 'Observability' metrics table in README.md in "
              "lockstep with the registry", file=sys.stderr)
        return 1
    print(f"check_metrics_doc: OK — {len(names)} metric families all "
          "documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
