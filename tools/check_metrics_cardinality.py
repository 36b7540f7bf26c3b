#!/usr/bin/env python
"""Guard against unbounded metric-label cardinality: every label name a
registered family declares must come from the BOUNDED vocabulary below
(an enum, a process-unique instance id, or a capped funnel), and every
family whose label values can originate ON THE WIRE must keep its
``__other__`` overflow funnel working — a misbehaving peer must never be
able to grow scrape-visible series without bound.

Two checks, same ratchet shape as ``check_flags_doc.py`` /
``check_metrics_doc.py`` (tier-1 runs this as a subprocess,
tests/test_obs_plane.py):

1. **declared label sets are bounded** — import every wiring module
   (the check_metrics_doc import list), walk the registry, and fail any
   family using a label name absent from ``BOUNDED_LABELS``. Adding a
   label name here is a REVIEWED declaration that its value space is
   bounded; an undeclared name is exactly the drift this gate exists to
   catch (someone labeling by user id, method string, or file path).

2. **wire-origin funnels hold** — for each family in ``WIRE_FED``,
   exercise the funnel: push more distinct wire-supplied names than the
   cap plus a non-identifier name through the producing path and assert
   the registry children stay within cap + builtins + ``__other__``,
   with the overflow landing in ``__other__``.

Exit 0 when both hold; exit 1 listing the violations.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# label name -> why its value space is bounded. Adding a name is a
# reviewed claim; the gate fails on any label name not listed here.
BOUNDED_LABELS = {
    "instance": "process-unique obs.metrics.next_instance ids — one per "
                "component constructed, bounded by process lifetime",
    "bucket": "engine batch/prompt buckets — a small parsed flag set",
    "phase": "generation phases: prefill/chunk/decode",
    "mode": "executor modes: eager/jit; Pallas lowering modes: "
            "native/interpret",
    "op_type": "registered op types — the fixed op registry",
    "kind": "small code-site enums (retrace kinds, flight event kinds)",
    "role": "wire roles: client/server",
    "method": "RPC method names — wire-origin, funneled past "
              "WireStats._METHOD_LABEL_CAP (or non-identifier shape) "
              "into __other__ (the funnel check below asserts it)",
    "supervisor": "ChildSupervisor instance ids (next_instance)",
    "child": "supervised child indices — bounded by fleet size",
    "kernel": "Pallas kernel families — a fixed code-site set",
    "outcome": "small code-site outcome enums (freeze/rollout results)",
    "rule": "declared SLO rule names — a reviewed config set",
    "window": "declared SLO window lengths — from rule configs",
    "trigger": "incident trigger enums: breach/canary_failed/"
               "child_restart/manual",
    "site": "compile-site enums (obs.perf: jit_step/jit_scan/"
            "sharded_step/engine_warmup/engine_infer/genengine_*/"
            "attribute/exec_cache_save, and eager for builds no such "
            "site owns) — "
            "a fixed code-site set; per-executable "
            "identity rides the CompileRecord, never a label",
    "stage": "compile stages (obs.perf.STAGES): trace/lower/"
             "xla_compile/cache_load/other",
    "source": "where a built executable came from: compiled/cache",
    "reason": "artifact reject reasons — the fixed enums "
              "serving.execcache.REJECT_REASONS (format/manifest/"
              "fingerprint/deserialize/run_failed), "
              "serving.generate.kvstore.REJECT_REASONS (format/"
              "manifest/fingerprint/deserialize) and "
              "parallel.planner.REJECT_REASONS (format/manifest/"
              "fingerprint/deserialize)",
    "device": "local jax devices (platform:id) — bounded by the "
              "attached hardware",
    "tenant": "tenant ids — wire-origin, funneled past "
              "serving_tenant_label_cap (or non-identifier shape) into "
              "__other__ by serving.batcher.TenantQuotas (the funnel "
              "check below asserts it)",
}

# families whose label VALUES can arrive off the RPC wire; each entry
# names the wire-fed label and the funnel-exercise below must show the
# __other__ cap holding for it
WIRE_FED = {
    "paddle_tpu_wire_calls": "method",
    "paddle_tpu_wire_call_seconds": "method",
}

# tenant-labeled families: wire-fed through TenantQuotas, which owns its
# own funnel (exercised separately below — the producing path differs
# from WireStats.note)
TENANT_FED = {
    "paddle_tpu_tenant_requests": "tenant",
    "paddle_tpu_tenant_rejected": "tenant",
}


def registered_families():
    """Import every wiring module (the check_metrics_doc list) and
    return the registry's families."""
    import paddle_tpu  # noqa: F401
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
    import paddle_tpu.serving.autoscale     # noqa: F401
    import paddle_tpu.serving.batcher       # noqa: F401
    import paddle_tpu.serving.engine        # noqa: F401
    import paddle_tpu.serving.generate.kvcache    # noqa: F401
    import paddle_tpu.serving.generate.kvstore    # noqa: F401
    import paddle_tpu.serving.generate.scheduler  # noqa: F401
    import paddle_tpu.serving.router        # noqa: F401
    import paddle_tpu.serving.server        # noqa: F401
    from paddle_tpu.obs import REGISTRY
    return {name: REGISTRY.get(name) for name in REGISTRY.names()}


def unbounded_label_violations(families):
    """[(family, label)] for every declared label name not in the
    bounded vocabulary."""
    out = []
    for name, fam in sorted(families.items()):
        for label in fam.label_names:
            if label not in BOUNDED_LABELS:
                out.append((name, label))
    return out


def wire_funnel_violations(families):
    """Exercise the __other__ funnel on every wire-fed family; returns
    a list of violation strings (empty = funnels hold)."""
    from paddle_tpu.distributed import rpc as rpcmod

    out = []
    for fam_name, label in sorted(WIRE_FED.items()):
        fam = families.get(fam_name)
        if fam is None:
            out.append(f"{fam_name}: wire-fed family not registered "
                       "(stale WIRE_FED entry or missing wiring import)")
            continue
        if label not in fam.label_names:
            out.append(f"{fam_name}: wire-fed label {label!r} not in "
                       f"declared labels {fam.label_names}")
            continue
    # one funnel exercise drives BOTH wire families (WireStats.note is
    # the single producing path for method-labeled series): flood a
    # fresh endpoint past the cap with wire-shaped names plus one
    # non-identifier name, then assert the registry series stayed capped
    # and the overflow funneled
    ws = rpcmod.WireStats(role="cardinality_check")
    cap = ws._METHOD_LABEL_CAP
    for i in range(cap + 16):
        ws.note(f"wirefuzz_{i}", 1, 1, 0.0)
    ws.note('bad"} 1\nforged 9', 1, 1, 0.0)     # non-identifier shape
    for fam_name in WIRE_FED:
        fam = families.get(fam_name)
        if fam is None:
            continue
        methods = {key[fam.label_names.index("method")]
                   for key in fam.children()
                   if key[fam.label_names.index("role")]
                   == "cardinality_check"}
        if "__other__" not in methods:
            out.append(f"{fam_name}: flooding past the cap never funneled "
                       "into __other__ — the wire-origin funnel is gone")
        over = {m for m in methods
                if m != "__other__" and m.startswith("wirefuzz_")}
        if len(over) > cap:
            out.append(f"{fam_name}: {len(over)} distinct wire-origin "
                       f"method labels exceed the declared cap {cap}")
        forged = [m for m in methods if "\n" in m or '"' in m]
        if forged:
            out.append(f"{fam_name}: non-identifier wire name reached "
                       f"the label set verbatim: {forged!r}")
    # the tenant funnel: flood a fresh TenantQuotas past its label cap
    # with wire-shaped tenant ids plus one non-identifier name, assert
    # the tenant-labeled series stayed capped with overflow in __other__
    from paddle_tpu.serving.batcher import TenantQuotas
    tq = TenantQuotas(rate=1000.0, burst=1000, label_cap=8)
    tcap = tq._label_cap
    for i in range(tcap + 16):
        tq.try_acquire(f"tenantfuzz_{i}")
    tq.try_acquire('bad"} 1\nforged 9')            # non-identifier shape
    for fam_name, label in sorted(TENANT_FED.items()):
        fam = families.get(fam_name)
        if fam is None:
            out.append(f"{fam_name}: tenant-fed family not registered "
                       "(stale TENANT_FED entry or missing wiring "
                       "import)")
            continue
        if label not in fam.label_names:
            out.append(f"{fam_name}: tenant-fed label {label!r} not in "
                       f"declared labels {fam.label_names}")
            continue
        tenants = {key[fam.label_names.index("tenant")]
                   for key in fam.children()
                   if key[fam.label_names.index("instance")]
                   == tq.obs_instance}
        if "__other__" not in tenants:
            out.append(f"{fam_name}: flooding past the cap never "
                       "funneled into __other__ — the tenant funnel is "
                       "gone")
        over = {t for t in tenants
                if t != "__other__" and t.startswith("tenantfuzz_")}
        if len(over) > tcap:
            out.append(f"{fam_name}: {len(over)} distinct tenant labels "
                       f"exceed the declared cap {tcap}")
        forged = [t for t in tenants if "\n" in t or '"' in t]
        if forged:
            out.append(f"{fam_name}: non-identifier tenant id reached "
                       f"the label set verbatim: {forged!r}")
    return out


def main():
    families = registered_families()
    if not families:
        print("check_metrics_cardinality: registry empty after wiring "
              "imports — the checker is broken, not the metrics",
              file=sys.stderr)
        return 1
    failures = []
    for fam_name, label in unbounded_label_violations(families):
        failures.append(
            f"{fam_name}: label {label!r} is not in the bounded "
            "vocabulary (tools/check_metrics_cardinality.py "
            "BOUNDED_LABELS) — declare why its value space is bounded "
            "or stop labeling by it")
    failures.extend(wire_funnel_violations(families))
    if failures:
        print(f"check_metrics_cardinality: {len(failures)} violations:",
              file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(f"check_metrics_cardinality: OK — {len(families)} families, "
          f"every label bounded; wire funnels hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
