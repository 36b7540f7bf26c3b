"""Cost attribution (obs.perf): the extracted HLO shape-bytes estimator
(hardened for scalar and tuple-nested shapes), ``attribute()`` over
programs / bundles / engines, and ``tools/hlo_report.py``'s ``--bundle``
scaffolding.
"""

import json

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.obs import perf
from paddle_tpu.testing.models import build_mlp, mlp_feed


# ---------------------------------------------------------------------------
# hlo_shape_bytes: the static estimator, unit-tested directly
# ---------------------------------------------------------------------------

def test_shape_bytes_plain_arrays():
    assert perf.hlo_shape_bytes("f32[4,8]{1,0}") == 4 * 8 * 4
    assert perf.hlo_shape_bytes("bf16[256,56,56,64]{3,2,1,0:T(8,128)}") \
        == 256 * 56 * 56 * 64 * 2
    assert perf.hlo_shape_bytes("s64[3]") == 24
    assert perf.hlo_shape_bytes("pred[7]{0}") == 7
    assert perf.hlo_shape_bytes("u8[16]") == 16
    assert perf.hlo_shape_bytes("s16[4]") == 8


def test_shape_bytes_scalar():
    # f32[] is a SCALAR — zero dims is ONE element, not zero bytes
    assert perf.hlo_shape_bytes("f32[]") == 4
    assert perf.hlo_shape_bytes("s32[]") == 4
    assert perf.hlo_shape_bytes("f64[]") == 8
    assert perf.hlo_shape_bytes("pred[]") == 1


def test_shape_bytes_tuples_nested():
    assert perf.hlo_shape_bytes("(f32[2]{0}, s32[4])") == 8 + 16
    # arbitrary nesting sums every member, scalars included
    assert perf.hlo_shape_bytes("(bf16[2,2]{1,0}, (f32[], pred[3]))") \
        == 8 + 4 + 3
    # an instruction LINE: result shape + operand shapes all counted
    line = ("%add.1 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)")
    assert perf.hlo_shape_bytes(line) == 3 * 32


def test_shape_bytes_ignores_unknown_and_empty():
    assert perf.hlo_shape_bytes("") == 0
    assert perf.hlo_shape_bytes("token[]") == 0
    assert perf.hlo_shape_bytes("opaque stuff without shapes") == 0


def test_hlo_entry_rows_parses_entry_only():
    hlo = """HloModule m
%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(f32[4]{0} %p)
}
ENTRY %main (a: f32[4], b: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %b = f32[4]{0} parameter(1)
  %add.0 = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
  ROOT %fus = f32[4]{0} fusion(f32[4]{0} %add.0), kind=kLoop
}
"""
    rows, kind_totals = perf.hlo_entry_rows(hlo)
    kinds = {k for _t, _rb, k, _n, _s in rows}
    assert kinds == {"add", "fusion"}              # parameters skipped
    assert kind_totals["add"] == 3 * 16            # result + 2 operands
    assert kind_totals["fusion"] == 2 * 16


# ---------------------------------------------------------------------------
# attribute(): program / bundle / engine targets
# ---------------------------------------------------------------------------

def test_attribute_program():
    main, startup, loss = build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    res = perf.attribute(main, feed=mlp_feed(4), fetch_list=[loss],
                         executor=exe, scope=scope, top=10)
    json.dumps(res)
    # the CPU backend provides cost_analysis: a 4x16 @ 16x32 @ 32x4 MLP
    # with backward + momentum has real flops
    assert res["cost"]["flops"] > 0
    assert res["cost"]["bytes_accessed"] > 0
    assert res["instructions"] > 0
    assert len(res["rows"]) <= 10
    assert res["rows"][0]["bytes"] >= res["rows"][-1]["bytes"]
    assert res["kind_totals"]
    assert res["compile_seconds"] > 0
    # the analysis itself lands in the compile log under its own site
    assert perf.COMPILE_LOG.records(site="attribute")


def test_attribute_requires_feed_for_programs():
    main, _startup, _loss = build_mlp()
    with pytest.raises(ValueError, match="feed"):
        perf.attribute(main)


def test_attribute_bundle_dir_and_engine(tmp_path):
    from paddle_tpu.serving import InferenceEngine
    main, startup, _loss, logits = build_mlp(return_logits=True)
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    d = str(tmp_path / "bundle")
    fluid.io.save_inference_model(d, ["img"], [logits], exe, main,
                                  scope=scope)
    # a bundle dir synthesizes its own feeds at batch rows
    res = perf.attribute(d, batch=4, top=5,
                         dump_hlo=str(tmp_path / "hlo.txt"))
    assert res["cost"]["flops"] > 0
    assert (tmp_path / "hlo.txt").read_text().startswith("HloModule")
    # an engine target reuses the engine's program/scope/executor
    eng = InferenceEngine(d, buckets=[2])
    res2 = perf.attribute(eng, batch=2, top=5)
    assert res2["instructions"] > 0


# ---------------------------------------------------------------------------
# the CLIs' shared scaffolding (tools/profile_common.py --bundle mode)
# ---------------------------------------------------------------------------

def test_profile_common_bundle_target(tmp_path):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import profile_common

    main, startup, _loss, logits = build_mlp(return_logits=True)
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    d = str(tmp_path / "bundle")
    fluid.io.save_inference_model(d, ["img"], [logits], exe, main,
                                  scope=scope)
    target = profile_common.build_bundle(d, batch=2)
    assert target.feeds[0]["img"].shape == (2, 16)
    step = target.step_fn()
    with target.ctx():
        out = step()
    assert np.asarray(out[0]).shape == (2, 4)
