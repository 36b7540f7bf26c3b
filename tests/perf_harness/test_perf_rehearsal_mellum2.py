"""The Mellum2 cell rehearsed on the CPU at a tiny size (hidden 64, 4 / 2
heads of 16, 8 of 16 experts top 2, window 8, 64 tokens a step): it runs
through the harness's own functions and is ``correct``, the plain reference
agrees with the system and a broken piece of the mathematics fails check
(a), the four new per-layer metrics read a trace's scopes, and the FLOPs
count held rows and band pairs. Times from these runs mean nothing."""

import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import harness, program_trace  # noqa: E402

sys.path.remove(ROOT)

CELL = "mellum2_12b_ep8.staged_len8192_b1"
TINY_CFG = {
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "moe_intermediate_size": 32,
    "num_experts_routed": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "sliding_window": 8, "vocab_size": 96, "probe_projections": 4,
    "init_std": 0.3, "embedding_init_std": 0.3, "row_buffer_factor": 3.0,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "optimizer": {"kind": "Adam", "learning_rate": 0.003,
                  "clip_global_norm": 1.0},
    # float32 on the CPU: roundings only, and the stated precision IS exact
    "executor": {"mode": "jit", "donate": True, "amp": False},
    "reference": {"rel_tolerance": 1e-4, "stated_precision": "exact",
                  "probe_rel_tolerance": {"logits": 1e-4,
                                          "logits_as_stated": 1e-4},
                  "reason": "float32 on the CPU"},
}
TINY_TRAFFIC = {"length": 64, "ring": 2}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    from paddle_tpu.core import compile_cache
    monkeypatch.setattr(compile_cache, "enable",
                        lambda: (None, compile_cache.CacheStats()))
    monkeypatch.setattr(harness, "TRACE_AFTER_S", 0.05)
    monkeypatch.setattr(harness, "TRACE_MIN_S", 0.05)
    monkeypatch.setattr(harness, "TRACE_MIN_STEPS", 3)


def _run(manifest, trace=False, tmp_path=None, cfg=None):
    lines = []
    result = harness.run_cell(
        manifest, CELL, 2 ** 31 + 5, 1.0, trace, time.perf_counter(),
        cfg_override={**TINY_CFG, **(cfg or {})},
        traffic_override=TINY_TRAFFIC, log=lines.append,
        trace_dir=str(tmp_path) if tmp_path else None)
    (checks,) = [json.loads(l[len("checks: "):]) for l in lines
                 if l.startswith("checks: ")]
    return result, checks, lines


def test_the_cell_runs_tiny_and_is_correct(manifest):
    result, checks, _ = _run(manifest)
    assert result["correct"] is True, checks
    assert result["attempted"] >= 2 and result["failed"] == 0
    wanted = {m["name"] for m in harness.metrics_of(manifest, CELL,
                                                    "end_to_end")}
    assert set(result["metrics"]) == wanted
    ref = checks["reference"]
    assert ref["rel_err"] < 1e-5
    assert ref["logits"]["rel_err"] < 1e-4
    assert ref["logits_as_stated"]["rel_err"] < 1e-4
    assert checks["losses"]["tenths"][-1] < checks["losses"]["tenths"][0]


def _check_step(manifest):
    """The tiny cell's program, its start-up weights, its check feed and
    the system's (loss, probe) on it."""
    import jax

    from benchmark.session import executor_check_step, stage_ring

    cell = harness.load_cell(manifest, CELL, TINY_CFG, TINY_TRAFFIC)
    ctx = harness.make_context(cell, seed=7)
    prog = harness.start_program(ctx)
    weights = harness.snapshot_weights(prog)
    feed, _ = stage_ring(ctx)[0]
    loss, probe = jax.device_get(executor_check_step(prog, feed))
    return cell, weights, feed, float(np.reshape(loss, ())), probe


def test_a_broken_piece_of_the_mathematics_fails_check_a(manifest):
    """The system passes check (a); the probe of the reference with the
    window ignored (what a system that ignored it would be compared with)
    is further from the system's than the tolerances the file gives for the
    chip (1e-2 at most) allow, and so is an all-bfloat16 run's here."""
    cell, weights, feed, loss, probe = _check_step(manifest)
    assert harness.check_reference(cell, weights, feed, loss, probe)["ok"]
    chip = harness.load_cell(manifest, CELL).cfg["reference"]
    ref = cell.model._reference()
    tokens = np.asarray(feed["tokens"])[0, :, 0]
    labels = np.asarray(feed["labels"])[0, :, 0]

    def distance(**how):
        logits = ref.run(cell.cfg, weights, tokens, labels, **how)[1]
        want = cell.model.sign_projections(
            tokens, logits, cell.cfg["probe_projections"])
        return np.abs(want - probe).max() / np.abs(want).max()

    assert distance() < 1e-4
    assert distance(mutate="window_ignored") > max(
        chip["probe_rel_tolerance"].values())
    assert distance(precision="bfloat16") > max(
        chip["probe_rel_tolerance"].values())


def test_the_four_new_metrics_read_a_traces_scopes(manifest, monkeypatch):
    """On the CPU no trace has a device plane, so the readers are handed
    one that says how long each scope took: the device times are the
    scopes' sums per step, and the roofline shares are the configuration's
    op_work over them, under 100%."""
    by_scope = {"fwd/routed_experts": 0.30, "bwd/routed_experts_grad": 0.60,
                "fwd/causal_self_attention": 0.40,
                "bwd/causal_self_attention_grad": 1.00,
                "fwd/rotary_embedding": 0.05,
                "bwd/rotary_embedding_grad": 0.05, "fwd/mul": 2.0}
    monkeypatch.setattr(program_trace, "load_run",
                        lambda: {"by_scope": by_scope})
    run = SimpleNamespace(trace={}, traced_steps=10, notes=[],
                          peaks=harness.load_peaks("TPU v5 lite"))
    metrics = harness.read_layer_metrics(
        {"per_layer": [m for m in manifest["per_layer"]
                       if m["name"].startswith(("experts_", "attention_"))]},
        CELL, run, log=lambda *_: None)
    assert metrics["experts_device_ms"]["value"] == pytest.approx(90.0)
    assert metrics["attention_device_ms"]["value"] == pytest.approx(150.0)
    cell = harness.load_cell(manifest, CELL)
    work = cell.model.op_work(cell.cfg, cell.traffic)
    assert metrics["experts_roofline_pct"]["value"] == pytest.approx(
        100 * work["experts"]["flops"] / 1.97e14 / 0.090)
    assert 0 < metrics["attention_roofline_pct"]["value"] < 100
    assert len(run.notes) == 2 and "FLOPs bound" in run.notes[0]
    # a trace without the scopes (the parent's program): nothing to read
    monkeypatch.setattr(program_trace, "load_run",
                        lambda: {"by_scope": {"fwd/mul": 1.0}})
    assert harness.read_layer_metrics(
        {"per_layer": [m for m in manifest["per_layer"]
                       if m["name"].startswith("experts_")]},
        CELL, run, log=lambda *_: None) == {}


def test_flops_count_held_rows_and_band_pairs(manifest):
    cell = harness.load_cell(manifest, CELL)
    cfg, model = cell.cfg, cell.model
    t = 8192
    work = model.op_work(cfg, {"batch": 1, "length": t})
    # by hand: three window layers of 1024 and one full layer, 32 heads
    window = 1024 * 1025 // 2 + (t - 1024) * 1024
    full = t * (t + 1) // 2
    assert model.band_pairs(t, 1024) == window
    assert work["attention"]["flops"] == 3 * 4 * 128 * 32 * (3 * window
                                                             + full)
    every = model.op_work(dict(cfg, sliding_window=0),
                          {"batch": 1, "length": t})
    assert every["attention"]["flops"] == 3 * 4 * 128 * 32 * 4 * full
    # held rows at their expectation: 8192 x 8 x 8 / 64 = 8192 a layer
    assert work["experts"]["flops"] == 4 * 6 * (
        8192 * 3 * 2304 * 896 + t * 2304 * 64)
    half = model.op_work(dict(cfg, num_experts=4), {"batch": 1, "length": t})
    assert half["experts"]["flops"] < 0.52 * work["experts"]["flops"]
    feed = {"tokens": np.zeros((1, t, 1), np.int32)}
    products = 4 * (2 * 2304 * 4096 + 2 * 2304 * 512) + 2304 * 12288
    assert model.train_flops(cfg, feed) == (
        6 * t * products + work["experts"]["flops"]
        + work["attention"]["flops"])
    assert 9.5e12 < model.train_flops(cfg, feed) < 9.8e12


def test_the_benchmarks_reference_is_the_repos(manifest):
    here = os.path.join(ROOT, "benchmark", "configs", "mellum2_12b_ep8",
                        "reference.py")
    there = os.path.join(ROOT, "paddle_tpu", "testing", "reference",
                         "mellum2.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()
