"""The LFM2-24B-A2B cell rehearsed on the CPU at a tiny size (hidden 64;
attention 4 / 2 heads of 16 with QK-norm; 3-tap gated convolutions; 8 of 32
experts top 4, no shared expert; the published layers 1-3: conv + dense,
attention + experts, conv + experts; 40 tokens a step): it runs through the
harness's own functions and is ``correct``, the plain reference agrees with
the system and broken pieces of the mathematics fail check (a), the two new
per-layer metrics read a trace (and nothing from a trace without the op),
and the FLOPs and bytes equal a hand count. Times from these runs mean
nothing, and nothing here counts on how many steps a loaded host fits into
the window."""

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

CELL = "lfm2_24b_a2b_ep8.staged_len8192_b1"
CONFIG = "lfm2_24b_a2b_ep8"
TINY_CFG = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts_routed": 32,
    "num_experts": 8, "vocab_size": 96, "probe_projections": 4,
    "init_std": 0.3, "expert_init_std": 0.3, "head_init_std": 0.3,
    "embedding_init_std": 0.3, "selection_bias_init_mean": 0.0,
    "selection_bias_init_std": 0.1, "row_buffer_factor": 4.0,
    "optimizer": {"kind": "Adam", "learning_rate": 0.003,
                  "clip_global_norm": 1.0},
    # float32 on the CPU: roundings only, and the stated precision IS exact
    "executor": {"mode": "jit", "donate": True, "amp": False},
    "reference": {"rel_tolerance": 1e-4, "stated_precision": "exact",
                  "probe_rel_tolerance": {"logits": 1e-4,
                                          "logits_as_stated": 1e-4},
                  "reason": "float32 on the CPU"},
}
# one sequence in the ring (see the Kimi-Linear rehearsal)
TINY_TRAFFIC = {"length": 40, "ring": 1}
NEW = ("short_conv_device_ms", "short_conv_roofline_pct")
# the accepted cells' attention and experts shares, over this cell's work:
# {metric: (op, seconds of its scopes in the test's trace)}
SHARES = {"attention_roofline_pct.lfm2": ("attention", 0.16),
          "experts_roofline_pct.lfm2": ("experts", 0.22)}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    from paddle_tpu.core import compile_cache
    monkeypatch.setattr(compile_cache, "enable",
                        lambda: (None, compile_cache.CacheStats()))


def test_the_cell_runs_tiny_and_is_correct(manifest):
    """Every check that one step can decide holds whatever the host fits
    into the window; the falling loss is judged where the window held the
    two steps it needs."""
    lines = []
    result = harness.run_cell(
        manifest, CELL, 2 ** 31 + 9, 1.0, False, time.perf_counter(),
        cfg_override=TINY_CFG, traffic_override=TINY_TRAFFIC,
        log=lines.append)
    (checks,) = [json.loads(l[len("checks: "):]) for l in lines
                 if l.startswith("checks: ")]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in harness.metrics_of(manifest, CELL, "end_to_end")}
    ref = checks["reference"]
    assert ref["ok"] and ref["rel_err"] < 1e-5
    assert ref["logits"]["rel_err"] < 1e-4
    assert ref["logits_as_stated"]["rel_err"] < 1e-4
    assert checks["no_compile_in_window"]["ok"]
    assert checks["pallas_native"]["ok"] and checks["losses"]["finite"]
    if result["attempted"] >= 2:
        assert result["correct"] is True, checks


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


def test_broken_pieces_and_an_all_bfloat16_run_fail_check_a(manifest):
    """The system passes check (a); the references with B and C swapped,
    the taps reversed, a SiLU after the convolution, QK-norm left out and
    the top 4 not renormalised are further from the system's probe than
    the chip's tolerances allow, and an all-bfloat16 run of the reference,
    handed to the harness's check (a) as the system's outputs, fails it at
    the chip's tolerances."""
    cell, weights, feed, loss, probe = _check_step(manifest)
    n = cell.cfg["probe_projections"]
    assert probe.shape == (n, cell.cfg["vocab_size"])
    assert harness.check_reference(cell, weights, feed, loss, probe)["ok"]
    chip = harness.load_cell(manifest, CELL).cfg["reference"]
    limit = max(chip["probe_rel_tolerance"].values())
    assert limit <= 1e-2
    ref = cell.model._reference()
    tok, lab = (np.asarray(feed[k])[0, :, 0] for k in ("tokens", "labels"))
    assert (lab[:-1] == tok[1:]).all()

    def distance(**how):
        run = ref.run(cell.cfg, weights, tok, lab, **how)
        want = cell.model.sign_projections(tok, run[1], n)
        return np.abs(want - probe).max() / np.abs(want).max()

    assert distance() < 1e-4
    for mutation in ("b_c_swapped", "taps_reversed", "silu_added",
                     "qk_norm_left_out", "not_renormalised"):
        assert distance(mutate=mutation) > limit, mutation
    bf16 = ref.run(cell.cfg, weights, tok, lab, precision="bfloat16")
    at_chip_limits = SimpleNamespace(**dict(vars(cell),
                                            cfg=dict(cell.cfg, reference=chip)))
    verdict = harness.check_reference(
        at_chip_limits, weights, feed, float(bf16[0]),
        cell.model.sign_projections(tok, np.asarray(bf16[1]), n))
    assert not verdict["ok"]
    assert verdict["logits"]["rel_err"] > chip["probe_rel_tolerance"][
        "logits"]


def test_the_two_new_metrics_read_a_trace(manifest, monkeypatch):
    """On the CPU no trace has a device plane, so the readers are handed
    one: the gated convolutions' time is their two scopes' sum per step,
    the roofline share THIS configuration's op_work over it, under 100%
    and bytes bound; a trace without the op reads nothing."""
    by_scope = {"fwd/gated_short_conv": 0.01,
                "bwd/gated_short_conv_grad": 0.02,
                "fwd/causal_self_attention": 0.1, "fwd/mul": 2.0}
    monkeypatch.setattr(program_trace, "load_run", lambda: {
        "by_scope": by_scope, "devices": {0: []}, "window": (0.0, 1.0)})
    run = SimpleNamespace(trace={}, traced_steps=10, notes=[],
                          peaks=harness.load_peaks("TPU v5 lite"))
    per_layer = [m for m in manifest["per_layer"] if m["name"] in NEW]
    assert [m["workloads"] for m in per_layer] == [[CELL]] * 2
    metrics = harness.read_layer_metrics({"per_layer": per_layer}, CELL,
                                         run, log=lambda *_: None)
    assert metrics["short_conv_device_ms"]["value"] == pytest.approx(3.0)
    cell = harness.load_cell(manifest, CELL)
    work = cell.model.op_work(cell.cfg, cell.traffic)["short_conv"]
    assert metrics["short_conv_roofline_pct"]["value"] == pytest.approx(
        100 * work["bytes"] / 8.19e11 / 0.003)
    assert 0 < metrics["short_conv_roofline_pct"]["value"] < 100
    assert len(run.notes) == 1 and "bytes bound" in run.notes[0]
    # the seven accepted metrics that list the cell read it too, and nothing
    # wired to another configuration's op_work does
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(SHARES) | {
        "exec_host_ms", "exec_enqueue_ms", "fwd_device_ms", "bwd_device_ms",
        "opt_device_ms", "experts_device_ms", "attention_device_ms"}
    # a program without the op: no gated_short_conv scope
    monkeypatch.setattr(program_trace, "load_run", lambda: {
        "by_scope": {"fwd/mul": 1.0}, "devices": {0: []},
        "window": (0.0, 1.0)})
    assert harness.read_layer_metrics({"per_layer": per_layer}, CELL, run,
                                      log=lambda *_: None) == {}
    monkeypatch.setattr(program_trace, "load_run", lambda: None)
    assert harness.read_layer_metrics({"per_layer": per_layer}, CELL, run,
                                      log=lambda *_: None) == {}


@pytest.mark.parametrize("name", sorted(SHARES))
def test_the_attention_and_experts_shares_read_this_configurations_work(
        manifest, monkeypatch, name):
    """``attention_roofline_pct.lfm2`` / ``experts_roofline_pct.lfm2``: the
    op's scopes' device time per step (those of ``attention_device_ms`` /
    ``experts_device_ms``) under THIS configuration's ``op_work`` of the
    op, under 100%; the Mellum2 cell's share of the same op reads its own
    work, and a trace without the op reads nothing."""
    by_scope = {"fwd/causal_self_attention": 0.05,
                "bwd/causal_self_attention_grad": 0.1,
                "fwd/rotary_embedding": 0.005,
                "bwd/rotary_embedding_grad": 0.005,
                "fwd/routed_experts": 0.07, "bwd/routed_experts_grad": 0.15,
                "fwd/gated_short_conv": 0.01, "fwd/mul": 2.0}
    monkeypatch.setattr(program_trace, "load_run", lambda: {
        "by_scope": by_scope, "devices": {0: []}, "window": (0.0, 1.0)})
    run = SimpleNamespace(trace={}, traced_steps=10, notes=[],
                          peaks=harness.load_peaks("TPU v5 lite"))
    (metric,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELL]
    assert (metric["layer"], metric["moves"]) == ("op lowerings",
                                                  "step_ms_p50")
    value = harness.read_layer_metrics({"per_layer": [metric]}, CELL, run,
                                       log=lambda *_: None)[name]["value"]
    op, took = SHARES[name]
    cell = harness.load_cell(manifest, CELL)
    work = cell.model.op_work(cell.cfg, cell.traffic)[op]
    least = max(work["flops"] / 1.97e14, work["bytes"] / 8.19e11)
    assert value == pytest.approx(100 * least / (took / 10))
    assert 0 < value < 100
    other = {"attention": "attention_roofline_pct",
             "experts": "experts_roofline_pct"}[op]
    (accepted,) = [m for m in manifest["per_layer"] if m["name"] == other]
    assert CELL not in accepted["workloads"]
    read = harness.read_layer_metrics(
        {"per_layer": [accepted]}, accepted["workloads"][0], run,
        log=lambda *_: None)[other]["value"]
    assert read != pytest.approx(value)     # Mellum2's work, not LFM2's
    monkeypatch.setattr(program_trace, "load_run", lambda: {
        "by_scope": {"fwd/mul": 1.0}, "devices": {0: []},
        "window": (0.0, 1.0)})
    assert harness.read_layer_metrics({"per_layer": [metric]}, CELL, run,
                                      log=lambda *_: None) == {}


def test_flops_and_bytes_equal_a_hand_count(manifest):
    cell = harness.load_cell(manifest, CELL)
    cfg, model = cell.cfg, cell.model
    t = 8192
    assert (cell.traffic["length"], cell.traffic["batch"]) == (t, 1)
    work = model.op_work(cfg, cell.traffic)
    # four conv layers: X [T, 6144] in and out [T, 2048] forward; X and d
    # out in, d X [T, 6144] out backward; bfloat16; the filter 3 x 2048
    # float32 read twice and its gradient written
    assert work["short_conv"]["bytes"] == 4 * (
        2 * t * (6144 + 2048 + 6144 + 2048 + 6144) + 3 * 4 * 3 * 2048)
    # 1.48 GB, 1.80 ms a step at 819 GB/s
    assert 1.47e9 < work["short_conv"]["bytes"] < 1.49e9
    assert 1.79e-3 < work["short_conv"]["bytes"] / 819e9 < 1.81e-3
    assert work["short_conv"]["flops"] == 4 * t * 2048 * 22
    assert work["short_conv"]["flops"] / 197e12 \
        < work["short_conv"]["bytes"] / 819e9
    # one attention layer, 32 heads of 64 over T (T + 1) / 2 pairs
    assert work["attention"]["flops"] == 12 * 64 * 32 * (t * (t + 1) // 2)
    # held rows at their expectation: 8192 x 4 x 8 / 64 = 4096 a layer,
    # four routed layers
    assert work["experts"]["flops"] == 4 * 6 * (
        4096 * 3 * 2048 * 1536 + t * 2048 * 64)
    products = (4 * 4 * 2048 * 2048                 # four conv mixers
                + 2 * 2048 * 2048 + 2 * 2048 * 512  # attention
                + 3 * 2048 * 11776                  # the dense MLP
                + 2048 * 8192)                      # the head
    feed = {"tokens": np.zeros((1, t, 1), np.int32)}
    assert model.train_flops(cfg, feed) == (
        6 * t * products + work["experts"]["flops"]
        + work["attention"]["flops"])
    assert 9.9e12 < model.train_flops(cfg, feed) < 10.0e12


def test_the_configuration_keeps_every_published_width(manifest):
    """Every number of the catalog's ``config`` is in the file under its
    key, but the three keys ``reduced`` names, whose published values stand
    beside them."""
    cfg = harness.load_cell(manifest, CELL).cfg
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts_per_tok": 4,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 9 + [
        "full_attention", "conv"]
    assert cfg["layer_types"].count("full_attention") == 10
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 64,
                                "vocab_size": 65536}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 8192)
    assert cfg["num_experts_routed"] == 64 and cfg["expert_offset"] == 0
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert cfg["vocab_size"] * 8 == 65536
    ref = harness.load_cell(manifest, CELL).model._reference()
    assert ref.layer_kinds(cfg) == [
        ("conv", "dense"), ("full_attention", "sparse")] + [
        ("conv", "sparse")] * 3


def test_the_benchmarks_reference_is_the_repos(manifest):
    here = os.path.join(ROOT, "benchmark", "configs", CONFIG, "reference.py")
    there = os.path.join(ROOT, "paddle_tpu", "testing", "reference",
                         "lfm2_moe.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()
