"""The Nemotron-3-Nano cell rehearsed on the CPU at a tiny size (hidden 64;
Mamba-2 8 heads of 8 in 4 groups of state 16, conv 4, chunks of 16; attention
4 / 2 heads of 16; 8 of 32 un-gated experts top 4 of width 32, one shared of
48; the layers ``MEMEM*E``; 72 tokens a step, four chunks and a half): it
runs through the harness's own functions and is ``correct``, the plain
reference agrees with the system and three broken pieces of the mathematics
fail check (a), the two new per-layer metrics read a trace's scopes, and the
FLOPs and bytes equal a hand count. Times from these runs mean nothing, and
nothing here counts on how many steps a loaded host fits into the window."""

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

CELL = "nemotron3_nano_30b_ep16.staged_len4096_b1"
CONFIG = "nemotron3_nano_30b_ep16"
TINY_CFG = {
    "hidden_size": 64, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "n_groups": 4, "ssm_state_size": 16, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "num_experts_routed": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 4, "vocab_size": 96, "probe_projections": 4,
    "init_std": 0.3, "mamba_init_std": 0.3, "mamba_out_init_std": 1.5,
    "attention_init_std": 0.3, "attention_out_init_std": 1.5,
    "expert_init_std": 0.3, "head_init_std": 0.3, "embedding_init_std": 0.3,
    "selection_bias_init_mean": 0.0, "selection_bias_init_std": 0.1,
    "row_buffer_factor": 4.0,
    "optimizer": {"kind": "Adam", "learning_rate": 0.003,
                  "clip_global_norm": 1.0},
    # float32 on the CPU: roundings only, and the stated precision IS exact
    "executor": {"mode": "jit", "donate": True, "amp": False},
    "reference": {"rel_tolerance": 1e-4, "stated_precision": "exact",
                  "probe_rel_tolerance": {"logits": 1e-4,
                                          "logits_as_stated": 1e-4},
                  "reason": "float32 on the CPU"},
}
# one sequence in the ring: with two, a window's losses alternate between
# them and whether its last tenth lies under its first depends on where a
# loaded host cuts it
TINY_TRAFFIC = {"length": 72, "ring": 1}


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
        manifest, CELL, 2 ** 31 + 5, 1.0, False, time.perf_counter(),
        cfg_override=TINY_CFG, traffic_override=TINY_TRAFFIC,
        log=lines.append)
    (checks,) = [json.loads(l[len("checks: "):]) for l in lines
                 if l.startswith("checks: ")]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in harness.metrics_of(manifest, CELL, "end_to_end")}
    assert {"setup_s", "step_ms_p50", "step_ms_p95",
            "samples_per_s"} == set(result["metrics"])
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


def test_broken_pieces_of_the_mathematics_fail_check_a(manifest):
    """The system passes check (a); the probes of the reference with the
    decay applied after the update, with heads reading group ``h % G`` and
    with a gated expert (what systems with those mistakes would be compared
    with) are further from the system's than the tolerances the file gives
    for the chip allow, and so is an all-bfloat16 run's here."""
    cell, weights, feed, loss, probe = _check_step(manifest)
    assert harness.check_reference(cell, weights, feed, loss, probe)["ok"]
    chip = harness.load_cell(manifest, CELL).cfg["reference"]
    limit = max(chip["probe_rel_tolerance"].values())
    assert limit <= 1e-2
    ref = cell.model._reference()
    tokens = np.asarray(feed["tokens"])[0, :, 0]
    labels = np.asarray(feed["labels"])[0, :, 0]

    def distance(**how):
        logits = ref.run(cell.cfg, weights, tokens, labels, **how)[1]
        want = cell.model.sign_projections(
            tokens, logits, cell.cfg["probe_projections"])
        return np.abs(want - probe).max() / np.abs(want).max()

    assert distance() < 1e-4
    for mutation in ("decay_after_update", "group_by_modulo",
                     "gated_expert"):
        assert distance(mutate=mutation) > limit, mutation
    assert distance(precision="bfloat16") > limit


def test_the_two_new_metrics_read_a_traces_scopes(manifest, monkeypatch):
    """On the CPU no trace has a device plane, so the readers are handed
    one that says how long each scope took: the device time is its scopes'
    sum per step (every op of a Mamba-2 mixer that is no mul, no split and
    no plain rms_norm), and the roofline share is THIS configuration's
    op_work over it, under 100%."""
    by_scope = {"fwd/routed_experts": 0.04, "bwd/routed_experts_grad": 0.08,
                "fwd/causal_self_attention": 0.02,
                "bwd/causal_self_attention_grad": 0.05,
                "fwd/ssd_scan": 0.08, "bwd/ssd_scan_grad": 0.20,
                "fwd/causal_conv1d": 0.02, "bwd/causal_conv1d_grad": 0.04,
                "fwd/gated_rms_norm": 0.02, "bwd/gated_rms_norm_grad": 0.04,
                "fwd/split": 0.001, "bwd/concat": 0.001,
                "fwd/mul": 2.0, "fwd/rms_norm": 1.0}
    monkeypatch.setattr(program_trace, "load_run",
                        lambda: {"by_scope": by_scope})
    run = SimpleNamespace(trace={}, traced_steps=10, notes=[],
                          peaks=harness.load_peaks("TPU v5 lite"))
    new = ("ssm_device_ms", "ssm_roofline_pct")
    per_layer = [m for m in manifest["per_layer"] if m["name"] in new]
    assert [m["workloads"] for m in per_layer] == [[CELL]] * 2
    metrics = harness.read_layer_metrics({"per_layer": per_layer}, CELL,
                                         run, log=lambda *_: None)
    assert metrics["ssm_device_ms"]["value"] == pytest.approx(40.0)
    cell = harness.load_cell(manifest, CELL)
    work = cell.model.op_work(cell.cfg, cell.traffic)
    assert metrics["ssm_roofline_pct"]["value"] == pytest.approx(
        100 * work["ssm"]["bytes"] / 8.19e11 / 0.040)
    assert 0 < metrics["ssm_roofline_pct"]["value"] < 100
    assert len(run.notes) == 1 and "bytes bound" in run.notes[0]
    # the seven accepted metrics that list the cell read it too, each from
    # scopes this configuration's program has, and nothing wired to another
    # configuration's op_work does
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(new) | {
        "exec_host_ms", "exec_enqueue_ms", "fwd_device_ms", "bwd_device_ms",
        "opt_device_ms", "experts_device_ms", "attention_device_ms"}
    accepted = [m for m in manifest["per_layer"]
                if m["name"] in ("experts_device_ms", "attention_device_ms")]
    metrics = harness.read_layer_metrics({"per_layer": accepted}, CELL, run,
                                         log=lambda *_: None)
    assert metrics["experts_device_ms"]["value"] == pytest.approx(12.0)
    assert metrics["attention_device_ms"]["value"] == pytest.approx(7.0)
    # a trace without the scopes (the parent's program): nothing to read
    monkeypatch.setattr(program_trace, "load_run",
                        lambda: {"by_scope": {"fwd/mul": 1.0}})
    assert harness.read_layer_metrics({"per_layer": per_layer}, CELL, run,
                                      log=lambda *_: None) == {}


def test_flops_and_bytes_equal_a_hand_count(manifest):
    cell = harness.load_cell(manifest, CELL)
    cfg, model = cell.cfg, cell.model
    t = 4096
    assert (cell.traffic["length"], cell.traffic["batch"]) == (t, 1)
    work = model.op_work(cfg, cell.traffic)
    # one attention layer, 32 query heads of 128 over T (T + 1) / 2 pairs:
    # scores and values forward, their four gradient products backward
    pairs = t * (t + 1) // 2
    assert work["attention"]["flops"] == 3 * 4 * 128 * 32 * pairs
    # bf16 elements a token: q, k, v (4096 + 2 x 256) twice and once more
    # as gradients, out three times
    assert work["attention"]["bytes"] == 2 * t * (3 * 4608 + 3 * 4096)
    # three Mamba-2 layers, 64 heads, chunks of 128: C B^T over the lower
    # triangle once a group of 8 heads (64 x 128 / 8), its product with the
    # inputs (64 x 64), the state written and read (2 x 128 x 64)
    # multiply-accumulates a token and head forward
    assert work["ssm"]["flops"] == 3 * 3 * 2 * t * 64 * (1024 + 4096 + 16384)
    # bf16 elements a token: the core 6208 in + 4096 out forward, those, d y
    # and 6208 of gradients backward; the convolution 5 x 6144; the gated
    # norm 8 x 4096; 32 chunks' float32 states out and in
    assert work["ssm"]["bytes"] == 3 * (
        2 * t * (10304 + 10304 + 6208 + 30720 + 32768)
        + 2 * 4 * 32 * 64 * 64 * 128)
    # held rows at their expectation: 4096 x 6 x 8 / 128 = 1536 a layer,
    # TWO products an expert
    assert work["experts"]["flops"] == 3 * 6 * (
        1536 * 2 * 2688 * 1856 + t * 2688 * 128)
    half = model.op_work(dict(cfg, n_routed_experts=4), cell.traffic)
    assert half["experts"]["flops"] < 0.6 * work["experts"]["flops"]
    feed = {"tokens": np.zeros((1, t, 1), np.int32)}
    mamba = 2688 * 10304 + 4096 * 2688
    attention = 2688 * (4096 + 2 * 256) + 4096 * 2688
    assert (mamba, attention) == (38_707_200, 23_396_352)
    products = (3 * mamba + attention + 3 * 2 * 2688 * 3712
                + 2688 * 16384)
    assert model.train_flops(cfg, feed) == (
        6 * t * products + work["experts"]["flops"]
        + work["attention"]["flops"] + work["ssm"]["flops"])
    # the issue's 6.3 TFLOP of products and 0.5 of attention and the core
    assert 6.7e12 < model.train_flops(cfg, feed) < 6.9e12


def test_the_configuration_keeps_every_published_width(manifest):
    """Every number of the catalog's ``config`` is in the file under its
    key, but the three keys ``reduced`` names, whose published values stand
    beside them; the pattern is whole."""
    cfg = harness.load_cell(manifest, CELL).cfg
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert [cfg["hybrid_override_pattern"].count(c) for c in "ME*"] == [
        23, 23, 6]
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "n_routed_experts": 128,
                                "vocab_size": 131072}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 8, 16384)
    assert cfg["num_experts_routed"] == 128
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert cfg["vocab_size"] * 8 == 131072
    ref = harness.load_cell(manifest, CELL).model._reference()
    assert ref.layer_kinds(cfg) == [
        "mamba", "experts", "mamba", "experts", "mamba", "attention",
        "experts"]
    here = cfg["deployment"]["layers_here"]
    assert here["letters"] == cfg["hybrid_override_pattern"][:7] == "MEMEM*E"
    assert (here["mamba"], here["experts"], here["attention"]) == (
        [0, 2, 4], [1, 3, 6], [5])


def test_the_benchmarks_reference_is_the_repos(manifest):
    here = os.path.join(ROOT, "benchmark", "configs", CONFIG, "reference.py")
    there = os.path.join(ROOT, "paddle_tpu", "testing", "reference",
                         "nemotron_h.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()
