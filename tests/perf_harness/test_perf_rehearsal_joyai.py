"""The JoyAI-LLM-Flash cell rehearsed on the CPU at a tiny size (hidden 64;
MLA 4 heads of 16 + 8 / 16, query rank 24, latent 16, the rope slices
turned over adjacent pairs; 8 of 32 experts top 4, 1 shared; one dense +
two sparse blocks and the multi-token-prediction module; 40 tokens a step):
it runs through the harness's own functions and is ``correct``, the plain
reference agrees with the system and broken pieces of the mathematics fail
check (a), the three new per-layer metrics read a trace (the new reader a
canned one, and nothing from a trace without the module), and the FLOPs and
bytes equal a hand count. Times from these runs mean nothing, and nothing
here counts on how many steps a loaded host fits into the window."""

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

CELL = "joyai_llm_flash_ep32.staged_len4096_b1"
CONFIG = "joyai_llm_flash_ep32"
TINY_CFG = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts_routed": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 4, "vocab_size": 96,
    "probe_projections": 4, "init_std": 0.3, "expert_init_std": 0.3,
    "head_init_std": 0.3, "latent_down_init_std": 0.3,
    "latent_up_init_std": 0.3, "embedding_init_std": 0.3,
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
# one sequence in the ring (see the Kimi-Linear rehearsal)
TINY_TRAFFIC = {"length": 40, "ring": 1}
NEW = ("mla_rope_device_ms", "mla_rope_roofline_pct", "mtp_device_ms")


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


def test_the_probe_is_both_logits_and_broken_pieces_fail_check_a(manifest):
    """The probe is the main logits' sign projections followed by the MTP
    module's, one vector; the system passes check (a); the references with
    the rotation over halves for adjacent pairs, the MTP's concatenation
    swapped and the MTP's input one token early are further from the
    system's probe than the chip's tolerances allow, and so is an
    all-bfloat16 run's here; lambda = 0 leaves the probe and moves the
    loss past the loss's tolerance."""
    cell, weights, feed, loss, probe = _check_step(manifest)
    n = cell.cfg["probe_projections"]
    assert probe.shape == (2 * n, cell.cfg["vocab_size"])
    assert harness.check_reference(cell, weights, feed, loss, probe)["ok"]
    chip = harness.load_cell(manifest, CELL).cfg["reference"]
    limit = max(chip["probe_rel_tolerance"].values())
    assert limit <= 1e-2
    ref = cell.model._reference()
    tok, lab, two = (np.asarray(feed[k])[0, :, 0]
                     for k in ("tokens", "labels", "mtp_labels"))
    assert (lab[:-1] == tok[1:]).all() and (two[:-1] == lab[1:]).all()

    def distance(**how):
        run = ref.run(cell.cfg, weights, tok, lab, two, **how)
        want = np.concatenate([cell.model.sign_projections(tok, lg, n)
                               for lg in run[1:3]])
        return np.abs(want - probe).max() / np.abs(want).max(), run[0]

    assert distance()[0] < 1e-4
    for mutation in ("rotate_half_pairs", "mtp_concat_swapped",
                     "mtp_input_current_token"):
        assert distance(mutate=mutation)[0] > limit, mutation
    assert distance(precision="bfloat16")[0] > limit
    same, off = distance(mutate="mtp_weight_zero")
    assert same < 1e-4
    assert abs(float(off) - loss) / loss > 100 * chip["rel_tolerance"]


def test_the_three_new_metrics_read_a_trace(manifest, monkeypatch):
    """On the CPU no trace has a device plane, so the readers are handed
    one: the latent blocks' time is their scopes' sum per step (the core,
    the assembly, the rotation, the split and its concat), the roofline
    share THIS configuration's op_work over it, under 100%, and the
    module's time its tagged operations' self time."""
    by_scope = {"fwd/routed_experts": 0.02, "bwd/routed_experts_grad": 0.04,
                "fwd/causal_self_attention": 0.1,
                "bwd/causal_self_attention_grad": 0.3,
                "fwd/latent_kv_heads": 0.01,
                "bwd/latent_kv_heads_grad": 0.01,
                "fwd/rotary_embedding": 0.02,
                "bwd/rotary_embedding_grad": 0.02, "fwd/split": 0.01,
                "bwd/concat": 0.01, "fwd/mul": 2.0, "fwd/rms_norm": 1.0}
    events = [("fusion", 0.0, 0.2, "jit(step_ps1)/fwd/mul/mtp/dot_general"),
              ("fusion", 0.2, 0.3, "jit(step_ps1)/bwd/mul_grad/mtp/x"),
              ("fusion", 0.3, 0.9, "jit(step_ps1)/fwd/mul/dot_general")]
    monkeypatch.setattr(program_trace, "load_run", lambda: {
        "by_scope": by_scope, "devices": {0: events}, "window": (0.0, 1.0)})
    run = SimpleNamespace(trace={}, traced_steps=10, notes=[],
                          peaks=harness.load_peaks("TPU v5 lite"))
    per_layer = [m for m in manifest["per_layer"] if m["name"] in NEW]
    assert [m["workloads"] for m in per_layer] == [[CELL]] * 3
    metrics = harness.read_layer_metrics({"per_layer": per_layer}, CELL,
                                         run, log=lambda *_: None)
    assert metrics["mla_rope_device_ms"]["value"] == pytest.approx(48.0)
    assert metrics["mtp_device_ms"]["value"] == pytest.approx(30.0)
    cell = harness.load_cell(manifest, CELL)
    work = cell.model.op_work(cell.cfg, cell.traffic)["mla_rope"]
    assert metrics["mla_rope_roofline_pct"]["value"] == pytest.approx(
        100 * work["flops"] / 1.97e14 / 0.048)
    assert 0 < metrics["mla_rope_roofline_pct"]["value"] < 100
    assert len(run.notes) == 1 and "FLOPs bound" in run.notes[0]
    # the six accepted metrics that list the cell read it too, and nothing
    # wired to another configuration's op_work does
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | {
        "exec_host_ms", "exec_enqueue_ms", "fwd_device_ms", "bwd_device_ms",
        "opt_device_ms", "experts_device_ms"}
    # the parent's program: no module segment, no rotary scope
    monkeypatch.setattr(program_trace, "load_run", lambda: {
        "by_scope": {"fwd/mul": 1.0}, "devices": {0: events[2:]},
        "window": (0.0, 1.0)})
    assert harness.read_layer_metrics({"per_layer": per_layer}, CELL, run,
                                      log=lambda *_: None) == {}
    monkeypatch.setattr(program_trace, "load_run", lambda: None)
    assert harness.read_layer_metrics({"per_layer": per_layer}, CELL, run,
                                      log=lambda *_: None) == {}


def test_flops_and_bytes_equal_a_hand_count(manifest):
    cell = harness.load_cell(manifest, CELL)
    cfg, model = cell.cfg, cell.model
    t = 4096
    assert (cell.traffic["length"], cell.traffic["batch"]) == (t, 1)
    work = model.op_work(cfg, cell.traffic)
    # six latent blocks (five main, the module's), 32 heads, scores at
    # 128 + 64 and values at 128 a head over T (T + 1) / 2 pairs, forward
    # and twice that backward; the rotation 3 FLOP a coordinate each way of
    # 33 slices of 64
    pairs = t * (t + 1) // 2
    turned = 33 * 64
    assert work["mla_rope"]["flops"] == 6 * (
        3 * 2 * pairs * 32 * (192 + 128) + 2 * 3 * t * turned)
    # bf16 elements a token: the assembly 2 x (8192 + 64 + 6144 + 4096),
    # the core 2 x (2 x 6144 + 4096) + 3 x 4096 + 2 x 6144 + 4096, the
    # rotated slices 4 x 2112
    assert work["mla_rope"]["bytes"] == 6 * 2 * t * (
        2 * 18496 + 2 * 16384 + 3 * 4096 + 2 * 6144 + 4096 + 4 * turned)
    # held rows at their expectation: 4096 x 8 x 8 / 256 = 1024 a layer,
    # five routed layers (four main, the module's)
    assert work["experts"]["flops"] == 5 * 6 * (
        1024 * 3 * 2048 * 768 + t * 2048 * 256)
    mla = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert mla == 26_345_472
    products = (6 * mla + 3 * 2048 * 7168 + 5 * 3 * 2048 * 768
                + 4096 * 2048 + 2 * 2048 * 16160)
    feed = {"tokens": np.zeros((1, t, 1), np.int32)}
    assert model.train_flops(cfg, feed) == (
        6 * t * products + work["experts"]["flops"]
        + work["mla_rope"]["flops"])
    # the issue's 10.7 TFLOP a step
    assert 10.5e12 < model.train_flops(cfg, feed) < 10.8e12
    mtp = work["mtp"]
    assert 0 < mtp["flops"] < model.train_flops(cfg, feed) / 3
    off = model.op_work(dict(cfg, num_nextn_predict_layers=0), cell.traffic)
    assert off["mtp"] == {"flops": 0, "bytes": 0}
    assert off["mla_rope"]["flops"] * 6 == work["mla_rope"]["flops"] * 5


def test_the_configuration_keeps_every_published_width(manifest):
    """Every number of the catalog's ``config`` is in the file under its
    key, but the three keys ``reduced`` names, whose published values stand
    beside them."""
    cfg = harness.load_cell(manifest, CELL).cfg
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256,
                                "vocab_size": 129280}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 16160)
    assert cfg["num_experts_routed"] == 256 and cfg["expert_offset"] == 0
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    assert cfg["vocab_size"] * 8 == 129280
    ref = harness.load_cell(manifest, CELL).model._reference()
    assert ref.layer_kinds(cfg) == ["dense"] + ["sparse"] * 4


def test_the_benchmarks_reference_is_the_repos(manifest):
    here = os.path.join(ROOT, "benchmark", "configs", CONFIG, "reference.py")
    there = os.path.join(ROOT, "paddle_tpu", "testing", "reference",
                         "joyai_llm_flash.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()
