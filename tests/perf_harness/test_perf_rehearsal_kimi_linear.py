"""The Kimi-Linear cell rehearsed on the CPU at a tiny size (hidden 64; KDA 4
heads of 16, conv 4, chunks of 16; MLA 4 heads of 24 + 8 / 16, latent 16; 8
of 32 experts top 4, 1 shared; one dense + three sparse blocks in the order
KDA, KDA, MLA, KDA; 72 tokens a step, four chunks and a half): it runs
through the harness's own functions and is ``correct``, the plain reference
agrees with the system and three broken pieces of the mathematics fail check
(a), the four new per-layer metrics read a trace's scopes, and the FLOPs and
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

CELL = "kimi_linear_48b_ep32.staged_len4096_b1"
CONFIG = "kimi_linear_48b_ep32"
TINY_CFG = {
    "hidden_size": 64, "num_hidden_layers": 4,
    "linear_attn_config": {"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                           "head_dim": 16, "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "kda_chunk_size": 16, "kda_gate_rank": 8, "num_attention_heads": 4,
    "kv_lora_rank": 16, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts_routed": 32, "num_experts": 8, "num_experts_per_token": 4,
    "vocab_size": 96, "probe_projections": 4, "init_std": 0.3,
    "kda_init_std": 0.3, "expert_init_std": 0.3, "head_init_std": 0.3,
    "latent_down_init_std": 0.3, "latent_up_init_std": 0.3,
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
    decay applied after the delta update, with the shared key one per head
    and with softmax scores (what systems with those mistakes would be
    compared with) are further from the system's than the tolerances the
    file gives for the chip allow, and so is an all-bfloat16 run's here."""
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
    for mutation in ("decay_after_update", "shared_key_per_head",
                     "softmax_scores"):
        assert distance(mutate=mutation) > limit, mutation
    assert distance(precision="bfloat16") > limit


def test_the_four_new_metrics_read_a_traces_scopes(manifest, monkeypatch):
    """On the CPU no trace has a device plane, so the readers are handed
    one that says how long each scope took: a device time is its scopes'
    sum per step (every op of the block's attention that is no mul and no
    plain rms_norm), and the roofline shares are THIS configuration's
    op_work over them, under 100%."""
    by_scope = {"fwd/routed_experts": 0.02, "bwd/routed_experts_grad": 0.04,
                "fwd/causal_self_attention": 0.02,
                "bwd/causal_self_attention_grad": 0.05,
                "fwd/latent_kv_heads": 0.004,
                "bwd/latent_kv_heads_grad": 0.004, "fwd/split": 0.001,
                "bwd/concat": 0.001,
                "fwd/gated_delta_rule": 0.08,
                "bwd/gated_delta_rule_grad": 0.20,
                "fwd/causal_conv1d": 0.02, "bwd/causal_conv1d_grad": 0.04,
                "fwd/kda_decay_gate": 0.01, "bwd/kda_decay_gate_grad": 0.01,
                "fwd/gated_rms_norm": 0.01, "bwd/gated_rms_norm_grad": 0.02,
                "fwd/sigmoid": 0.005, "bwd/sigmoid_grad": 0.005,
                "fwd/mul": 2.0, "fwd/rms_norm": 1.0}
    monkeypatch.setattr(program_trace, "load_run",
                        lambda: {"by_scope": by_scope})
    run = SimpleNamespace(trace={}, traced_steps=10, notes=[],
                          peaks=harness.load_peaks("TPU v5 lite"))
    new = ("kda_device_ms", "kda_roofline_pct", "latent_attention_device_ms",
           "latent_attention_roofline_pct")
    per_layer = [m for m in manifest["per_layer"] if m["name"] in new]
    assert [m["workloads"] for m in per_layer] == [[CELL]] * 4
    metrics = harness.read_layer_metrics({"per_layer": per_layer}, CELL,
                                         run, log=lambda *_: None)
    assert metrics["kda_device_ms"]["value"] == pytest.approx(40.0)
    assert metrics["latent_attention_device_ms"]["value"] == pytest.approx(
        8.0)
    cell = harness.load_cell(manifest, CELL)
    work = cell.model.op_work(cell.cfg, cell.traffic)
    assert metrics["kda_roofline_pct"]["value"] == pytest.approx(
        100 * work["kda"]["bytes"] / 8.19e11 / 0.040)
    assert metrics["latent_attention_roofline_pct"]["value"] \
        == pytest.approx(
            100 * work["latent_attention"]["flops"] / 1.97e14 / 0.008)
    assert all(0 < metrics[n]["value"] < 100 for n in new)
    assert len(run.notes) == 2 and "bytes bound" in run.notes[0] \
        and "FLOPs bound" in run.notes[1]
    # the six accepted metrics that list the cell read it too, and nothing
    # wired to another configuration's op_work does
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(new) | {
        "exec_host_ms", "exec_enqueue_ms", "fwd_device_ms", "bwd_device_ms",
        "opt_device_ms", "experts_device_ms"}
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
    # one latent block, 32 heads, scores at 128 + 64 and values at 128 a
    # head, over T (T + 1) / 2 pairs, forward and twice that backward
    pairs = t * (t + 1) // 2
    assert work["latent_attention"]["flops"] == 3 * 2 * pairs * 32 * (
        192 + 128)
    # bf16 elements a token: the assembly 2 x (8192 + 64 + 6144 + 4096), the
    # core 2 x (2 x 6144 + 4096) + 3 x 4096 + 2 x 6144 + 4096
    assert work["latent_attention"]["bytes"] == 2 * t * (
        2 * 18496 + 2 * 16384 + 3 * 4096 + 2 * 6144 + 4096)
    # four KDA blocks, 32 heads of 128, chunks of 64: 160 + 384
    # multiply-accumulates a token, head and channel forward
    assert work["kda"]["flops"] == 4 * 3 * 2 * t * 32 * 128 * (160 + 384)
    # bytes a token, in units of the 4096 channels: the core 12 forward (q,
    # k, v, o in bfloat16, g in float32), 12 again and 10 of gradients
    # backward, the convolutions 30, the gate 14, the gated norm 16; beta
    # three times; 64 chunks' float32 states out and in
    assert work["kda"]["bytes"] == 4 * (
        t * (4096 * (12 + 12 + 10 + 30 + 14 + 16) + 6 * 32)
        + 2 * 4 * 64 * 32 * 128 * 128)
    # held rows at their expectation: 4096 x 8 x 8 / 256 = 1024 a layer
    assert work["experts"]["flops"] == 4 * 6 * (
        1024 * 3 * 2304 * 1024 + t * 2304 * 256)
    half = model.op_work(dict(cfg, num_experts=4), cell.traffic)
    # (the router, a quarter of the count, stays whole)
    assert half["experts"]["flops"] < 0.65 * work["experts"]["flops"]
    feed = {"tokens": np.zeros((1, t, 1), np.int32)}
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert (kda, mla) == (39_460_864, 29_114_368)
    products = (4 * kda + mla + 3 * 2304 * 9216 + 4 * 3 * 2304 * 1024
                + 2304 * 20480)
    assert model.train_flops(cfg, feed) == (
        6 * t * products + work["experts"]["flops"]
        + work["latent_attention"]["flops"] + work["kda"]["flops"])
    # the issue's 9.0 TFLOP a step
    assert 8.9e12 < model.train_flops(cfg, feed) < 9.1e12


def test_the_configuration_keeps_every_published_width(manifest):
    """Every number of the catalog's ``config`` is in the file under its
    key, but the three keys ``reduced`` names, whose published values stand
    beside them; the nested group is whole."""
    cfg = harness.load_cell(manifest, CELL).cfg
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    lin = cfg["linear_attn_config"]
    assert (lin["head_dim"], lin["num_heads"],
            lin["short_conv_kernel_size"]) == (128, 32, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(
        range(1, 28))
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 20480)
    assert cfg["num_experts_routed"] == 256
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    assert cfg["vocab_size"] * 8 == 163840
    ref = cfg and harness.load_cell(manifest, CELL).model._reference()
    assert ref.layer_kinds(cfg) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("mla", "sparse"), ("kda", "sparse")]
    assert cfg["deployment"]["layers_here"] == {
        "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "dense": [1],
        "sparse": [2, 3, 4, 5]}


def test_the_benchmarks_reference_is_the_repos(manifest):
    here = os.path.join(ROOT, "benchmark", "configs", CONFIG, "reference.py")
    there = os.path.join(ROOT, "paddle_tpu", "testing", "reference",
                         "kimi_linear.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()
