"""The benchmark rehearsed on the CPU at tiny sizes (depths (1,1,1,1) at
32 px and batch 8; hidden 32; a corpus of 256): every generator kind drives
its configuration through the harness's own functions, the plain references
agree with the system, the result has exactly the contract's keys, and each
part of ``correct`` fails when it is broken on purpose. Times from these
runs mean nothing and are not looked at."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import harness  # noqa: E402

sys.path.remove(ROOT)

# lr 0.001 and (below) a ring of one batch: at this size 0.01 memorises a
# batch of 8 in one step and then bounces, and a window of three or four CPU
# steps must still show a fall
TINY_RESNET = {"image_size": 32, "depths": [1, 1, 1, 1],
               "optimizer": {"kind": "Momentum", "learning_rate": 0.001,
                             "momentum": 0.9}}
TINY_LSTM = {"hidden": 32, "vocab": 64}
TINY_CORPUS = {"batch": 8, "n_sequences": 256, "length_median": 30,
               "length_min": 4, "length_max": 100,
               "bucket_bounds": [16, 32, 64, 100]}
TINY = {
    "resnet50_imagenet.staged_b256": (TINY_RESNET, {"batch": 8, "ring": 1}),
    "lstm_textcls_h512.staged_len": (TINY_LSTM, {"batch": 8, "length": 12}),
    "lstm_textcls_h512.ragged_reader": (TINY_LSTM, TINY_CORPUS),
    "resnet50_imagenet.staged_dp4_b1024": (TINY_RESNET, {"batch": 16,
                                                         "ring": 1}),
}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def _cell_name(manifest, prefix):
    (name,) = [w["name"] for w in manifest["workloads"]
               if w["name"].startswith(prefix)]
    return name


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """The rehearsal must not switch the process's persistent compile cache
    on under the other tests of this worker."""
    from paddle_tpu.core import compile_cache
    monkeypatch.setattr(compile_cache, "enable",
                        lambda: (None, compile_cache.CacheStats()))
    monkeypatch.setattr(harness, "TRACE_AFTER_S", 0.05)
    monkeypatch.setattr(harness, "TRACE_MIN_S", 0.05)
    monkeypatch.setattr(harness, "TRACE_MIN_STEPS", 3)


def _run(manifest, prefix, trace=False, seconds=1.0, cfg=None, traffic=None,
         tmp_path=None, seed=2 ** 31 + 77):
    name = _cell_name(manifest, prefix)
    co, to = TINY[prefix]
    lines = []
    result = harness.run_cell(
        manifest, name, seed, seconds, trace, time.perf_counter(),
        cfg_override={**co, **(cfg or {})},
        traffic_override={**to, **(traffic or {})}, log=lines.append,
        trace_dir=str(tmp_path) if tmp_path else None)
    (checks,) = [json.loads(l[len("checks: "):]) for l in lines
                 if l.startswith("checks: ")]
    return name, result, checks, lines


@pytest.mark.parametrize("prefix", sorted(TINY))
def test_each_cell_runs_tiny_and_is_correct(manifest, prefix):
    name, result, checks, _ = _run(manifest, prefix)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, checks
    assert result["attempted"] >= 2 and result["failed"] == 0
    wanted = {m["name"]: m["unit"]
              for m in harness.metrics_of(manifest, name, "end_to_end")}
    assert {k: v["unit"] for k, v in result["metrics"].items()} == wanted
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # (a): the plain reference reproduces the system's first loss, and the
    # LSTM's per-sequence logits
    assert checks["reference"]["ok"]
    assert checks["reference"]["rel_err"] < \
        checks["reference"]["rel_tolerance"]
    assert ("logits" in checks["reference"]) == prefix.startswith("lstm")
    if prefix.startswith("lstm"):           # float32 on the CPU: roundings
        assert checks["reference"]["logits"]["rel_err"] < 1e-5
        assert checks["reference"]["logits_as_stated"]["rel_err"] < 1e-5
    assert checks["losses"]["tenths"][-1] < checks["losses"]["tenths"][0]
    if "dp4" in prefix:                      # (e): spread over four devices
        assert checks["placement"]["ok"]
        assert all(v["devices"] == 4 for k, v in checks["placement"].items()
                   if k != "ok")
    json.loads(json.dumps(result))           # one line of JSON


def test_traced_run_reports_the_per_layer_metrics_it_can_read(manifest,
                                                              tmp_path):
    name, result, checks, lines = _run(
        manifest, "lstm_textcls_h512.staged_len", trace=True,
        tmp_path=tmp_path)
    assert result["correct"] is True, checks
    names = {m["name"] for m in harness.metrics_of(manifest, name,
                                                   "per_layer")}
    assert set(result["metrics"]) <= names
    # host spans and program counters read on any backend; the CPU's trace
    # has no TPU plane, so the device-trace readers find nothing and their
    # metrics are left out of the line
    assert {"build_s", "cache_misses", "dispatch_ms", "compiles_in_window",
            "feed_wait_ms"} <= set(result["metrics"])
    assert "device_busy_ms" not in result["metrics"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert any("nothing to read" in l for l in lines)


def test_a_non_finite_loss_fails_the_run(manifest):
    _, result, checks, _ = _run(
        manifest, "resnet50_imagenet.staged_b256",
        cfg={"optimizer": {"kind": "Momentum", "learning_rate": 1e30,
                           "momentum": 0.9}})
    assert result["correct"] is False
    assert result["failed"] > 0 and not checks["losses"]["finite"]
    assert checks["reference"]["ok"]          # the first loss was still fine


def test_a_compile_inside_the_window_fails_the_run(manifest, monkeypatch):
    """A ragged cell whose shapes were not all warmed compiles in the
    window; (c) must see it."""
    real = harness.load_cell

    def unwarmed(*a, **kw):
        cell = real(*a, **kw)
        opener = cell.generator.open_session

        def open_session(ctx):
            session = opener(ctx)
            session.warm_feeds = ()
            return session
        cell.generator.open_session = open_session
        return cell

    monkeypatch.setattr(harness, "load_cell", unwarmed)
    _, result, checks, _ = _run(manifest, "lstm_textcls_h512.ragged_reader",
                                seconds=1.0)
    assert result["correct"] is False
    assert not checks["no_compile_in_window"]["ok"]
    assert checks["no_compile_in_window"]["delta"]["compile_log_count"] > 0


def _ragged_check_step(manifest):
    """The tiny ragged cell's program, its start-up weights, one really
    ragged feed padded to 100, and a function that takes (loss, logits) of
    a feed from the start-up weights every time."""
    import jax

    from benchmark.session import executor_check_step

    name = _cell_name(manifest, "lstm_textcls_h512.ragged_reader")
    co, to = TINY["lstm_textcls_h512.ragged_reader"]
    cell = harness.load_cell(manifest, name, co, to)
    ctx = harness.make_context(cell, seed=5)
    prog = harness.start_program(ctx)
    weights = harness.snapshot_weights(prog)
    samples = cell.model.corpus(cell.cfg, np.random.RandomState(5),
                                cell.traffic)[:8]
    assert len({len(s[0]) for s in samples}) > 1          # really ragged
    feed = cell.model.collate(cell.cfg, samples, 100)

    def system(f):
        for n, w in zip(prog.params, weights):
            prog.scope.set(n, jax.numpy.asarray(w))
        loss, logits = jax.device_get(executor_check_step(prog, f))
        return float(np.reshape(loss, ())), logits

    return cell, weights, feed, system


def test_padding_that_leaks_into_the_ragged_logits_fails_the_reference(
        manifest):
    """The reference runs each sequence for exactly its own length. A system
    that let padded steps reach the last state — stood in for here by
    feeding every length as the bucket's bound — must not pass (a)."""
    from paddle_tpu.core.lod import LoDArray

    cell, weights, feed, system = _ragged_check_step(manifest)
    assert harness.check_reference(cell, weights, feed, *system(feed))["ok"]
    leaky = dict(feed, words=LoDArray(
        feed["words"].data, np.full_like(feed["words"].lens, 100)))
    verdict = harness.check_reference(cell, weights, feed, *system(leaky))
    assert not verdict["ok"], verdict
    assert verdict["logits"]["rel_err"] > verdict["logits"]["rel_tolerance"]


def test_lower_precision_or_no_signal_fails_the_lstm_reference(manifest):
    """What the mean loss cannot see at a random initialisation (every loss
    is ln 2 give or take 1e-5) the logits do, at the tolerance the
    configuration's file gives for the chip: a recurrence computed in
    bfloat16 — stood in for by the reference itself run in bfloat16 — and a
    network whose logits are all zero both fail (a), and both would pass on
    the loss alone."""
    import jax.numpy as jnp

    cell, weights, feed, system = _ragged_check_step(manifest)
    loss, logits = system(feed)
    assert harness.check_reference(cell, weights, feed, loss, logits)["ok"]
    low_loss, low = cell.model.run_reference(cell.cfg, weights, feed,
                                             dtype=jnp.bfloat16)
    for what, (l, probe) in {
            "bfloat16": (low_loss, low["logits"]),
            "zeroed": (float(np.log(2.0)), np.zeros_like(logits))}.items():
        verdict = harness.check_reference(cell, weights, feed, l, probe)
        assert not verdict["ok"], (what, verdict)
        gate = verdict["logits_as_stated"]
        assert gate["rel_err"] > 10 * gate["rel_tolerance"], (what, verdict)
        assert abs(l - loss) < 5e-3, what       # the loss hardly moves
    # exact arithmetic alone would let bfloat16 through (on the chip the
    # stated precision itself is 3e-3..5e-3 from it): the second gate is the
    # one that holds the precision
    assert harness.check_reference(cell, weights, feed, low_loss,
                                   low["logits"])["logits"]["rel_err"] < 0.02
    with pytest.raises(harness.CellError):   # a kind that fetches no probe
        harness.check_reference(cell, weights, feed, loss, None)


def test_check_parts_in_isolation():
    falling = [float(x) for x in range(30, 0, -1)]
    assert harness.check_losses(falling, 0)["ok"]
    assert not harness.check_losses(falling[::-1], 0)["ok"]
    # fell and then drifted above where it began: the LAST tenth decides
    assert not harness.check_losses([5.0] * 3 + [0.1] * 20 + [9.0] * 7,
                                    0)["ok"]
    assert not harness.check_losses([1.0], 0)["ok"]
    assert not harness.check_losses(falling, 1)["ok"]
    assert not harness.check_losses([2.0, float("nan")], 1)["ok"]
    assert not harness.check_pallas(
        {"dispatches": {"lstm": {"native": 0, "interpret": 2}}})["ok"]
    assert harness.check_pallas(
        {"dispatches": {"lstm": {"native": 5, "interpret": 0}}})["ok"]
    before = {"compile_log_count": 3, "compile_cache_misses": 2}
    assert harness.check_counters(before, dict(before))["ok"]
    assert not harness.check_counters(
        before, dict(before, compile_cache_misses=3))["ok"]
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile(list(range(101)), 95) == pytest.approx(95)
    with pytest.raises(harness.CellError):
        harness.percentile([], 50)


def test_flops_count_real_tokens_and_real_layers(manifest):
    """Padding earns nothing; the full ResNet-50 is the published 4.1 GMACs
    an image forward."""
    cells = {w["config"]: w["name"] for w in manifest["workloads"]}
    lstm = harness.load_cell(manifest, cells["lstm_textcls_h512"])
    short = [(np.zeros((5, 1), np.int32), 0), (np.zeros((9, 1), np.int32), 1)]
    a = lstm.model.train_flops(lstm.cfg, lstm.model.collate(lstm.cfg, short,
                                                            16))
    b = lstm.model.train_flops(lstm.cfg, lstm.model.collate(lstm.cfg, short,
                                                            100))
    assert a == b > 0
    res = harness.load_cell(manifest, cells["resnet50_imagenet"])
    fwd, stem = res.model.forward_flops_per_sample(res.cfg)
    assert 2 * 3.8e9 < fwd < 2 * 4.2e9
    assert stem == 2 * 112 * 112 * 49 * 3 * 64


def test_run_py_refuses_a_cpu_backend_and_prints_no_result(manifest):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, manifest["command"][1]),
         "--workload", manifest["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert "no TPU" in proc.stderr
