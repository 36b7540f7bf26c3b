"""The five per-layer metrics of ``setup_s`` that read the compile telemetry's
stage counters (``trace_s``, ``lower_s``, ``xla_compile_s``, ``cache_load_s``,
``eager_build_s``), rehearsed on the CPU at tiny sizes, and their reader
``counter_labels`` on its own. The seconds of these runs mean nothing; that
each metric is there, finite and fed by the right children is what is held."""

import json
import math
import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import harness  # noqa: E402

sys.path.remove(ROOT)

SETUP_STAGES = ("trace_s", "lower_s", "xla_compile_s", "cache_load_s",
                "eager_build_s")
TINY = {
    "lstm_textcls_h512.staged_len": ({"hidden": 32, "vocab": 64},
                                     {"batch": 8, "length": 12}),
    "resnet50_imagenet.staged_b256": (
        {"image_size": 32, "depths": [1, 1, 1, 1],
         "optimizer": {"kind": "Momentum", "learning_rate": 0.001,
                       "momentum": 0.9}}, {"batch": 8, "ring": 1}),
}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(os.path.join(
        harness.BENCH_DIR, "readers", "counter_labels.py"), "r_counter_labels")


@pytest.fixture
def _no_persistent_cache(monkeypatch):
    from paddle_tpu.core import compile_cache
    monkeypatch.setattr(compile_cache, "enable",
                        lambda: (None, compile_cache.CacheStats()))
    monkeypatch.setattr(harness, "TRACE_AFTER_S", 0.05)
    monkeypatch.setattr(harness, "TRACE_MIN_S", 0.05)
    monkeypatch.setattr(harness, "TRACE_MIN_STEPS", 3)


def test_the_manifest_gives_every_cell_the_five_metrics(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    layers = {"trace_s": "executor", "lower_s": "executor",
              "xla_compile_s": "compile cache",
              "cache_load_s": "compile cache",
              "eager_build_s": "op lowerings"}
    for name in SETUP_STAGES:
        m = by_name[name]
        # no `workloads` list: every cell runs the executor, those that
        # later PRs add too (and the accepted rehearsal tests of cells 6 and
        # 7 hold the metrics that LIST their cell to a fixed set)
        assert "workloads" not in m
        assert all(m in harness.metrics_of(manifest, c, "per_layer")
                   for c in cells)
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "s", "lower", "program_counter", "setup_s")
        assert m["layer"] == layers[name]
        spec = harness.load_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", name + ".json"))
        assert spec["reader"] == "counter_labels"
        assert spec["params"]["name"] == "paddle_tpu_compile_stage_seconds"
        # the four stages leave the eager site out; eager_build_s is it
        eager = name == "eager_build_s"
        assert (spec["params"].get("match", {}).get("site") == "eager") \
            == eager
        assert (spec["params"].get("exclude", {}).get("site") == "eager") \
            == (not eager)
    # what was there stays: this PR only adds
    assert {"build_s", "cache_misses"} <= set(by_name)


def test_counter_labels_is_none_for_a_family_the_program_lacks(reader):
    run = SimpleNamespace(notes=[])
    assert reader.read({"name": "paddle_tpu_no_such_family",
                        "match": {"stage": "trace"}}, run) is None
    assert run.notes == []


def test_counter_labels_sums_the_children_that_pass_the_filter(reader):
    from paddle_tpu.obs.metrics import REGISTRY
    fam = REGISTRY.counter("paddle_tpu_test_counter_labels",
                           "scratch family of the benchmark's reader test",
                           labels=("site", "stage"))
    fam.reset()
    for site, stage, x in (("jit_step", "trace", 1.5), ("jit_scan", "trace",
                                                        0.25),
                           ("jit_step", "lower", 4.0),
                           ("eager", "trace", 8.0), ("eager", "lower", 16.0)):
        fam.labels(site=site, stage=stage).inc(x)
    run = SimpleNamespace(notes=[])
    name = "paddle_tpu_test_counter_labels"
    assert reader.read({"name": name, "match": {"stage": "trace"},
                        "exclude": {"site": "eager"}}, run) == 1.75
    assert reader.read({"name": name, "match": {"site": "eager"}}, run) == 24
    assert reader.read({"name": name, "match": {"stage": "other"},
                        "exclude": {"site": "eager"}}, run) == 0
    assert reader.read({"name": name, "match": {"site": "jit_scan"}},
                       run) == 0.25
    assert len(run.notes) == 4             # the children each read summed
    reader.read({"name": name, "match": {"stage": "lower"}}, run)
    assert "eager,lower 16.000" in run.notes[-1] \
        and "jit_step,lower 4.000" in run.notes[-1]


@pytest.mark.parametrize("prefix", sorted(TINY))
def test_traced_rehearsal_reports_the_five_metrics(manifest, prefix, tmp_path,
                                                   _no_persistent_cache):
    from paddle_tpu.obs.metrics import REGISTRY
    (name,) = [w["name"] for w in manifest["workloads"]
               if w["name"].startswith(prefix)]
    cfg, traffic = TINY[prefix]
    stage_seconds = REGISTRY.get("paddle_tpu_compile_stage_seconds")
    before = sum(c.value for k, c in stage_seconds.children().items()
                 if k[0] != "eager")
    lines = []
    result = harness.run_cell(
        manifest, name, 2 ** 31 + 38, 1.0, True, time.perf_counter(),
        cfg_override=cfg, traffic_override=traffic, log=lines.append,
        trace_dir=str(tmp_path))
    (checks,) = [json.loads(l[len("checks: "):]) for l in lines
                 if l.startswith("checks: ")]
    assert result["correct"] is True, checks
    values = {k: result["metrics"][k]["value"] for k in SETUP_STAGES}
    assert all(math.isfinite(v) and v >= 0 for v in values.values()), values
    assert all(result["metrics"][k]["unit"] == "s" for k in SETUP_STAGES)
    # this run built its executables here, without a persistent cache:
    # traced, lowered and compiled, nothing loaded
    assert values["trace_s"] > 0 and values["lower_s"] > 0
    assert values["xla_compile_s"] > 0
    # staging the feeds and the plain reference build op by op
    assert values["eager_build_s"] > 0
    # the four, with the sites' `other`, are what the executor's builds took
    after = sum(c.value for k, c in stage_seconds.children().items()
                if k[0] != "eager")
    assert after - before > 0
    assert sum(values[k] for k in SETUP_STAGES[:4]) <= after + 1e-9
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    # the reader's notes: the children each metric summed
    assert sum("paddle_tpu_compile_stage_seconds" in l and "by child" in l
               for l in lines) == 5
