"""tools/bench_compare.py — the bench regression gate, pinned as a
tier-1 subprocess gate: identical records exit 0, a seeded 10%
throughput regression exits nonzero NAMING the lane, and malformed /
missing-lane records fail typed (exit 2) rather than tracebacking.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "bench_compare.py")
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_compare  # noqa: E402


def _lines(records):
    return "\n".join(json.dumps(r) for r in records) + "\n"


RECORDS = [
    {"metric": "resnet50_train_throughput", "value": 2567.5,
     "unit": "images/sec/chip", "vs_baseline": 0.86},
    {"metric": "generation_serving", "value": 99.4,
     "unit": "tokens/sec, 8 concurrent GenClient streams"},
    {"metric": "online_learning", "value": 1900.0,
     "unit": "ms publish-to-served lag p50 (freeze cut -> ...)"},
    {"metric": "lstm_textcls_train_ms_batch", "value": 5.03,
     "unit": "ms/batch (bs64 hid512 len100, lower is better)"},
]


def _run(*argv):
    return subprocess.run([sys.executable, CLI, *argv],
                          capture_output=True, text=True, timeout=60)


# ---------------------------------------------------------------------------
# the subprocess gate
# ---------------------------------------------------------------------------

def test_identical_records_exit_zero(tmp_path):
    p = tmp_path / "run.json"
    p.write_text(_lines(RECORDS))
    r = _run(str(p), str(p))
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_self_compare_of_driver_record(tmp_path):
    """The acceptance pin: exit 0 on self-compare of a record in the
    driver's BENCH_r*.json shape (pretty-printed; lane rows are JSON
    lines inside ``tail`` behind a log-noise line, flagship last and
    repeated under ``parsed``)."""
    driver = {"n": 5,
              "cmd": "if [ -f bench.py ]; then python bench.py; "
                     "else exit 0; fi",
              "rc": 0,
              "tail": "WARNING:jax._src.xla_bridge:905: a log line that is "
                      "not a record\n" + _lines(RECORDS[1:] + RECORDS[:1]),
              "parsed": RECORDS[0]}
    p = tmp_path / "BENCH_r05.json"
    p.write_text(json.dumps(driver, indent=2))
    r = _run(str(p), str(p))
    assert r.returncode == 0, r.stderr
    assert "resnet50_train_throughput" in r.stdout


def test_seeded_throughput_regression_exits_nonzero_naming_lane(tmp_path):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(_lines(RECORDS))
    regressed = [dict(r) for r in RECORDS]
    regressed[0] = dict(regressed[0], value=round(2567.5 * 0.9, 1))
    new.write_text(_lines(regressed))
    r = _run(str(old), str(new))
    assert r.returncode == 1
    assert "resnet50_train_throughput" in r.stderr   # named
    assert "REGRESSION" in r.stdout


def test_lower_is_better_lane_regresses_upward(tmp_path):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(_lines(RECORDS))
    worse = [dict(r) for r in RECORDS]
    worse[2] = dict(worse[2], value=1900.0 * 1.12)   # lag p50 ms UP 12%
    new.write_text(_lines(worse))
    r = _run(str(old), str(new))
    assert r.returncode == 1
    assert "online_learning" in r.stderr
    # ...and the same delta DOWN is an improvement, not a regression
    better = [dict(r) for r in RECORDS]
    better[2] = dict(better[2], value=1900.0 * 0.88)
    new.write_text(_lines(better))
    assert _run(str(old), str(new)).returncode == 0


def test_malformed_records_fail_typed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("this is not a bench record\nnor { this\n")
    ok = tmp_path / "ok.json"
    ok.write_text(_lines(RECORDS))
    r = _run(str(bad), str(ok))
    assert r.returncode == 2
    assert "bench_compare:" in r.stderr
    assert "Traceback" not in r.stderr
    # a lane whose value is not numeric fails typed too
    bad.write_text(_lines([{"metric": "x", "value": "fast",
                            "unit": "QPS"}]))
    r = _run(str(bad), str(ok))
    assert r.returncode == 2
    assert "Traceback" not in r.stderr


def test_missing_lane_fails_typed_unless_ignored(tmp_path):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(_lines(RECORDS))
    new.write_text(_lines(RECORDS[:-1]))             # lstm lane dropped
    r = _run(str(old), str(new))
    assert r.returncode == 2
    assert "lstm_textcls_train_ms_batch" in r.stderr
    assert "Traceback" not in r.stderr
    assert _run(str(old), str(new), "--ignore-missing").returncode == 0


def test_trajectory_dir_mode(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(_lines(RECORDS))
    (tmp_path / "BENCH_r02.json").write_text(_lines(RECORDS))
    r = _run("--dir", str(tmp_path))
    assert r.returncode == 0
    assert "BENCH_r01.json -> " in r.stdout
    # fewer than two records: typed failure
    r = _run("--dir", str(tmp_path / "nothing"))
    assert r.returncode == 2 and "Traceback" not in r.stderr


# ---------------------------------------------------------------------------
# in-process API (what bench.py --compare-to runs)
# ---------------------------------------------------------------------------

def test_compare_records_threshold_and_smoke_suffix():
    old = {"a": {"metric": "a", "value": 100.0, "unit": "QPS"}}
    new = {"a": {"metric": "a", "value": 96.0, "unit": "QPS"}}
    assert bench_compare.compare_records(old, new, 5.0)["ok"]
    new["a"]["value"] = 94.0
    res = bench_compare.compare_records(old, new, 5.0)
    assert not res["ok"] and res["regressions"] == ["a"]
    # _smoke suffixes strip, so smoke runs compare against full runs
    assert bench_compare._lane_name("serving_throughput_smoke") \
        == "serving_throughput"


def test_driver_record_shape_parses(tmp_path):
    driver = {"n": 5, "cmd": "python bench.py", "rc": 0,
              "tail": "WARNING: noise line\n" + _lines(RECORDS),
              "parsed": RECORDS[0]}
    p = tmp_path / "BENCH_r05.json"
    p.write_text(json.dumps(driver))
    recs = bench_compare.load_records(str(p))
    assert set(recs) == {r["metric"] for r in RECORDS}


def test_new_lanes_are_not_failures():
    old = {"a": {"metric": "a", "value": 1.0, "unit": "QPS"}}
    new = {"a": {"metric": "a", "value": 1.0, "unit": "QPS"},
           "b": {"metric": "b", "value": 9.9, "unit": "QPS"}}
    res = bench_compare.compare_records(old, new)
    assert res["ok"] and res["new_lanes"] == ["b"]


def test_warm_start_lane_is_lower_is_better():
    """The warm_start_serving lane's second-denominated time-to-ready
    unit (the exact string bench.py emits) must regress UPWARD in both
    the direction helper and a full compare; seconds-per-unit throughput
    strings keep the higher-is-better default."""
    rec = {"metric": "warm_start_serving", "value": 0.05,
           "unit": "s replica time-to-ready, warm-started from persisted "
                   "executables (lower is better; gate: >= 2x faster "
                   "than cold compile on the same bundle, asserted "
                   "in-lane)"}
    assert bench_compare.lower_is_better(rec)
    assert bench_compare.lower_is_better(
        {"metric": "x", "value": 1.0, "unit": "s time-to-ready"})
    assert not bench_compare.lower_is_better(
        {"metric": "x", "value": 1.0, "unit": "steps/s"})
    old = {"warm_start_serving": rec}
    slower = {"warm_start_serving": dict(rec, value=0.07)}
    res = bench_compare.compare_records(old, slower, 5.0)
    assert res["regressions"] == ["warm_start_serving"]
    faster = {"warm_start_serving": dict(rec, value=0.03)}
    assert bench_compare.compare_records(old, faster, 5.0)["ok"]


def test_reload_storm_lane_is_lower_is_better():
    """The reload_storm_serving lane's TTFT-ratio unit (the exact
    string bench.py emits) pins lower-is-better: a BIGGER reload/steady
    ratio is a regression. Plain "x ..." speedup units keep the
    higher-is-better default."""
    rec = {"metric": "reload_storm_serving", "value": 1.05,
           "unit": "x TTFT p99, reload window vs steady state, 8 "
                   "GenClient streams under a rolling v1->v2->v1 reload "
                   "(lower is better; gate <= 1.5x asserted in-lane)"}
    assert bench_compare.lower_is_better(rec)
    assert not bench_compare.lower_is_better(
        {"metric": "x", "value": 2.0,
         "unit": "x fused conv+bn+relu (fwd+bwd) vs its jnp twin"})
    old = {"reload_storm_serving": rec}
    worse = {"reload_storm_serving": dict(rec, value=1.4)}
    res = bench_compare.compare_records(old, worse, 5.0)
    assert res["regressions"] == ["reload_storm_serving"]
    better = {"reload_storm_serving": dict(rec, value=0.9)}
    assert bench_compare.compare_records(old, better, 5.0)["ok"]

def test_placement_planner_lane_is_higher_is_better():
    """The placement_planner lane's planned-vs-all-dp speedup unit (the
    exact string bench.py emits) keeps the higher-is-better default: a
    SMALLER speedup means the searched placement lost modeled ground to
    the trivial all-dp mesh."""
    rec = {"metric": "placement_planner", "value": 1.8,
           "unit": "x planned mesh vs naive all-dp, modeled step "
                   "seconds on the wide-MLP sweep model (gate: planned "
                   "<= all-dp on every model; report rendered + "
                   "plan-cache round trip hit asserted in-lane)"}
    assert not bench_compare.lower_is_better(rec)
    assert not bench_compare.lower_is_better(
        dict(rec, metric="placement_planner_smoke"))
    old = {"placement_planner": rec}
    worse = {"placement_planner": dict(rec, value=1.0)}
    res = bench_compare.compare_records(old, worse, 5.0)
    assert res["regressions"] == ["placement_planner"]
    better = {"placement_planner": dict(rec, value=2.5)}
    assert bench_compare.compare_records(old, better, 5.0)["ok"]


def test_trajectory_backend_skip(tmp_path):
    """--dir trajectory mode skips lanes whose two records carry
    DIFFERENT backend stamps (a CPU smoke diffed against a TPU run is a
    machine change, not a regression) with a one-line note naming them;
    explicit OLD NEW compares keep diffing every lane."""
    cpu = [dict(r, backend="cpu") for r in RECORDS]
    tpu = [dict(r, backend="tpu") for r in RECORDS]
    # seed a would-be regression in a lane whose backends differ
    tpu[0] = dict(tpu[0], value=round(2567.5 * 0.5, 1))
    (tmp_path / "BENCH_r01.json").write_text(_lines(cpu))
    (tmp_path / "BENCH_r02.json").write_text(_lines(tpu))
    r = _run("--dir", str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "skipped (backend stamps differ)" in r.stdout
    assert "resnet50_train_throughput" in r.stdout
    # same-backend pairs in the same trajectory still gate
    mixed = [dict(r) for r in cpu]
    mixed[0] = dict(mixed[0], value=round(2567.5 * 0.5, 1))
    (tmp_path / "BENCH_r03.json").write_text(_lines(cpu))
    (tmp_path / "BENCH_r04.json").write_text(_lines(mixed))
    r = _run("--dir", str(tmp_path))
    assert r.returncode == 1
    assert "resnet50_train_throughput" in r.stderr
    # explicit two-file mode compares regardless of backend stamps
    old_p, new_p = tmp_path / "BENCH_r01.json", tmp_path / "BENCH_r02.json"
    r = _run(str(old_p), str(new_p))
    assert r.returncode == 1
    assert "resnet50_train_throughput" in r.stderr


def test_compare_records_backend_skip_api():
    old = {"a": {"metric": "a", "value": 100.0, "unit": "QPS",
                 "backend": "tpu"}}
    new = {"a": {"metric": "a", "value": 50.0, "unit": "QPS",
                 "backend": "cpu"}}
    res = bench_compare.compare_records(old, new, 5.0, backend_skip=True)
    assert res["ok"] and res["backend_skipped"] == ["a"]
    assert res["rows"] == []
    # default (no skip) still regresses; records without stamps compare
    res = bench_compare.compare_records(old, new, 5.0)
    assert res["regressions"] == ["a"] and res["backend_skipped"] == []
    for r in (old, new):
        r["a"] = {k: v for k, v in r["a"].items() if k != "backend"}
    res = bench_compare.compare_records(old, new, 5.0, backend_skip=True)
    assert res["regressions"] == ["a"]


def test_elastic_training_lane_is_lower_is_better():
    """The elastic_training lane's publish-to-served-lag unit (the exact
    string bench.py emits) pins lower-is-better — a LARGER lag under the
    fleet's kill/hot-join churn is a regression — including for the
    _smoke-suffixed variant."""
    rec = {"metric": "elastic_training", "value": 450.0,
           "unit": "ms publish-to-served lag p50 (pacer freeze cut -> "
                   "registry publish -> rollout onto the live fleet), "
                   "with a Master-fed elastic trainer pool surviving a "
                   "pserver-shard SIGKILL + worker kill/hot-join"}
    assert bench_compare.lower_is_better(rec)
    assert bench_compare.lower_is_better(dict(rec, metric="elastic_training_smoke"))
    old = {"elastic_training_smoke": dict(rec, metric="elastic_training_smoke")}
    slower = {"elastic_training_smoke":
              dict(rec, metric="elastic_training_smoke", value=600.0)}
    res = bench_compare.compare_records(old, slower, 5.0)
    assert res["regressions"] == ["elastic_training_smoke"]
    faster = {"elastic_training_smoke":
              dict(rec, metric="elastic_training_smoke", value=300.0)}
    assert bench_compare.compare_records(old, faster, 5.0)["ok"]


def test_multi_tenant_serving_lane_is_lower_is_better():
    """The multi_tenant_serving lane's quiet-tenant-p99 unit (the exact
    string bench.py emits) pins lower-is-better — a LARGER p99 beside
    the quota-throttled noisy neighbor is a regression — including for
    the _smoke-suffixed variant."""
    rec = {"metric": "multi_tenant_serving", "value": 6.1,
           "unit": "ms quiet-tenant p99 beside a quota-throttled noisy "
                   "neighbor (lower is better; gate <= 1.3x solo "
                   "baseline asserted in-lane; quota rejects typed, "
                   "zero failovers)"}
    assert bench_compare.lower_is_better(rec)
    assert bench_compare.lower_is_better(
        dict(rec, metric="multi_tenant_serving_smoke"))
    old = {"multi_tenant_serving_smoke":
           dict(rec, metric="multi_tenant_serving_smoke")}
    slower = {"multi_tenant_serving_smoke":
              dict(rec, metric="multi_tenant_serving_smoke", value=9.0)}
    res = bench_compare.compare_records(old, slower, 5.0)
    assert res["regressions"] == ["multi_tenant_serving_smoke"]
    faster = {"multi_tenant_serving_smoke":
              dict(rec, metric="multi_tenant_serving_smoke", value=4.0)}
    assert bench_compare.compare_records(old, faster, 5.0)["ok"]
