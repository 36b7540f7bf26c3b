"""Profiler tests: spans recorded around a real training step, report
aggregation, loadable chrome://tracing JSON (the timeline.py contract —
reference tools/timeline.py:40-134, python/paddle/fluid/profiler.py:33-109).
"""

import io
import json

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.core import profiler as core_prof


def _build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        logits = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(logits, label))
        fluid.optimizer.SGD(0.1).minimize(loss, startup)
    return main, startup, loss


def _feed(rng):
    return {"x": rng.normal(0, 1, (8, 8)).astype("float32"),
            "label": rng.randint(0, 4, (8, 1)).astype("int64")}


def test_profiler_eager_per_op_spans(tmp_path):
    main, startup, loss = _build()
    exe = fluid.Executor(mode="eager")
    exe.run(startup)
    rng = np.random.RandomState(0)
    out = io.StringIO()
    trace_path = str(tmp_path / "trace.json")
    with fluid.profiler.profiler(sorted_key="total",
                                 profile_path=trace_path, file=out):
        for _ in range(3):
            exe.run(main, feed=_feed(rng), fetch_list=[loss])
    report = out.getvalue()
    assert "Profiling Report" in report
    assert "mul" in report and "softmax" in report  # per-op rows
    # chrome trace is loadable and carries complete events
    with open(trace_path) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert "mul" in names and "sgd" in names
    assert all(e["dur"] >= 1 for e in trace["traceEvents"]
               if e["ph"] == "X")


def test_profiler_jit_step_spans():
    main, startup, loss = _build()
    exe = fluid.Executor(mode="jit")
    exe.run(startup)
    rng = np.random.RandomState(1)
    exe.run(main, feed=_feed(rng), fetch_list=[loss])  # compile outside
    core_prof.enable_profiler()
    for _ in range(2):
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
    rows = core_prof.disable_profiler(sorted_key="calls")
    byname = {r["name"]: r for r in rows}
    assert byname["executor.run"]["calls"] == 2
    assert byname["executor.enqueue"]["calls"] == 2


def test_profiler_off_records_nothing():
    core_prof.reset_profiler()
    main, startup, loss = _build()
    exe = fluid.Executor(mode="eager")
    exe.run(startup)
    exe.run(main, feed=_feed(np.random.RandomState(2)), fetch_list=[loss])
    assert core_prof.events() == []


# ---------------------------------------------------------------------------
# LatencyWindow edges: empty window, single sample, capacity wraparound
# ---------------------------------------------------------------------------

def test_latency_window_empty():
    w = core_prof.LatencyWindow(capacity=8)
    snap = w.snapshot()
    # health endpoints read these straight: no samples must mean zeros,
    # never a divide-by-zero or a missing key
    assert snap == {"count": 0, "window": 0, "p50_ms": 0.0, "p99_ms": 0.0}
    assert w.percentiles((50, 90, 99)) == {50: 0.0, 90: 0.0, 99: 0.0}


def test_latency_window_single_sample():
    w = core_prof.LatencyWindow(capacity=8)
    w.record(0.004)                    # 4 ms
    snap = w.snapshot()
    assert snap["count"] == 1 and snap["window"] == 1
    # every percentile of a single sample IS that sample
    np.testing.assert_allclose(snap["p50_ms"], 4.0)
    np.testing.assert_allclose(snap["p99_ms"], 4.0)
    np.testing.assert_allclose(snap["max_ms"], 4.0)


def test_latency_window_capacity_wraparound_percentiles():
    w = core_prof.LatencyWindow(capacity=8)
    for ms in range(12):               # 0..11 ms; ring keeps the LAST 8
        w.record(ms / 1e3)
    snap = w.snapshot()
    assert snap["count"] == 12 and snap["window"] == 8
    # the window holds 4..11: percentiles are over THOSE, the evicted
    # 0..3 must not drag the percentiles down
    np.testing.assert_allclose(snap["p50_ms"], np.percentile(
        np.arange(4, 12), 50), rtol=1e-6)
    np.testing.assert_allclose(snap["max_ms"], 11.0)
    ps = w.percentiles((0, 50, 100))
    np.testing.assert_allclose(ps[0], 4.0)
    np.testing.assert_allclose(ps[100], 11.0)
    # keep wrapping a full extra lap: still exactly the last 8
    for ms in range(12, 24):
        w.record(ms / 1e3)
    snap = w.snapshot()
    assert snap["window"] == 8 and snap["count"] == 24
    np.testing.assert_allclose(snap["p50_ms"], np.percentile(
        np.arange(16, 24), 50), rtol=1e-6)
