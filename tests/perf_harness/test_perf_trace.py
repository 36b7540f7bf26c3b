"""The benchmark's trace reducer on a hand-built trace, and its table of
peaks against the planner's."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import harness, trace  # noqa: E402

sys.path.remove(ROOT)

SPANS = ("feed_next", "step_call", "loss_wait")


@pytest.fixture(scope="module")
def reduced():
    """The hand-built trace in the structure ``trace.load_xplane`` gives."""
    with open(os.path.join(harness.BENCH_DIR, "testdata",
                           "handbuilt_trace.json")) as f:
        raw = json.load(f)

    def per_device(key):
        return {int(k): [tuple(e) for e in v]
                for k, v in raw.get(key, {}).items()}

    return trace.reduce_trace(
        {"devices": per_device("devices"),
         "in_flight": per_device("in_flight"),
         "host": sorted((tuple(e) for e in raw["host"]),
                        key=lambda e: e[1]),
         "planes": raw.get("planes", [])}, chips=2)


def test_busy_is_the_union_not_the_sum(reduced):
    assert reduced["window_s"] == pytest.approx(10.0)
    assert reduced["busy_s_device0"] == pytest.approx(7.0)   # sum is 10.5
    assert reduced["busy_s"] == pytest.approx((7.0 + 2.0) / 2)
    idle = 1 - reduced["busy_s_device0"] / reduced["window_s"]
    assert idle == pytest.approx(0.3)


def test_self_time_takes_children_out(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["fusion"] == pytest.approx(2.0 + 1.0 + 0.5)
    assert ops["lstm_seq"] == pytest.approx(2.0)
    assert ops["while"] == pytest.approx(1.0)
    assert ops["all-reduce"] == pytest.approx(0.5)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s_device0"])
    assert reduced["device_ops"][0][0] == "fusion"          # longest first


def test_idle_gaps_are_laid_at_the_covering_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["feed_next"] == pytest.approx(1.0)
    assert gaps["step_call"] == pytest.approx(0.5)
    assert gaps["loss_wait"] == pytest.approx(1.5)
    assert sum(gaps.values()) == pytest.approx(3.0)
    assert trace.attribute_gaps([(20.0, 21.0)], reduced and [
        ("feed_next", 0.0, 1.0)]) == [("between_spans", pytest.approx(1.0))]


def test_exposed_against_hidden_collective_time(reduced):
    total, exposed = trace.exposed_seconds(
        reduced["events_device0"], reduced["selfs_device0"], trace.COLLECTIVE)
    assert total == pytest.approx(1.0)
    assert exposed == pytest.approx(0.5)


def test_share_of_busy_time_in_named_kernels(reduced):
    run = SimpleNamespace(trace=reduced, traced_steps=2, notes=[])
    share = harness.load_module(os.path.join(
        harness.BENCH_DIR, "readers", "device_op_share.py"), "r_share")
    assert share.read({"pattern": " custom-call\\("}, run) == \
        pytest.approx(100 * 2.0 / 7.0)
    busy = harness.load_module(os.path.join(
        harness.BENCH_DIR, "readers", "device_busy.py"), "r_busy")
    assert busy.read({}, run) == pytest.approx(3500.0)
    coll = harness.load_module(os.path.join(
        harness.BENCH_DIR, "readers", "collective_exposed.py"), "r_coll")
    assert coll.read({"pattern": trace.COLLECTIVE}, run) == \
        pytest.approx(250.0)
    # a reader that finds nothing to read returns nothing
    empty = SimpleNamespace(trace=None, traced_steps=0, notes=[])
    assert share.read({"pattern": "x"}, empty) is None
    assert busy.read({}, empty) is None and coll.read(
        {"pattern": "x"}, empty) is None


def test_interval_arithmetic():
    assert trace.union([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == \
        [(0, 2.5), (3, 4)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert trace.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == \
        [(0, 0.5), (2.5, 3)]
    assert trace.strip_suffix("%fusion.123") == "fusion"
    assert trace.strip_suffix("all-reduce-start.7") == "all-reduce-start"


def test_peaks_equal_the_planners_and_unknown_devices_raise():
    from paddle_tpu.parallel.planner import DEVICE_RATES
    assert harness.load_peaks("TPU v5 lite") == DEVICE_RATES["TPU v5 lite"]
    with pytest.raises(harness.CellError):
        harness.load_peaks("TPU v9 imaginary")


def test_mfu_reader_divides_by_chips_and_peak():
    mfu = harness.load_module(os.path.join(
        harness.BENCH_DIR, "readers", "model_flops_utilization.py"), "r_mfu")
    run = SimpleNamespace(traced_steps=10, traced_wall_s=2.0, chips=4,
                          traced_flops=1.97e14 * 2.0,
                          peaks=harness.load_peaks("TPU v5 lite"))
    assert mfu.read({}, run) == pytest.approx(25.0)
    run.peaks = None
    assert mfu.read({}, run) is None
