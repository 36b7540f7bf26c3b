"""``benchmark/program_trace.py`` and the readers PR 26 added: the pure
functions on hand-built event lists, the wire-format reader on a trace the
CPU backend writes, and each reader on a ``run`` without a trace (what the
parent of PR 26, or a CPU, gives: nothing, and no exception)."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import harness, program_trace as pt, trace  # noqa: E402

sys.path.remove(ROOT)

MS = 1e-3
CLASSES = ("feed_next", "step_call", "loss_wait")


def _reader(metric):
    """(params, read) of one of the new per-layer metrics, as the harness
    loads them."""
    spec = harness.load_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", metric + ".json"))
    reader = harness.load_module(os.path.join(
        harness.BENCH_DIR, "readers", spec["reader"] + ".py"), "r_" + metric)
    return spec["params"], reader.read


def _ev(name, lo_ms, hi_ms, **ids):
    return (name, lo_ms * MS, hi_ms * MS, ids)


@pytest.fixture()
def lines():
    """Two steps on the main line (the benchmark's spans with the program's
    nested in ``step_call``) and a feeder line beside it."""
    main = [
        _ev("feed_next", 0, 2), _ev("reader.get_wait", 0.5, 1.9),
        _ev("step_call", 2, 6), _ev("executor.run", 2.1, 5.9, step_num=7),
        _ev("executor.feed", 2.2, 2.4), _ev("executor.state", 2.4, 3.4),
        _ev("executor.lookup", 3.4, 3.5), _ev("executor.enqueue", 3.5, 5.5),
        _ev("executor.writeback", 5.5, 5.8),
        _ev("loss_wait", 6, 10),
        _ev("feed_next", 10, 10.1),
        _ev("step_call", 10.1, 13.1),
        _ev("executor.run", 10.2, 13.0, step_num=8),
        _ev("executor.enqueue", 11.0, 12.0),
        _ev("loss_wait", 13.1, 20)]
    feeder = [
        _ev("reader.pull", 0, 1.5, batch=3), _ev("lod.pack", 0.5, 1.2),
        _ev("reader.stage", 1.5, 1.8, batch=3),
        _ev("reader.put_wait", 1.8, 9.0),
        _ev("reader.pull", 9.0, 11.0, batch=4)]
    return [main, feeder]


def test_step_span_less_its_enqueue_child(lines):
    host = pt.span_seconds(lines, ("executor.run", "sharding.step"),
                           ("executor.enqueue",))
    assert host == pytest.approx([1.8 * MS, 1.8 * MS])
    assert pt.span_seconds(lines, ("executor.enqueue",)) == pytest.approx(
        [2.0 * MS, 1.0 * MS])
    # a child on ANOTHER line inside the same interval is not taken off
    assert pt.span_seconds(lines, ("feed_next",), ("reader.pull",)) == \
        pytest.approx([2.0 * MS, 0.1 * MS])
    assert pt.span_seconds(lines, ("sharding.step",)) == []


def test_feeder_busy_share_is_the_union_over_its_lines_extent(lines):
    # pull 0..1.5 and 9..11, stage 1.5..1.8: 3.8 ms of the line's 11
    assert pt.busy_share(lines, ("reader.pull", "reader.stage")) == \
        pytest.approx(3.8 / 11.0)
    assert pt.busy_share(lines, ("sharding.step",)) is None


def test_idle_gaps_lie_at_the_innermost_span_of_each_thread_line(lines):
    gaps = [(0.0, 1.0 * MS), (2.0 * MS, 4.0 * MS), (12.5 * MS, 13.5 * MS)]
    laid = pt.lay_gaps(gaps, lines, CLASSES)
    seconds, (main, feeder) = laid["feed_next"]
    assert seconds == pytest.approx(1.0 * MS)
    assert main == pytest.approx({"reader.get_wait": 0.5 * MS})
    # the pack nests in the pull: 0.5..1.0 is the pack's, 0..0.5 the pull's
    assert feeder == pytest.approx({"reader.pull": 0.5 * MS,
                                    "lod.pack": 0.5 * MS})
    seconds, (main, feeder) = laid["step_call"]
    assert seconds == pytest.approx(2.0 * MS + 0.6 * MS)
    assert main == pytest.approx({
        "executor.run": (0.1 + 0.5) * MS,           # 2.1-2.2 and 12.5-13.0
        "executor.feed": 0.2 * MS, "executor.state": 1.0 * MS,
        "executor.lookup": 0.1 * MS, "executor.enqueue": 0.5 * MS})
    assert feeder == pytest.approx({"reader.put_wait": 2.0 * MS})
    seconds, (main, feeder) = laid["loss_wait"]
    assert seconds == pytest.approx(0.4 * MS) and main == {} and feeder == {}


def test_outermost_scope_of_an_op_name():
    assert pt.outermost_scope(
        "jit(step_ps1)/bwd/mul_grad/dot_general:") == "bwd/mul_grad"
    assert pt.outermost_scope(
        "jit(step_ps1)/fwd/while/while/body/closed_call/fwd/mul/dot_general"
    ) == "fwd/while"
    assert pt.outermost_scope("jit(sharded_step_ps1)/opt/momentum/mul") == \
        "opt/momentum"
    assert pt.outermost_scope("jit(step)/dot_general:") is None
    assert pt.outermost_scope("") is None
    assert pt.outermost_scope("jit(step_ps1)/fwd") is None


def test_scope_totals_are_self_time_and_sum_to_busy():
    events = [
        ("%fusion.1", 0.0, 2 * MS, "jit(step_ps1)/fwd/conv2d/conv:"),
        # a while and the operations of its body: the body's are taken off
        ("%while", 2 * MS, 8 * MS, ""),
        ("%fusion.2", 2.5 * MS, 4 * MS, "jit(step_ps1)/bwd/lstm_grad/dot:"),
        ("%fusion.3", 4 * MS, 7 * MS, "jit(step_ps1)/bwd/lstm_grad/add:"),
        ("%copy.1", 9 * MS, 10 * MS, ""),
        ("%fusion.4", 10 * MS, 12 * MS, "jit(step_ps1)/opt/momentum/sub:"),
        ("%fusion.5", 30 * MS, 31 * MS, "jit(step_ps1)/fwd/mul/dot:")]
    by_scope = pt.scope_seconds(events, 1 * MS, 20 * MS)
    assert by_scope == pytest.approx({
        "fwd/conv2d": 1 * MS, "bwd/lstm_grad": 4.5 * MS,
        "opt/momentum": 2 * MS, pt.UNCLAIMED: (1.5 + 1) * MS})
    busy = trace.measure(trace.union(trace.clip(
        [(a, b) for _, a, b, _ in events], 1 * MS, 20 * MS)))
    assert sum(by_scope.values()) == pytest.approx(busy)
    assert pt.phase_seconds(by_scope) == pytest.approx({
        "fwd": 1 * MS, "bwd": 4.5 * MS, "opt": 2 * MS,
        pt.UNCLAIMED: 2.5 * MS})


def test_window_is_the_extent_of_the_named_spans(lines):
    assert pt.window(lines, CLASSES) == pytest.approx((0.0, 20 * MS))
    assert pt.window(lines, ("nothing",)) is None


def test_wire_reader_on_a_trace_the_cpu_backend_writes(tmp_path):
    """Span names, nesting, ``step_num`` and ``batch`` come out of the raw
    ``.xplane.pb`` as ``jax.profiler.ProfileData`` shows them."""
    import glob
    import threading

    import jax
    from jax.profiler import (ProfileData, StepTraceAnnotation,
                              TraceAnnotation)

    def feeder():
        for i in range(2):
            with TraceAnnotation("reader.pull", batch=i):
                with TraceAnnotation("lod.pack"):
                    pass

    jax.profiler.start_trace(str(tmp_path))
    t = threading.Thread(target=feeder)
    t.start()
    for i in range(3):
        with StepTraceAnnotation("executor.run", step_num=40 + i):
            with TraceAnnotation("executor.enqueue"):
                pass
        with TraceAnnotation("not_the_programs"):
            pass
    t.join()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    loaded = pt.load(path)
    assert loaded["devices"] == {}
    main, = [l for l in loaded["host"] if l[0][0] == "executor.run"]
    feed, = [l for l in loaded["host"] if l[0][0] == "reader.pull"]
    assert [e[0] for e in main] == ["executor.run", "executor.enqueue"] * 3
    assert [e[3]["step_num"] for e in main[::2]] == [40, 41, 42]
    assert [e[0] for e in feed] == ["reader.pull", "lod.pack"] * 2
    assert [e[3]["batch"] for e in feed[::2]] == [0, 1]
    assert all(run[1] <= enq[1] and enq[2] <= run[2]
               for run, enq in zip(main[::2], main[1::2]))
    want = sorted((e.start_ns, e.duration_ns)
                  for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for e in line.events
                  if e.name == "executor.run")
    got = sorted((e[1] * 1e9, (e[2] - e[1]) * 1e9) for e in main[::2])
    assert [x for pair in got for x in pair] == pytest.approx(
        [x for pair in want for x in pair], abs=2.0)            # ns


@pytest.mark.parametrize("metric", [
    "exec_host_ms", "exec_enqueue_ms", "fwd_device_ms", "bwd_device_ms",
    "opt_device_ms", "feeder_busy_pct"])
def test_trace_readers_read_nothing_without_a_trace(metric, monkeypatch):
    """Where the harness reduced no trace (a CPU rehearsal; a traced run
    whose profiler never finished) the readers do not go looking for one:
    None, no exception, the metric left out."""
    monkeypatch.setattr(pt, "load_run", lambda: 1 / 0)
    params, read = _reader(metric)
    run = SimpleNamespace(trace=None, traced_steps=0, notes=[], spans={},
                          counters={"setup_end": {}, "window_end": {}})
    assert read(params, run) is None
    assert run.notes == []
    # a trace, and in it nothing of the program's (the parent of PR 26)
    monkeypatch.setattr(pt, "load_run", lambda: {
        "host": [[("step_call", 0.0, 1.0, {})]], "devices": {0: []},
        "window": (0.0, 1.0), "gaps": [(0.0, 1.0)], "by_scope": {}})
    run.trace, run.traced_steps = {"busy_s_device0": 0.0}, 3
    assert read(params, run) is None
    assert run.notes == []


def test_scope_reader_on_a_loaded_trace(monkeypatch):
    events = [("%f.1", 0.0, 3 * MS, "jit(step_ps1)/fwd/mul/dot:"),
              ("%f.2", 3 * MS, 9 * MS, "jit(step_ps1)/bwd/mul_grad/dot:"),
              ("%f.3", 9 * MS, 10 * MS, "jit(step_ps1)/opt/sgd/sub:"),
              ("%copy", 10 * MS, 10.5 * MS, "")]
    loaded = {"by_scope": pt.scope_seconds(events, 0.0, 1.0)}
    monkeypatch.setattr(pt, "load_run", lambda: loaded)
    run = SimpleNamespace(trace={"busy_s_device0": 10.5 * MS},
                          traced_steps=2, notes=[])
    values = {}
    for metric in ("fwd_device_ms", "bwd_device_ms", "opt_device_ms"):
        params, read = _reader(metric)
        values[metric] = read(params, run)
    assert values == pytest.approx({"fwd_device_ms": 1.5,
                                    "bwd_device_ms": 3.0,
                                    "opt_device_ms": 0.5})
    assert len(run.notes) == 2 and "bwd/mul_grad 3.000 (57.1%)" in \
        run.notes[0] and "no scope claims 4.76%" in run.notes[1]
    # a program without scopes (before PR 26): nothing, not zero
    loaded["by_scope"] = {pt.UNCLAIMED: 1.0}
    assert read(params, run) is None


def test_counter_ratio_reads_the_registry_by_label():
    from paddle_tpu.core.lod import pack_sequences
    import numpy as np

    params, read = _reader("pack_fill_pct")
    from paddle_tpu.obs.metrics import REGISTRY
    fam = REGISTRY.get("paddle_tpu_lod_pack_elements")
    r0 = fam.labels(kind="real").value
    p0 = fam.labels(kind="padded").value
    pack_sequences([np.zeros((n, 1), "int64") for n in (1, 3)], max_len=4)
    assert read(params, None) == pytest.approx(100.0 * (r0 + 4) / (p0 + 8))
    missing = dict(params, numerator={"name": "paddle_tpu_no_such",
                                      "labels": {}})
    assert read(missing, None) is None
