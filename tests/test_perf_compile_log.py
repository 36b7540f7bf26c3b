"""Compile telemetry (obs.perf): every compiled-executable build lands a
``paddle_tpu_compile_seconds`` observation + CompileRecord + ``compile``
flight event; engine warmup yields exactly one per executable; steady-
state dispatch yields ZERO (the zero-retrace invariant, now observable);
the layer's flags are NOT in the executor jit key (flipping never
retraces).
"""

import json

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.obs import perf
from paddle_tpu.obs.metrics import REGISTRY
from paddle_tpu.obs.recorder import RECORDER
from paddle_tpu.testing.models import build_mlp, export_tiny_lm, mlp_feed


@pytest.fixture(autouse=True)
def _fresh_perf_log():
    perf.COMPILE_LOG.clear()
    RECORDER.clear()
    yield
    perf.COMPILE_LOG.clear()
    RECORDER.clear()


def _export_mlp(tmp_path, **kw):
    main, startup, _loss, logits = build_mlp(return_logits=True, **kw)
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    d = str(tmp_path / "bundle")
    fluid.io.save_inference_model(d, ["img"], [logits], exe, main,
                                  scope=scope)
    return d


# ---------------------------------------------------------------------------
# executor-level telemetry
# ---------------------------------------------------------------------------

def test_jit_build_lands_record_histogram_and_flight_event():
    main, startup, loss = build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    hist = REGISTRY.get("paddle_tpu_compile_seconds")
    before = hist.total()
    exe.run(startup, scope=scope)
    exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
    recs = perf.COMPILE_LOG.records()
    # startup block + training step = two compiled-executable builds
    assert len(recs) == 2
    assert all(r.site == "jit_step" for r in recs)
    assert all(r.seconds > 0 for r in recs)
    step = recs[-1]
    assert step.identity["feeds"]["img"] == [4, 16]
    assert "program_version" in step.identity
    assert hist.total() == before + 2
    events = RECORDER.events(kinds={"compile"})
    assert len(events) == 2
    assert events[-1]["component"] == "jit_step"
    assert events[-1]["detail"]["seconds"] > 0
    # records and dumps are json-safe end to end
    json.dumps([r.as_dict() for r in recs])
    # steady state: replaying the same shapes adds NOTHING
    n = perf.COMPILE_LOG.stats()["count"]
    for _ in range(3):
        exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
    assert perf.COMPILE_LOG.stats()["count"] == n
    # a NEW batch shape is an internal jit retrace of the same compiled
    # fn — the build-time retrace counter misses it, this layer must not
    exe.run(main, feed=mlp_feed(6), fetch_list=[loss], scope=scope)
    assert perf.COMPILE_LOG.stats()["count"] == n + 1


def test_run_steps_scan_attributed_to_jit_scan():
    main, startup, loss = build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    perf.COMPILE_LOG.clear()
    exe.run_steps(main, feeds=[mlp_feed(4), mlp_feed(4, seed=1)],
                  fetch_list=[loss], scope=scope, steps=2)
    sites = [r.site for r in perf.COMPILE_LOG.records()]
    assert sites == ["jit_scan"]


def test_flag_off_disables_layer_and_never_retraces():
    from paddle_tpu.core.executor import _JIT_KEY_FLAGS
    assert "obs_compile_log" not in _JIT_KEY_FLAGS

    main, startup, loss = build_mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
    retraces = REGISTRY.get("paddle_tpu_executor_retraces").total()
    n = perf.COMPILE_LOG.stats()["count"]
    fluid.set_flags({"obs_compile_log": 0})
    try:
        assert not perf.enabled()
        # flipping the layer off must not retrace the cached step...
        exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
        assert REGISTRY.get("paddle_tpu_executor_retraces").total() \
            == retraces
        # ...and a build while off records nothing anywhere
        ev_before = len(RECORDER.events(kinds={"compile"}))
        exe.run(main, feed=mlp_feed(8), fetch_list=[loss], scope=scope)
        assert perf.COMPILE_LOG.stats()["count"] == n
        assert len(RECORDER.events(kinds={"compile"})) == ev_before
    finally:
        fluid.set_flags({"obs_compile_log": 256})
    # back on: the layer resumes without retracing the old shapes
    exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
    assert REGISTRY.get("paddle_tpu_executor_retraces").total() == retraces


def test_compile_log_ring_bounded_and_stats():
    log = perf.CompileLog(capacity=3)
    for i in range(5):
        log.add(perf.CompileRecord("jit_step", 0.5, identity={"i": i}))
    recs = log.records()
    assert len(recs) == 3
    assert [r.identity["i"] for r in recs] == [2, 3, 4]
    st = log.stats()
    assert st["count"] == 5                       # lifetime, not window
    assert st["total_seconds"] == pytest.approx(2.5)
    assert st["by_site"]["jit_step"]["count"] == 3
    log.clear()
    assert log.records() == [] and log.stats()["count"] == 0


# ---------------------------------------------------------------------------
# engine warmup: exactly one record + one event per executable
# ---------------------------------------------------------------------------

def test_inference_engine_warmup_one_record_per_executable(tmp_path):
    from paddle_tpu.serving import InferenceEngine
    d = _export_mlp(tmp_path)
    perf.COMPILE_LOG.clear()
    RECORDER.clear()
    eng = InferenceEngine(d, buckets=[1, 2, 4])
    compiled = eng.warmup()
    assert compiled == 3
    recs = perf.COMPILE_LOG.records()
    assert len(recs) == 3
    assert [r.site for r in recs] == ["engine_warmup"] * 3
    assert sorted(r.identity["bucket"] for r in recs) == [1, 2, 4]
    assert len(RECORDER.events(kinds={"compile"})) == 3
    # steady state: dispatches through every bucket add ZERO
    n = perf.COMPILE_LOG.stats()["count"]
    for rows in (1, 2, 3, 4, 2):
        eng.infer({"img": np.zeros((rows, 16), np.float32)})
    assert perf.COMPILE_LOG.stats()["count"] == n
    assert eng.hot_recompiles == 0


def test_generation_engine_warmup_one_record_per_executable(tmp_path):
    from paddle_tpu.serving.generate import GenerationEngine
    d = str(tmp_path / "lm")
    export_tiny_lm(d)
    perf.COMPILE_LOG.clear()
    RECORDER.clear()
    eng = GenerationEngine(d, max_seqs=2, max_len=32, num_blocks=32)
    compiled = eng.warmup()
    recs = perf.COMPILE_LOG.records()
    # one per executable: the decode step + every prefill bucket
    assert compiled == len(recs) == 4
    assert all(r.site == "genengine_warmup" for r in recs)
    phases = sorted((r.identity["phase"], r.identity["bucket"])
                    for r in recs)
    assert phases == [("decode", 2), ("prefill", 8), ("prefill", 16),
                      ("prefill", 32)]
    assert len(RECORDER.events(kinds={"compile"})) == 4
    # steady state: a full generate (prefill + decode steps) adds ZERO
    n = perf.COMPILE_LOG.stats()["count"]
    handle, _toks, finished = eng.start([1, 2, 3], 4)
    while not finished:
        finished = any(f for _h, _t, f in eng.step())
    assert perf.COMPILE_LOG.stats()["count"] == n
    assert eng.hot_recompiles == 0
