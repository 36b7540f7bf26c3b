"""Compile stages (obs.perf): JAX's own duration events (jaxpr trace, jaxpr
-> MLIR, backend compile, the persistent cache's retrieval) split every
build's wall time into trace / lower / xla_compile / cache_load / other, on
the CompileRecord and in ``paddle_tpu_compile_stage_seconds{site, stage}``;
``paddle_tpu_compile_builds{site, source}`` counts the executables. Builds
that no executor-owned function asked for land under ``site=eager``."""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core import compile_cache
from paddle_tpu.obs import perf
from paddle_tpu.obs.metrics import REGISTRY
from paddle_tpu.obs.recorder import RECORDER
from paddle_tpu.testing.models import build_mlp, mlp_feed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SECONDS, BUILDS, HISTOGRAM = ("paddle_tpu_compile_stage_seconds",
                              "paddle_tpu_compile_builds",
                              "paddle_tpu_compile_seconds")
TRACE, LOWER, BACKEND = perf._EV_TRACE, perf._EV_LOWER, perf._EV_BACKEND


@pytest.fixture(autouse=True)
def _fresh_perf_log():
    perf.COMPILE_LOG.clear()
    RECORDER.clear()
    yield
    perf.COMPILE_LOG.clear()
    RECORDER.clear()


def _sum(family, **labels):
    """The sum of ``family``'s children whose labels match."""
    fam = REGISTRY.get(family)
    return sum(child.value for key, child in fam.children().items()
               if all(dict(zip(fam.label_names, key))[k] == v
                      for k, v in labels.items()))


def _families():
    return {name: REGISTRY.get(name).snapshot()["values"]
            for name in (SECONDS, BUILDS, HISTOGRAM)}


def _span(event, seconds, inside=None, hit=False):
    """One of JAX's timed spans, as ``dispatch.log_elapsed_time`` publishes
    it: the scalar on entry, whatever ``inside`` does, the duration."""
    jax.monitoring.record_scalar(event, 0.0, fun_name="f")
    if inside is not None:
        inside()
    if hit:
        jax.monitoring.record_event(perf._EV_CACHE_HIT)
        jax.monitoring.record_event_duration_secs(perf._EV_CACHE_READ,
                                                  seconds / 4)
    jax.monitoring.record_event_duration_secs(event, seconds, fun_name="f")


def _started_mlp(**kw):
    main, startup, loss = build_mlp(**kw)
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    return main, loss, exe, scope


# ---------------------------------------------------------------------------
# (1) the record of a real build
# ---------------------------------------------------------------------------

def test_first_run_lands_stages_that_sum_to_seconds_and_the_second_none():
    main, startup, loss = build_mlp(hidden=12, seed=3)
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
    start, step = perf.COMPILE_LOG.records()
    for rec in (start, step):
        st = rec.stages
        assert set(st) == set(perf.STAGES)
        assert st["trace"] > 0 and st["lower"] > 0
        assert st["xla_compile"] + st["cache_load"] > 0
        assert st["other"] >= 0
        assert sum(st.values()) == pytest.approx(rec.seconds, rel=1e-9)
        assert rec.as_dict()["stages"] == st
    # the identity tells the start-up program from the train program
    assert start.identity["n_fetch"] == 0 and step.identity["n_fetch"] == 1
    assert step.identity["n_ops"] == len(main.global_block().ops)
    assert start.identity["n_ops"] == len(startup.global_block().ops)
    assert step.identity["feeds"]["img"] == [4, 16]
    assert RECORDER.events(kinds={"compile"})[-1]["detail"]["stages"][
        "trace"] > 0
    by_site = perf.COMPILE_LOG.stats()["by_site"]["jit_step"]
    assert sum(by_site["stages"].values()) == pytest.approx(
        by_site["seconds"])
    # steady state: the same shapes again move no family at all
    before = _families()
    for _ in range(3):
        exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
    assert _families() == before


# ---------------------------------------------------------------------------
# (2) a fresh process served from the persistent cache
# ---------------------------------------------------------------------------

_CHILD = """
import json, sys
sys.path.insert(0, %r)
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import paddle_tpu.fluid as fluid
from paddle_tpu.core import compile_cache
from paddle_tpu.obs import perf
from paddle_tpu.obs.metrics import REGISTRY
from paddle_tpu.testing.models import build_mlp, mlp_feed
_, stats = compile_cache.enable()
main, startup, loss = build_mlp()
exe, scope = fluid.Executor(), fluid.Scope()
exe.run(startup, scope=scope)
exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
builds = REGISTRY.get("paddle_tpu_compile_builds")
print(json.dumps({
    "stages": perf.COMPILE_LOG.stats()["by_site"]["jit_step"]["stages"],
    "cache_read": sum(r.cache_read for r in perf.COMPILE_LOG.records()),
    "from_cache": builds.labels(site="jit_step", source="cache").value,
    "compiled": builds.labels(site="jit_step", source="compiled").value,
    "hits": stats.hits}))
""" % ROOT


def test_second_process_loads_from_the_persistent_cache(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))

    def child():
        out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first, second = child(), child()
    assert first["compiled"] == 2 and first["from_cache"] == 0
    assert first["stages"]["xla_compile"] > 0
    assert first["stages"]["cache_load"] == 0
    assert second["from_cache"] >= 1 and second["hits"] >= 1
    assert second["stages"]["cache_load"] > 0
    assert second["stages"]["xla_compile"] < first["stages"]["xla_compile"]
    # the read alone is a part of the load (which also hashes the module
    # for its key and deserialises)
    assert 0 < second["cache_read"] <= second["stages"]["cache_load"]
    # what no cache saves is still there
    assert second["stages"]["trace"] > 0 and second["stages"]["lower"] > 0


# ---------------------------------------------------------------------------
# (3) builds nobody owns
# ---------------------------------------------------------------------------

def test_jnp_function_outside_any_executor_lands_under_eager():
    seconds, builds = _sum(SECONDS, site="eager"), _sum(BUILDS, site="eager")
    owned = _sum(SECONDS) - seconds
    jax.jit(lambda x: jnp.tanh(x) * 1.2345 + 0.5)(np.ones(7, np.float32))
    assert _sum(BUILDS, site="eager") >= builds + 1
    for stage in ("trace", "lower"):
        assert _sum(SECONDS, site="eager", stage=stage) > 0
    assert _sum(SECONDS, site="eager") > seconds
    assert _sum(SECONDS, site="eager", stage="other") == 0
    # no record, no histogram child, nothing under another site
    assert perf.COMPILE_LOG.records() == []
    assert ("eager",) not in REGISTRY.get(HISTOGRAM).children()
    assert _sum(SECONDS) - _sum(SECONDS, site="eager") == owned


# ---------------------------------------------------------------------------
# (4) two threads building at once
# ---------------------------------------------------------------------------

def test_two_threads_building_at_once_credit_each_its_own_site():
    main, startup, loss = build_mlp(hidden=10, seed=5)
    exe = fluid.Executor()
    scope = fluid.Scope()
    jax.random.PRNGKey(0)                    # its builds are not the test's
    gate = threading.Barrier(2)
    owned_builds = _sum(BUILDS, site="jit_step")
    owned_seconds = _sum(SECONDS, site="jit_step")
    eager_builds = _sum(BUILDS, site="eager")
    eager_trace = _sum(SECONDS, site="eager", stage="trace")
    errors = []

    def start_up():
        try:
            gate.wait(10)
            exe.run(startup, scope=scope)
        except Exception as e:               # pragma: no cover
            errors.append(e)

    def eager():
        try:
            gate.wait(10)
            for i in range(3):
                jax.jit(lambda x, i=i: x * (2.5 + i) - i)(
                    np.ones(5 + i, np.float32))
            _span(TRACE, 1000.0)             # would swamp the other thread
        except Exception as e:               # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=f) for f in (start_up, eager)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors
    (rec,) = perf.COMPILE_LOG.records()
    assert rec.site == "jit_step" and rec.seconds < 100
    assert sum(rec.stages.values()) == pytest.approx(rec.seconds)
    assert rec.stages["trace"] > 0 and rec.stages["xla_compile"] > 0
    assert _sum(BUILDS, site="jit_step") == owned_builds + 1
    assert _sum(SECONDS, site="jit_step") == pytest.approx(
        owned_seconds + rec.seconds)
    assert _sum(BUILDS, site="eager") >= eager_builds + 3
    assert _sum(SECONDS, site="eager", stage="trace") >= eager_trace + 1000


# ---------------------------------------------------------------------------
# (5) the layer off
# ---------------------------------------------------------------------------

def test_layer_off_moves_no_family_and_retraces_nothing():
    from paddle_tpu.core.executor import _JIT_KEY_FLAGS
    from paddle_tpu.core.flags import flags
    assert "obs_compile_log" not in _JIT_KEY_FLAGS
    assert "obs_compile_cost" not in flags()
    main, loss, exe, scope = _started_mlp(hidden=9, seed=7)
    exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
    retraces = REGISTRY.get("paddle_tpu_executor_retraces").total()
    perf.COMPILE_LOG.clear()
    fluid.set_flags({"obs_compile_log": 0})
    try:
        before = _families()
        exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
        assert REGISTRY.get("paddle_tpu_executor_retraces").total() \
            == retraces
        exe.run(main, feed=mlp_feed(8), fetch_list=[loss], scope=scope)
        jax.jit(lambda x: x * 3.25 - 1.5)(np.ones(3, np.float32))
        _span(BACKEND, 2.0, hit=True)
        assert _families() == before
        assert perf.COMPILE_LOG.records() == []
        assert perf.building().open == []
    finally:
        fluid.set_flags({"obs_compile_log": 256})
    # back on: the old shapes are still compiled, a new one is seen again
    exe.run(main, feed=mlp_feed(4), fetch_list=[loss], scope=scope)
    assert REGISTRY.get("paddle_tpu_executor_retraces").total() == retraces
    assert perf.COMPILE_LOG.records() == []
    exe.run(main, feed=mlp_feed(6), fetch_list=[loss], scope=scope)
    assert len(perf.COMPILE_LOG.records()) == 1


# ---------------------------------------------------------------------------
# (6) + (7) every owning site carries stages, and they sum to its histogram
# ---------------------------------------------------------------------------

def _build_jit_scan(tmp_path):
    main, loss, exe, scope = _started_mlp(hidden=11, seed=13)
    exe.run_steps(main, feeds=[mlp_feed(4), mlp_feed(4, seed=1)],
                  fetch_list=[loss], scope=scope, steps=2)


def _export(tmp_path):
    main, startup, _loss, logits = build_mlp(return_logits=True, hidden=14)
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    d = str(tmp_path / "bundle")
    fluid.io.save_inference_model(d, ["img"], [logits], exe, main,
                                  scope=scope)
    return d


def _build_engine_warmup(tmp_path):
    from paddle_tpu.serving import InferenceEngine
    assert InferenceEngine(_export(tmp_path), buckets=[1, 2]).warmup() == 2


def _build_attribute(tmp_path):
    main, loss, exe, scope = _started_mlp(hidden=15, seed=17)
    perf.attribute(main, feed=mlp_feed(4), fetch_list=[loss], executor=exe,
                   scope=scope)


def _build_sharded_step(tmp_path):
    from paddle_tpu.parallel import (ShardingPlan, make_mesh,
                                     shard_program_step)
    assert len(jax.devices()) >= 4          # conftest asks XLA for eight
    main, loss, exe, scope = _started_mlp(hidden=16, seed=23)
    fn, state, feeds = shard_program_step(
        exe, main, mlp_feed(8), [loss], ShardingPlan(make_mesh(4, ("dp",))),
        scope=scope)
    n = perf.COMPILE_LOG.stats()["count"]
    for _ in range(3):                      # one build, then steady
        state, _ = fn(state, feeds)
    assert perf.COMPILE_LOG.stats()["count"] == n + 1


@pytest.mark.parametrize("site,build,n", [
    ("jit_scan", _build_jit_scan, 1),
    ("sharded_step", _build_sharded_step, 1),
    ("engine_warmup", _build_engine_warmup, 2),
    ("attribute", _build_attribute, 1),
])
def test_every_owning_site_carries_stages(tmp_path, site, build, n):
    builds = _sum(BUILDS, site=site)
    build(tmp_path)
    recs = perf.COMPILE_LOG.records(site=site)
    assert len(recs) == n
    for rec in recs:
        assert rec.stages["trace"] > 0 and rec.stages["lower"] > 0
        assert rec.stages["xla_compile"] + rec.stages["cache_load"] > 0
        assert sum(rec.stages.values()) == pytest.approx(rec.seconds)
    assert _sum(BUILDS, site=site) == builds + n


def test_stage_counter_sums_to_the_histogram_per_site(tmp_path):
    for name in (SECONDS, HISTOGRAM):
        REGISTRY.get(name).reset()
    main, loss, exe, scope = _started_mlp(hidden=13, seed=19)
    for batch in (2, 4):
        exe.run(main, feed=mlp_feed(batch), fetch_list=[loss], scope=scope)
    _build_jit_scan(tmp_path)
    _build_engine_warmup(tmp_path)
    jax.jit(lambda x: x / 7.5)(np.ones(4, np.float32))     # eager: neither
    observed = {key[0]: sum(child.window._durs) for key, child in
                REGISTRY.get(HISTOGRAM).children().items()
                if child.count}
    assert {"jit_step", "jit_scan", "engine_warmup"} <= set(observed)
    assert "eager" not in observed
    for site, seconds in observed.items():
        assert _sum(SECONDS, site=site) == pytest.approx(seconds), site
        assert sum(r.seconds for r in perf.COMPILE_LOG.records(site=site)) \
            == pytest.approx(seconds)


# ---------------------------------------------------------------------------
# the listener itself, driven with JAX's own events
# ---------------------------------------------------------------------------

def test_nested_spans_count_their_own_seconds_once():
    """A jitted jnp function traced for the first time inside the program's
    trace opens a span inside the outer one; an outcome decides what a
    backend compile was."""
    with perf.building():
        _span(TRACE, 1.0, inside=lambda: (_span(TRACE, 0.25),
                                          _span(TRACE, 0.125)))
        _span(LOWER, 0.5, inside=lambda: _span(TRACE, 0.0625))
        _span(BACKEND, 2.0, hit=True)
        _span(BACKEND, 4.0)
    rec = perf.note_compile("jit_step", 10.0, identity={"n_ops": 3})
    assert rec.stages == {"trace": 1.0625, "lower": 0.4375,
                          "cache_load": 2.0, "xla_compile": 4.0,
                          "other": 2.5}
    assert rec.cache_read == 0.5
    assert perf.building().open == []
    # outside an owned build the same spans are the eager site's
    eager = {s: _sum(SECONDS, site="eager", stage=s) for s in perf.STAGES}
    loaded = _sum(BUILDS, site="eager", source="cache")
    _span(TRACE, 1.0, inside=lambda: _span(TRACE, 0.25))
    _span(BACKEND, 2.0, hit=True)
    assert _sum(SECONDS, site="eager", stage="trace") == pytest.approx(
        eager["trace"] + 1.0)
    assert _sum(SECONDS, site="eager", stage="cache_load") == pytest.approx(
        eager["cache_load"] + 2.0)
    assert _sum(SECONDS, site="eager", stage="xla_compile") == \
        eager["xla_compile"]
    assert _sum(BUILDS, site="eager", source="cache") == loaded + 1
    assert len(perf.COMPILE_LOG.records()) == 1


def test_a_build_that_raises_leaves_nothing_behind():
    eager = _sum(SECONDS, site="eager")
    with pytest.raises(RuntimeError):
        with perf.building():
            _span(TRACE, 3.0)
            raise RuntimeError("the lowering of an op failed")
    assert perf.building().owners == 0
    rec = perf.note_compile("jit_step", 1.0)
    assert rec.stages == {"trace": 0.0, "lower": 0.0, "xla_compile": 0.0,
                          "cache_load": 0.0, "other": 1.0}
    assert _sum(SECONDS, site="eager") == eager


@pytest.mark.parametrize("seconds,measured,want", [
    (2.0, (0.5, 0.25, 1.0, 0.0), (0.5, 0.25, 1.0, 0.0, 0.25)),
    (2.0, (), (0.0, 0.0, 0.0, 0.0, 2.0)),
    # JAX's clock (time.time) ran ahead of ours: scaled, other 0, same sum
    (1.0, (1.0, 0.5, 0.5, 0.0), (0.5, 0.25, 0.25, 0.0, 0.0)),
])
def test_split_seconds_always_sums_to_the_wall(seconds, measured, want):
    got = perf.split_seconds(seconds, measured)
    assert tuple(got[s] for s in perf.STAGES) == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(seconds)


# ---------------------------------------------------------------------------
# compile_cache.enable(): one listener a process
# ---------------------------------------------------------------------------

def test_enable_twice_leaves_one_cache_stats_listener(monkeypatch, tmp_path):
    from jax._src import monitoring
    # with the variable set enable() places no directory in code, so the
    # worker's other tests keep running without a persistent cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))

    def listeners():
        return [f for f in monitoring.get_event_listeners()
                if isinstance(getattr(f, "__self__", None),
                              compile_cache.CacheStats)]

    _, first = compile_cache.enable()
    _, second = compile_cache.enable()
    assert [f.__self__ for f in listeners()] == [second]
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert (first.hits, first.misses) == (0, 0)
    assert (second.hits, second.misses) == (1, 1)
    # the stage listeners went on at import, once
    assert monitoring.get_event_duration_listeners().count(
        perf._on_duration) == 1
