"""The step's spans inside the program, on the profiler's clock (PR 26):
``Executor.run`` / ``run_prepared`` / the sharded step / the reader's feeder
thread as ``jax.profiler`` trace annotations that never block, a named scope
``<phase>/<op type>`` per Fluid op in the compiled step, and the two counter
families at the same boundaries. PERF.md section 3 lists them."""

import glob
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import reader
from paddle_tpu.core import executor as core_exec
from paddle_tpu.core import profiler as core_prof
from paddle_tpu.core.lod import pack_sequences
from paddle_tpu.obs.metrics import REGISTRY

CHILDREN = ["executor.feed", "executor.state", "executor.lookup",
            "executor.enqueue", "executor.writeback"]


# ------------------------------------------------------------------ helpers
def _mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        logits = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(logits, label))
        fluid.optimizer.SGD(0.1).minimize(loss, startup)
    return main, startup, loss


def _mlp_feed(seed=0, batch=8):
    rng = np.random.RandomState(seed)
    return {"x": rng.normal(0, 1, (batch, 8)).astype("float32"),
            "label": rng.randint(0, 4, (batch, 1)).astype("int64")}


def _convnet():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[8, 8, 3])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        x = fluid.layers.conv2d(input=img, num_filters=4, filter_size=3,
                                padding=1, act=None, bias_attr=False,
                                data_format="NHWC")
        x = fluid.layers.batch_norm(input=x, act="relu", data_layout="NHWC")
        # the filter feeds two consumers, so append_backward sums its
        # gradient's two parts
        y = fluid.layers.elementwise_add(x=x, y=x)
        logits = fluid.layers.fc(input=y, size=5, act=None)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Momentum(0.01, 0.9).minimize(loss, startup)
    feed = {"img": np.zeros((2, 8, 8, 3), "float32"),
            "label": np.zeros((2, 1), "int64")}
    return main, startup, loss, feed


def _lstm():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        words = fluid.layers.data("words", shape=[1], dtype="int64",
                                  lod_level=1)
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        net = fluid.layers.embedding(words, size=(50, 8))
        proj = fluid.layers.fc(net, 16 * 4)
        net, _ = fluid.layers.dynamic_lstm(proj, size=16 * 4)
        last = fluid.layers.sequence_last_step(net)
        logits = fluid.layers.fc(last, 2, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=2e-3).minimize(loss, startup)
    rng = np.random.RandomState(0)
    feed = {"words": pack_sequences(
        [rng.randint(0, 50, (n, 1)).astype("int64") for n in (3, 5)]),
        "label": np.zeros((2, 1), "int64")}
    return main, startup, loss, feed


def _host_lines(trace_dir, prefixes=("executor.", "sharding.", "reader.",
                                     "lod.")):
    """[[(name, start, end, stats)] per host thread line that holds one of
    the program's spans], from the newest xplane under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    assert files, "the profiler wrote no trace"
    lines = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith(prefixes)]
            if evs:
                lines.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return lines


def _steps_of(line, step_name):
    """[(step span, [its children, in order])] on one thread line."""
    out = []
    for ev in line:
        if ev[0] == step_name:
            out.append((ev, []))
        elif out and out[-1][0][1] <= ev[1] and ev[2] <= out[-1][0][2]:
            out[-1][1].append(ev)
    return out


def _lowered_text(main, startup, loss, feed, **executor_kwargs):
    from paddle_tpu.obs.perf import lower_program

    exe = fluid.Executor(mode="jit", **executor_kwargs)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    block = main.global_block()
    lowered, _ = lower_program(main, exe._prepare_feed(block, feed), [loss],
                               executor=exe, scope=scope)
    return lowered


# ------------------------------------------------- spans in the xplane trace
def test_executor_run_step_span_and_its_five_children(tmp_path):
    main, startup, loss = _mlp()
    exe = fluid.Executor(mode="jit")
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_mlp_feed(), fetch_list=[loss], scope=scope)
    with fluid.profiler.device_tracer(str(tmp_path)):
        for i in range(3):
            exe.run(main, feed=_mlp_feed(i), fetch_list=[loss], scope=scope)
    (line,) = [l for l in _host_lines(tmp_path)
               if any(e[0] == "executor.run" for e in l)]
    steps = _steps_of(line, "executor.run")
    assert len(steps) == 3
    nums = [s[0][3]["step_num"] for s in steps]
    assert nums == list(range(nums[0], nums[0] + 3))
    for _, children in steps:
        assert [c[0] for c in children] == CHILDREN
        assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))


def test_run_prepared_step_span(tmp_path):
    main, startup, loss = _mlp()
    exe = fluid.Executor(mode="jit")
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    prepared = exe.prepare_steps(main, [_mlp_feed(0), _mlp_feed(1)], [loss],
                                 scope=scope)
    exe.run_prepared(prepared)
    with fluid.profiler.device_tracer(str(tmp_path)):
        exe.run_prepared(prepared)
        exe.run_prepared(prepared)
    (line,) = [l for l in _host_lines(tmp_path)
               if any(e[0] == "executor.run_prepared" for e in l)]
    steps = _steps_of(line, "executor.run_prepared")
    assert len(steps) == 2
    assert steps[1][0][3]["step_num"] == steps[0][0][3]["step_num"] + 1
    for _, children in steps:
        assert [c[0] for c in children] == ["executor.enqueue",
                                            "executor.writeback"]


def test_sharded_step_spans_on_four_virtual_devices(tmp_path):
    from paddle_tpu.parallel import ShardingPlan, make_mesh, shard_program_step

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    main, startup, loss = _mlp()
    exe = fluid.Executor(mode="jit")
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    plan = ShardingPlan(make_mesh(4, ("dp",)))
    fn, state, feeds = shard_program_step(exe, main, _mlp_feed(), [loss],
                                          plan, scope=scope)
    state, _ = fn(state, feeds)
    with fluid.profiler.device_tracer(str(tmp_path)):
        for _ in range(2):
            state, fetches = fn(state, feeds)
    assert np.isfinite(np.asarray(fetches[0])).all()
    (line,) = [l for l in _host_lines(tmp_path)
               if any(e[0] == "sharding.step" for e in l)]
    steps = _steps_of(line, "sharding.step")
    assert [s[0][3]["step_num"] for s in steps] == [2, 3]
    for _, children in steps:
        assert [c[0] for c in children] == ["executor.enqueue"]


def test_double_buffer_feeder_and_consumer_spans(tmp_path):
    def source():
        for i in range(4):
            time.sleep(0.03)              # the consumer starves
            yield {"x": np.full((2, 3), i, "float32")}

    staged = REGISTRY.get("paddle_tpu_reader_batches")
    before = {e: staged.labels(event=e).value for e in ("staged", "starved")}
    with fluid.profiler.device_tracer(str(tmp_path)):
        got = [np.asarray(f["x"])[0, 0]
               for f in reader.double_buffer(source)()]
    assert got == [0, 1, 2, 3]
    lines = _host_lines(tmp_path, prefixes=("reader.",))
    (feeder,) = [l for l in lines if any(e[0] == "reader.pull" for e in l)]
    (consumer,) = [l for l in lines
                   if any(e[0] == "reader.get_wait" for e in l)]
    assert feeder is not consumer
    pulls = [e for e in feeder if e[0] == "reader.pull"]
    stages = [e for e in feeder if e[0] == "reader.stage"]
    # one more pull than batches: the one that finds the source at its end
    assert [e[3]["batch"] for e in pulls] == [0, 1, 2, 3, 4]
    assert [e[3]["batch"] for e in stages] == [0, 1, 2, 3]
    assert all(p[2] <= s[1] for p, s in zip(pulls, stages))
    assert not any(e[0] in ("reader.pull", "reader.stage") for e in consumer)
    after = {e: staged.labels(event=e).value for e in ("staged", "starved")}
    assert after["staged"] - before["staged"] == 4
    assert 1 <= after["starved"] - before["starved"] <= 5


def test_reader_put_wait_when_the_feeder_is_ahead(tmp_path):
    def source():
        for i in range(5):
            yield {"x": np.full((1,), i, "float32")}

    with fluid.profiler.device_tracer(str(tmp_path)):
        it = reader.double_buffer(source, capacity=1)()
        first = next(it)
        time.sleep(0.3)                    # the feeder fills its queue of 1
        rest = list(it)
    assert len(rest) == 4 and np.asarray(first["x"])[0] == 0
    names = {e[0] for l in _host_lines(tmp_path, ("reader.",)) for e in l}
    assert "reader.put_wait" in names


# ------------------------------------------------- the same statements run
@pytest.mark.parametrize("profiler_on", [False, True])
def test_no_step_path_blocks_with_the_profiler_on_or_off(monkeypatch,
                                                         profiler_on):
    main, startup, loss = _mlp()
    exe = fluid.Executor(mode="jit")
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_mlp_feed(), fetch_list=[loss], scope=scope)
    prepared = exe.prepare_steps(main, [_mlp_feed(0)], [loss], scope=scope)
    exe.run_prepared(prepared)

    def refuse(*a, **k):
        raise AssertionError("a step path called block_until_ready")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    core_prof.reset_profiler()
    if profiler_on:
        core_prof.enable_profiler()
    try:
        for i in range(2):
            exe.run(main, feed=_mlp_feed(i), fetch_list=[loss], scope=scope,
                    return_numpy=False)
        exe.run_prepared(prepared, return_numpy=False)
    finally:
        rows = core_prof.disable_profiler() if profiler_on else None
    if profiler_on:
        byname = {r["name"]: r["calls"] for r in rows}
        assert byname["executor.run"] == 2
        assert byname["executor.run_prepared"] == 1
        assert byname["executor.enqueue"] == 3
        for name in CHILDREN[:3]:
            assert byname[name] == 2
    else:
        assert core_prof.events() == []


def test_record_event_carries_ids_and_costs_one_object():
    ev = core_prof.record_event("x", kind="stage", batch=3)
    assert not hasattr(ev, "__dict__")          # __slots__: the hot path
    core_prof.reset_profiler()
    with ev:
        pass
    assert core_prof.events() == []             # profiler off: no record
    core_prof.enable_profiler()
    with core_prof.record_event("y", kind="stage", step_num=7):
        with core_prof.record_event("z"):
            pass
    rows = core_prof.disable_profiler()
    assert sorted(r["name"] for r in rows) == ["y", "z"]


# --------------------------------------------- a named scope per Fluid op
def test_phase_of_every_op_kind_append_backward_and_minimize_insert():
    main, _, _, _ = _convnet()
    block = main.global_block()
    phases = {}
    for op in block.ops:
        phases.setdefault(core_exec._op_phase(op), set()).add(op.type)
    assert {"conv2d", "batch_norm", "mul", "mean"} <= phases["fwd"]
    assert {"conv2d_grad", "mul_grad", "mean_grad"} <= phases["bwd"]
    assert phases["opt"] == {"momentum"}
    # what append_backward inserts writes @GRAD variables: backward
    assert "fill_constant" in phases["bwd"] and "sum" in phases["bwd"]
    for op in block.ops:
        if op.type in ("sum", "fill_constant", "fill_zeros_like") and any(
                "@GRAD" in n for n in op.output_arg_names()):
            assert core_exec._op_phase(op) == "bwd"
    scopes = core_exec._analyze_program(main).op_scopes(block)
    assert len(scopes) == len(block.ops)
    assert scopes[0] == f"fwd/{block.ops[0].type}"
    assert core_exec._analyze_program(main).op_scopes(block) is scopes


def test_lowered_convnet_step_carries_phase_scopes():
    text = _lowered_text(*_convnet()).as_text(debug_info=True)
    for scope in ("fwd/conv2d", "fwd/batch_norm", "bwd/conv2d_grad",
                  "bwd/batch_norm_grad", "bwd/sum", "opt/momentum"):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope


def test_lowered_lstm_step_carries_adam_and_recurrence_scopes():
    text = _lowered_text(*_lstm()).as_text(debug_info=True)
    for scope in ("fwd/lstm", "bwd/lstm_grad", "opt/adam",
                  "fwd/lookup_table"):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope


def test_step_function_names_carry_the_scope_scheme():
    """JAX's persistent compile cache keys on the module's name and not on
    its metadata: the tag keeps a cache filled before the scopes existed
    from handing back scope-less executables."""
    tag = core_exec.SCOPE_SCHEME
    assert tag and tag.isalnum()
    main, startup, loss, feed = _convnet()
    lowered = _lowered_text(main, startup, loss, feed)
    assert f"module @jit_step_{tag} " in lowered.as_text()[:200]
    exe = fluid.Executor(mode="jit")
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    prepared = exe.prepare_steps(main, [feed], [loss], scope=scope)
    state = {n: scope.find_var(n) for n in prepared.carry_keys}
    text = prepared.fn.lower(state, prepared.stacked).as_text()
    assert f"module @jit_multi_{tag} " in text[:200]


def test_sharded_step_function_name_and_scopes():
    from paddle_tpu.parallel import ShardingPlan, make_mesh, shard_program_step

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    main, startup, loss = _mlp()
    exe = fluid.Executor(mode="jit")
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    seen = {}
    real_jit = core_exec.tpu_jit

    def spy(fn, **kw):
        seen["jitted"] = real_jit(fn, **kw)
        return seen["jitted"]

    core_exec.tpu_jit = spy
    try:
        _, state, feeds = shard_program_step(
            exe, main, _mlp_feed(), [loss], ShardingPlan(make_mesh(4)),
            scope=scope)
    finally:
        core_exec.tpu_jit = real_jit
    lowered = seen["jitted"].lower(state, feeds)
    assert (f"module @jit_sharded_step_{core_exec.SCOPE_SCHEME} "
            in lowered.as_text()[:200])
    text = lowered.as_text(debug_info=True)
    assert "/fwd/mul/" in text and "/bwd/mul_grad/" in text \
        and "/opt/sgd/" in text


# ------------------------------------------------------------ the counters
def test_pack_counters_against_a_hand_counted_pack():
    fam = REGISTRY.get("paddle_tpu_lod_pack_elements")
    real, padded = (fam.labels(kind=k) for k in ("real", "padded"))
    r0, p0 = real.value, padded.value
    seqs = [np.zeros((n, 1), "int64") for n in (2, 5, 3)]
    pack_sequences(seqs)                              # 3 x 5
    assert (real.value - r0, padded.value - p0) == (10, 15)
    pack_sequences(seqs, max_len=8)                   # 3 x 8
    assert (real.value - r0, padded.value - p0) == (20, 39)
    pack_sequences(seqs, pad_multiple=4)              # 3 x 8
    assert (real.value - r0, padded.value - p0) == (30, 63)
    assert REGISTRY.totals()["paddle_tpu_lod_pack_elements"] >= 93
