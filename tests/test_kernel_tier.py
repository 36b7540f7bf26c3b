"""The kernel tier's ONE routing rule (ops/pallas/__init__.py).

Covers: kernel_tier flag resolution (auto|pallas|jnp); every dispatch site
in paddle_tpu/ops/ obeying ``use_pallas(family, supported)`` through its
real op (no dispatch under jnp, counted interpret dispatches under pallas
on the CPU, a counted fallback to the twin's exact result on an
unsupported shape) and its Pallas route matching its jnp route; the
Executor's jit key and the execcache/kvstore fingerprints naming exactly
the flags a lowering reads; every AUTO_PALLAS family having an AOT compile
test; the kernel probe covering every family with a site; bundles an older
build published with a ``tune/`` dir still verifying, serving and being
collected; and the kernel-tier capability surfaces (ModelRegistry
manifests, InferenceEngine.stats()).
"""

import inspect
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.lod import LoDArray
from paddle_tpu.fluid import framework
from paddle_tpu.ops import pallas as tier

from op_test import OpTest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset():
    tier.reset_fallback_counts()
    yield
    fluid.set_flags({"kernel_tier": "auto"})
    tier.reset_fallback_counts()


def test_auto_resolves_jnp_on_cpu():
    fluid.set_flags({"kernel_tier": "auto"})
    assert tier.resolve_tier() == "jnp"  # the suite runs on CPU
    assert not tier.use_pallas("lstm")
    assert not tier.use_pallas("conv_bn")


def test_explicit_tiers():
    fluid.set_flags({"kernel_tier": "pallas"})
    assert tier.resolve_tier() == "pallas"
    assert tier.use_pallas("gru")          # pallas = everywhere, even gru
    fluid.set_flags({"kernel_tier": "jnp"})
    assert tier.resolve_tier() == "jnp"
    assert not tier.use_pallas("lstm")


def test_invalid_tier_raises():
    fluid.set_flags({"kernel_tier": "cuda"})
    with pytest.raises(ValueError, match="kernel_tier"):
        tier.resolve_tier()
    with pytest.raises(ValueError, match="kernel_tier"):
        tier.use_pallas("lstm")


def test_unsupported_shape_falls_back_with_counter_bump():
    fluid.set_flags({"kernel_tier": "pallas"})
    tier.reset_fallback_counts()
    assert not tier.use_pallas("conv_bn", supported=False)
    assert not tier.use_pallas("conv_bn", supported=False)
    assert not tier.use_pallas("optimizer", supported=False)
    assert tier.fallback_counts() == {"conv_bn": 2, "optimizer": 1}
    # a supported dispatch does not bump
    assert tier.use_pallas("conv_bn", supported=True)
    assert tier.fallback_counts()["conv_bn"] == 2
    # under a jnp tier nothing asks for pallas, so nothing is a fallback
    fluid.set_flags({"kernel_tier": "jnp"})
    tier.reset_fallback_counts()
    assert not tier.use_pallas("conv_bn", supported=False)
    assert tier.fallback_counts() == {}


_BUILD_A_CONVNET = """
import sys
import paddle_tpu
import paddle_tpu.fluid as fluid

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    img = fluid.layers.data("img", shape=[8, 8, 3])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    conv = fluid.layers.conv2d(img, 4, 3, padding=1, bias_attr=False,
                               data_format="NHWC")
    bn = fluid.layers.batch_norm(conv, act="relu", data_layout="NHWC")
    prob = fluid.layers.fc(bn, size=10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(prob, label))
    fluid.optimizer.Momentum(0.01, 0.9).minimize(loss, startup)
    # a linear-attention layer too: its delta_rule and causal_conv1d
    # kernels load at the first dispatch, not here
    tokens = fluid.layers.data("tokens", shape=[1, 64, 32],
                               append_batch_size=False)
    kda = fluid.layers.kda_attention(tokens, 2, 16, conv_size=4,
                                     chunk_size=16)
    # and a state-space layer: its ssd_scan kernels load the same way
    mamba = fluid.layers.mamba2_mixer(tokens, 2, 16, 1, 16, chunk_size=16)
print(sorted(m for m in sys.modules
             if m.startswith(("jax.experimental.pallas",
                              "paddle_tpu.ops.pallas."))))
"""


def test_set_up_stays_lazy_no_kernel_module_before_a_dispatch():
    """``import paddle_tpu`` and building a program load no kernel module
    and not ``jax.experimental.pallas`` (1.3 s on a CPU host): a kernel
    module is imported inside its dispatch function, so a cell that runs no
    such op never pays for it in its set-up. The guard for every kernel
    PR: a top-level ``from .pallas import <kernel>`` in an ops module
    fails here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [REPO] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    done = subprocess.run([sys.executable, "-c", _BUILD_A_CONVNET], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().splitlines()[-1] == "[]", done.stdout


# another legal value for each flag the Executor keys its jit cache on
_OTHER_VALUE = {"xla_compiler_options": "xla_tpu_scoped_vmem_limit_kib=65536",
                "bn_fusion_barrier": True, "bn_fusion_barrier_fwd": True,
                "bn_fusion_barrier_bwd": True, "conv_space_to_depth": True,
                "conv_1x1_grad_as_dot": True, "kernel_tier": "pallas"}


def _sources(*packages):
    """The text of every module under paddle_tpu/<package>/."""
    for sub in packages:
        for root, _dirs, files in os.walk(os.path.join(REPO, "paddle_tpu",
                                                       sub)):
            for name in files:
                if name.endswith(".py"):
                    with open(os.path.join(root, name)) as f:
                        yield f.read()


def _kernel_probe():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import kernel_probe
    finally:
        sys.path.pop(0)
    return kernel_probe


def test_jit_key_flags_are_the_seven_with_another_value_here():
    from paddle_tpu.core import executor as ex
    assert sorted(ex._JIT_KEY_FLAGS) == sorted(_OTHER_VALUE)


@pytest.mark.parametrize("flag", sorted(_OTHER_VALUE))
def test_every_jit_key_flag_is_defined_and_changes_the_key(flag):
    """A flag in the jit key stands for something: it is defined, a
    lowering under paddle_tpu/ops/ or core/ reads it, and another value is
    another key (so a flip retraces)."""
    from paddle_tpu.core import executor as ex
    from paddle_tpu.core.flags import flags, get_flag

    assert flag in ex._JIT_KEY_FLAGS
    assert flag in flags()
    assert any(f'get_flag("{flag}")' in src
               for src in _sources("ops", "core"))
    prev = get_flag(flag)
    assert prev != _OTHER_VALUE[flag]
    k1 = ex._jit_flag_key()
    fluid.set_flags({flag: _OTHER_VALUE[flag]})
    try:
        k2 = ex._jit_flag_key()
    finally:
        fluid.set_flags({flag: prev})
    assert k1 != k2, "a flip must retrace (distinct jit cache keys)"
    assert ex._jit_flag_key() == k1


def test_execcache_and_kvstore_fingerprints_name_exactly_the_jit_key_flags():
    from paddle_tpu.core.executor import _JIT_KEY_FLAGS
    from paddle_tpu.serving.execcache import fingerprint
    from paddle_tpu.serving.generate.kvstore import kv_fingerprint

    exe_fp = fingerprint("hash", "infer_b4",
                         {"x": np.zeros((4, 8), np.float32)}, ["y"])
    kv_fp = kv_fingerprint("hash", 2, 2, 8, 4, np.float32)
    assert sorted(exe_fp["flags"]) == sorted(kv_fp["flags"]) \
        == sorted(_JIT_KEY_FLAGS)


# ---------------------------------------------------------------------------
# every dispatch site, through its real op
# ---------------------------------------------------------------------------

def _run_op(op_type, inputs, outputs, attrs):
    """One op as a single-op Program through the jit Executor. ``outputs``
    maps slot -> lod level; returns the outputs as numpy arrays."""
    t = OpTest()
    t.op_type, t.inputs, t.attrs = op_type, inputs, attrs
    t.outputs = {slot: (None, None) if lod else None
                 for slot, lod in outputs.items()}
    main, _startup, feed = t._build()
    got = fluid.Executor(fluid.CPUPlace(), mode="jit").run(
        main, feed=feed, fetch_list=list(outputs))
    return [np.asarray(g.data if isinstance(g, LoDArray) else g)
            for g in got]


def _rnn_site(op_type, gates, off_attr):
    def run(supported):
        rng = np.random.RandomState(21)
        H, lod = 4, [[0, 3, 7]]
        x = rng.uniform(-0.5, 0.5, (7, gates * H)).astype("float32")
        w = rng.uniform(-0.3, 0.3, (H, gates * H)).astype("float32")
        outs = {"Hidden": 1, "Cell": 1} if op_type == "lstm" \
            else {"Hidden": 1}
        return _run_op(op_type, {"Input": (x, lod), "Weight": w}, outs,
                       {} if supported else {off_attr: "relu"})
    return run


def _ctc_site(supported):
    # a batch whose longest sequence is ONE step has no recurrence to fuse
    rng = np.random.RandomState(7)
    lod = [[0, 4, 9]] if supported else [[0, 1, 2]]
    n = lod[0][-1]
    logits = rng.uniform(-1, 1, (n, 5)).astype("float32")
    labels, label_lod = (np.array([[1], [2], [3], [4]], "int64"),
                         [[0, 2, 4]]) if supported else \
        (np.array([[1], [2]], "int64"), [[0, 1, 2]])
    return _run_op("warpctc", {"Logits": (logits, lod),
                               "Label": (labels, label_lod)},
                   {"Loss": 0}, {"blank": 0, "norm_by_times": False})


def _conv_bn_infer(filter_size, mode="jit"):
    framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[8, 8, 3])
        c = fluid.layers.conv2d(img, 6, filter_size,
                                padding=(filter_size - 1) // 2,
                                bias_attr=False, data_format="NHWC")
        b = fluid.layers.batch_norm(c, act="relu", data_layout="NHWC",
                                    is_test=True)
        assert fluid.fuse_conv_bn(main) == 1
    exe = fluid.Executor(fluid.CPUPlace(), mode=mode)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {"img": rng.normal(0, 1, (2, 8, 8, 3)).astype("float32")}
    return [np.asarray(exe.run(main, feed=feed, fetch_list=[b],
                               scope=scope)[0])]


def _fused_momentum_losses(sparse_only=False, mode="jit", steps=3):
    """A fused-Momentum loss trajectory. ``sparse_only``: the one parameter
    is an is_sparse embedding table, so the fused op has nothing for its
    arena (the site's unsupported case)."""
    framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    rng = np.random.RandomState(4)
    with fluid.program_guard(main, startup):
        y = fluid.layers.data("y", shape=[1])
        if sparse_only:
            ids = fluid.layers.data("ids", shape=[1], dtype="int64",
                                    lod_level=1)
            emb = fluid.layers.embedding(ids, size=[15, 1], is_sparse=True)
            pred = fluid.layers.sequence_pool(emb, "sum")
            feed = {"ids": [np.array([[0], [4], [4]], "int64"),
                            np.array([[2]], "int64")],
                    "y": rng.normal(0, 1, (2, 1)).astype("float32")}
        else:
            x = fluid.layers.data("x", shape=[6])
            pred = fluid.layers.fc(x, size=1)
            feed = {"x": rng.normal(0, 1, (4, 6)).astype("float32"),
                    "y": rng.normal(0, 1, (4, 1)).astype("float32")}
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_sub(pred, y)))
        fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 fused=True).minimize(loss, startup)
    exe = fluid.Executor(fluid.CPUPlace(), mode=mode)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    return [np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                               scope=scope)[0]) for _ in range(steps)]


def _embedding_sgd_site(supported):
    """The sgd op's SparseRows branch, called as the op calls it; a table
    of rank 3 is the shape the rowwise kernel does not take."""
    import jax.numpy as jnp
    from paddle_tpu.core.sparse import SparseRows
    from paddle_tpu.ops.optimizer_ops import _sgd_apply

    rng = np.random.RandomState(5)
    tail = (6,) if supported else (2, 3)
    p = jnp.asarray(rng.normal(0, 1, (12,) + tail).astype("float32"))
    g = SparseRows(jnp.asarray([0, 4, 9], jnp.int32),
                   jnp.asarray(rng.normal(0, 1, (3,) + tail)
                               .astype("float32")), 12)
    return [np.asarray(_sgd_apply(p, g, jnp.float32(0.1)))]


def _paged_attention_site(supported):
    from test_paged_attention_pallas import _case
    inputs, outputs, h = _case(dtype=np.float32 if supported
                               else np.float16)
    return _run_op("paged_attention", inputs, dict.fromkeys(outputs, 0),
                   {"num_heads": h})


def _attention_site(supported):
    # 128 positions, 128 lanes: one head of 128 is the kernels' shape, two
    # heads of 64 are the twin's
    rng = np.random.RandomState(1)
    q, k, v = (rng.randn(1, 128, 128).astype(np.float32) for _ in range(3))
    heads = 1 if supported else 2
    return _run_op("causal_self_attention", {"Q": q, "K": k, "V": v},
                   {"Out": 0, "LogSumExp": 0},
                   {"num_heads": heads, "num_kv_heads": heads,
                    "window": 0})[:1]


def _routed_experts_site(tokens, width):
    import jax.numpy as jnp
    rng = np.random.RandomState(2)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 4
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, tokens, 128],
                              append_batch_size=False)
        out, load, _aux = fluid.layers.routed_experts(
            x, 4, 2, width, row_buffer_factor=2.0)
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    router = main.global_block().all_parameters()[0].name
    scope.set(router, jnp.asarray(rng.randn(128, 4).astype(np.float32)))
    feed = {"x": np.abs(rng.randn(1, tokens, 128)).astype(np.float32)}
    return [np.asarray(o) for o in exe.run(main, feed=feed, scope=scope,
                                           fetch_list=[out, load])]


def _grouped_matmul_site(supported):
    # the experts' width: whole halves of a lane tile or not
    return _routed_experts_site(256, 128 if supported else 96)


def _moe_combine_site(supported):
    # the tokens: whole sublanes or not
    return _routed_experts_site(256 if supported else 252, 128)


def _delta_rule_site(supported):
    # 64 tokens, 128 lanes: one head of 128 is the kernels' shape, two heads
    # of 64 are the twin's
    rng = np.random.RandomState(3)
    q, k, v = (rng.randn(1, 64, 128).astype(np.float32) for _ in range(3))
    g = -0.3 * rng.rand(1, 64, 128).astype(np.float32)
    heads = 1 if supported else 2
    beta = rng.rand(1, 64, heads).astype(np.float32)
    return _run_op("gated_delta_rule",
                   {"Q": q, "K": k, "V": v, "G": g, "Beta": beta},
                   {"Out": 0, "States": 0},
                   {"num_heads": heads, "chunk_size": 32})


def _causal_conv1d_site(supported):
    # 32 tokens: two 128-lane columns are the kernels' shape, 192 channels
    # (a column and a half) the twin's
    rng = np.random.RandomState(6)
    channels = 256 if supported else 192
    x = rng.randn(2, 32, channels).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, (4, channels)).astype(np.float32)
    bias = rng.uniform(-0.5, 0.5, channels).astype(np.float32)
    return _run_op("causal_conv1d", {"X": x, "Filter": w, "Bias": bias},
                   {"Out": 0}, {})


def _ssd_scan_site(supported):
    # 32 tokens, two heads in one group of state 128: heads of 64 fill a
    # lane tile and are the kernels' shape, heads of 48 are the twin's
    rng = np.random.RandomState(8)
    heads, p = 2, 64 if supported else 48
    x = rng.randn(1, 32, heads * p).astype(np.float32)
    dt = 0.5 * rng.randn(1, 32, heads).astype(np.float32)
    b, c = (rng.randn(1, 32, 128).astype(np.float32) for _ in range(2))
    a_log = np.log(rng.uniform(1.0, 16.0, heads)).astype(np.float32)
    dt_bias = np.log(np.expm1(rng.uniform(0.001, 0.1, heads))) \
        .astype(np.float32)
    return _run_op("ssd_scan",
                   {"X": x, "Dt": dt, "B": b, "C": c, "ALog": a_log,
                    "DtBias": dt_bias, "D": np.ones(heads, np.float32)},
                   {"Out": 0, "States": 0},
                   {"num_heads": heads, "n_groups": 1, "chunk_size": 16})


# family -> (the op at a tiny shape, run(supported) -> outputs; Pallas
# dispatches one supported run traces)
SITES = {
    "lstm": (_rnn_site("lstm", 4, "gate_activation"), 1),
    "gru": (_rnn_site("gru", 3, "activation"), 1),
    "ctc": (_ctc_site, 1),
    "conv_bn": (lambda ok: _conv_bn_infer(3 if ok else 5), 1),
    "optimizer": (lambda ok: _fused_momentum_losses(sparse_only=not ok), 1),
    "embedding_sgd": (_embedding_sgd_site, 1),
    "paged_attention": (_paged_attention_site, 1),
    "attention": (_attention_site, 1),
    "grouped_matmul": (_grouped_matmul_site, 3),    # gate, up, down
    "moe_combine": (_moe_combine_site, 1),
    "delta_rule": (_delta_rule_site, 1),
    "causal_conv1d": (_causal_conv1d_site, 1),
    "ssd_scan": (_ssd_scan_site, 1),
}
# the other family's dispatches in each run of a site whose op holds two
# (routed_experts: its products and its combine), whatever the site's own
# family is asked to support
BESIDE = {"grouped_matmul": {"moe_combine": 1},
          "moe_combine": {"grouped_matmul": 3}}


def _interpreted_since(before):
    """{family: interpreted Pallas dispatches since ``before``}; nothing on
    the CPU may count as native."""
    out = {}
    for fam, modes in tier.dispatch_counts().items():
        was = before.get(fam, {"native": 0, "interpret": 0})
        assert modes["native"] == was["native"] == 0
        if modes["interpret"] != was["interpret"]:
            out[fam] = modes["interpret"] - was["interpret"]
    return out


def test_every_family_with_a_dispatch_site_is_in_sites():
    asked = {family for src in _sources("ops")
             for family in re.findall(r'use_pallas\(\s*"(\w+)"', src)}
    assert asked == set(SITES)
    assert tier.AUTO_PALLAS <= set(SITES)


@pytest.mark.parametrize("family", sorted(SITES))
def test_every_dispatch_site_obeys_the_one_rule(family):
    """What the benchmark's ``dispatches`` / ``fallbacks`` line and its
    check (d) rest on, for every family: under jnp nothing dispatches;
    under pallas on the CPU the family's kernels dispatch interpreted,
    under its own name; an unsupported shape gets the twin's exact result
    and ONE counted fallback."""
    run, dispatches = SITES[family]
    beside = BESIDE.get(family, {})
    start = tier.dispatch_counts()
    fluid.set_flags({"kernel_tier": "jnp"})
    twin, twin_unsupported = run(True), run(False)
    assert _interpreted_since(start) == {}
    assert tier.fallback_counts() == {}

    fluid.set_flags({"kernel_tier": "pallas"})
    kernel = run(True)
    assert _interpreted_since(start) == {family: dispatches, **beside}
    assert tier.fallback_counts() == {}
    for a, b in zip(kernel, twin):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)

    fell_back = run(False)
    assert _interpreted_since(start) == {
        family: dispatches, **{k: 2 * v for k, v in beside.items()}}
    assert tier.fallback_counts() == {family: 1}
    for a, b in zip(fell_back, twin_unsupported):
        if beside:      # the other family's kernel still ran: its roundings
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        else:
            assert np.array_equal(a, b)


def _rnn_route(cell):
    """The recurrence's compute function, eager and jitted, under the
    current tier, over ragged lengths (3 and 2, hidden 4)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.rnn_ops import _gru_compute, _lstm_scan

    rng = np.random.RandomState(2)
    b, L, H = 2, 3, 4
    gates = 4 if cell == "lstm" else 3
    lens = jnp.asarray(np.array([3, 2], "int32"))
    x = jnp.asarray(rng.normal(0, 0.5, (b, L, gates * H)).astype("float32"))
    w = jnp.asarray(rng.normal(0, 0.5, (H, gates * H)).astype("float32"))
    zeros = jnp.zeros((b, H), jnp.float32)

    def fn():           # a function of its own per route: jit keys on it
        if cell == "lstm":
            return _lstm_scan(x, lens, w, zeros, zeros, "sigmoid", "tanh",
                              "tanh")
        return _gru_compute(x, lens, w, None, None, {})
    return [np.asarray(o) for f in (fn, jax.jit(fn))
            for o in jax.tree_util.tree_leaves(f())]


def _sparse_sgd_losses(mode):
    framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data("ids", shape=[1], dtype="int64",
                                lod_level=1)
        emb = fluid.layers.embedding(ids, size=[15, 8], is_sparse=True)
        pred = fluid.layers.fc(fluid.layers.sequence_pool(emb, "sum"),
                               size=1)
        label = fluid.layers.data("y", shape=[1])
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_sub(pred, label)))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss, startup)
    exe = fluid.Executor(fluid.CPUPlace(), mode=mode)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(5)
    feed = {"ids": [np.array([[0], [4], [4], [9]], "int64"),
                    np.array([[2]], "int64"),
                    np.array([[14], [0]], "int64")],
            "y": rng.normal(0, 1, (3, 1)).astype("float32")}
    return [np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                               scope=scope)[0]) for _ in range(2)]


def _both_modes(fn):
    return lambda: [o for mode in ("eager", "jit") for o in fn(mode=mode)]


# family -> (run under the current tier -> arrays, rtol, atol). The seq
# kernels matmul in bf16 (the TPU recipe) against the f32 scan; the arena
# kernel is the same elementwise update in the same dtype: bitwise.
ROUTES = {
    "lstm": (lambda: _rnn_route("lstm"), 5e-3, 2e-3),
    "gru": (lambda: _rnn_route("gru"), 5e-3, 2e-3),
    "embedding_sgd": (_both_modes(_sparse_sgd_losses), 5e-4, 1e-6),
    "optimizer": (_both_modes(_fused_momentum_losses), 0, 0),
    "conv_bn": (_both_modes(lambda mode: _conv_bn_infer(3, mode)),
                2e-4, 1e-5),
}


@pytest.mark.parametrize("family", list(ROUTES))
def test_pallas_route_matches_jnp_route_through_the_real_op(family):
    run, rtol, atol = ROUTES[family]
    results = {}
    for name in ("jnp", "pallas"):
        fluid.set_flags({"kernel_tier": name})
        results[name] = run()
    assert tier.fallback_counts() == {}
    assert len(results["pallas"]) == len(results["jnp"])
    for got, want in zip(results["pallas"], results["jnp"]):
        np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)


# the entry points of each AUTO_PALLAS family's kernels (ops/pallas/):
# what tests/test_kernel_aot.py must compile for the described chip
AOT_ENTRY_POINTS = {
    "lstm": ("rnn", ("_lstm_seq_fwd_pallas", "_lstm_seq_bwd_pallas")),
    "attention": ("attention", ("attention_pallas", "attention_pallas_bwd")),
    "grouped_matmul": ("grouped_matmul", ("gmm", "gmm_t", "tgmm")),
    "moe_combine": ("moe_combine", ("combine",)),
    "delta_rule": ("delta_rule", ("delta_rule_fwd", "delta_rule_bwd")),
    "causal_conv1d": ("causal_conv1d", ("causal_conv1d_fwd",
                                        "causal_conv1d_bwd")),
    "ssd_scan": ("ssd_scan", ("ssd_scan_fwd", "ssd_scan_bwd")),
}


@pytest.mark.parametrize("family", sorted(tier.AUTO_PALLAS))
def test_every_auto_family_has_an_aot_compile(family):
    """ROADMAP A4's rule: a family the default path runs on a TPU compiles
    for the described chip in tests/test_kernel_aot.py, so a kernel Mosaic
    refuses fails here and not on the chip. A family admitted without an
    entry in the table above fails with a KeyError."""
    import importlib
    import test_kernel_aot

    module, entry_points = AOT_ENTRY_POINTS[family]
    kernels = importlib.import_module(f"paddle_tpu.ops.pallas.{module}")
    compiled = "".join(
        inspect.getsource(fn) for name, fn in vars(test_kernel_aot).items()
        if name.startswith("test_") and "compile" in name)
    for name in entry_points:
        assert callable(getattr(kernels, name))
        assert re.search(rf"\b{name}\b", compiled), \
            f"{family}: {name} has no AOT compile in test_kernel_aot.py"


def test_kernel_probe_tiny_has_a_record_for_every_family_with_a_site():
    kernel_probe = _kernel_probe()
    first = {}
    for name, family, build in kernel_probe.cases(True):
        first.setdefault(family, (name, build))
    assert set(first) == set(SITES)
    for family, (name, build) in first.items():
        rec = kernel_probe.probe(name, family, build(), repeats=1, inner=1)
        assert rec["family"] == family and rec["error"] is None, rec
        assert rec["lowered"] and rec["pallas_ms"] > 0 and rec["jnp_ms"] > 0
        assert rec["max_rel_err"] < 2e-2, rec


def test_measure_interleaves_windows_and_drops_raising_runner():
    kernel_probe = _kernel_probe()
    calls = []

    def mk(name):
        return lambda: calls.append(name)

    def boom():
        raise RuntimeError("cannot run")

    ms, dropped = kernel_probe.measure(
        {"a": mk("a"), "b": mk("b"), "c": boom}, repeats=2, inner=3)
    assert set(ms) == {"a", "b"}         # the raising runner cannot win
    assert dropped == {"c": "RuntimeError: cannot run"}
    # one untimed warmup each, then repeats windows of inner calls,
    # interleaved across the runners
    assert calls == ["a", "b"] + (["a"] * 3 + ["b"] * 3) * 2
    assert all(v >= 0.0 for v in ms.values())


def _save_tiny_model(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.fc(input=x, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [y], exe, main, scope=scope)
    return d


def test_registry_manifest_and_engine_stats_carry_kernel_tier(tmp_path):
    from paddle_tpu.serving import InferenceEngine, ModelRegistry

    model_dir = _save_tiny_model(tmp_path)
    reg = ModelRegistry(str(tmp_path / "registry"))
    v = reg.publish("m", model_dir)                  # defaults to active tier
    assert reg.manifest("m", v)["kernel_tier"] == tier.resolve_tier()
    v2 = reg.publish("m", model_dir, kernel_tier="pallas")
    assert reg.manifest("m", v2)["kernel_tier"] == "pallas"
    with pytest.raises(ValueError, match="kernel_tier"):
        reg.publish("m", model_dir, kernel_tier="cuda")
    # the failed publish must not leave a torn version dir that bricks
    # the next publish of that version number
    v3 = reg.publish("m", model_dir)
    assert v3 == v2 + 1
    # verify() still passes: the capability field rides the manifest but
    # the content hash covers the bundle files only
    reg.verify("m", v2)

    eng = InferenceEngine(model_dir, buckets="1,2")
    assert eng.stats()["kernel_tier"] == tier.resolve_tier()
    # warmup re-samples the tier: an engine warmed under jnp says so
    fluid.set_flags({"kernel_tier": "jnp"})
    eng.warmup()
    st = eng.stats()
    assert st["kernel_tier"] == "jnp"
    assert st["warmed"]


def _add_tune_dir(version_dir):
    """What a build before PR 30 left in a version it published with
    ``tune=True``: a kernel-tuning table under tune/, listed with its
    sha256 under ``tune_files`` in the manifest."""
    import hashlib
    os.makedirs(os.path.join(version_dir, "tune"))
    rel = "tune/table-0123456789abcdef.jtune"
    blob = json.dumps({"schema": "pdtpu-tune-table-v1", "entries": []}) \
        .encode()
    data = b"PDTPUTUNE1\n" + hashlib.sha256(blob).hexdigest().encode() \
        + b"\n" + blob
    with open(os.path.join(version_dir, rel), "wb") as f:
        f.write(data)
    manifest = os.path.join(version_dir, "VERSION.json")
    with open(manifest) as f:
        m = json.load(f)
    m["tune_files"] = {rel: hashlib.sha256(data).hexdigest()}
    with open(manifest, "w") as f:
        json.dump(m, f, indent=1, sort_keys=True)
    return os.path.join(version_dir, rel)


def test_older_builds_tuned_bundle_verifies_serves_and_is_collected(
        tmp_path):
    from paddle_tpu.serving import InferenceEngine, ModelRegistry

    model_dir = _save_tiny_model(tmp_path)
    reg = ModelRegistry(str(tmp_path / "registry"))
    plain = reg.publish("m", model_dir)
    tuned = reg.publish("m", model_dir)
    tuned_dir, _ = reg.resolve("m", tuned)
    _add_tune_dir(tuned_dir)
    assert "tune_files" in reg.verify("m", tuned)
    x = np.random.RandomState(0).normal(0, 1, (2, 4)).astype("float32")
    outs = []
    for v in (plain, tuned):
        eng = InferenceEngine(reg.resolve("m", v)[0], buckets="2")
        eng.warmup()
        outs.append(eng.infer({"x": x}))
        assert "tune_digest" not in eng.stats()     # it reports no table
    for a, b in zip(*outs):
        assert np.array_equal(a, b)
    for _ in range(2):
        reg.publish("m", model_dir)
    assert tuned in reg.gc("m", keep_latest=1)
    assert not os.path.exists(tuned_dir)            # tune/ went with it


def test_older_builds_tuned_generative_bundle_serves_the_same_tokens(
        tmp_path):
    from paddle_tpu.serving import GenerationEngine, ModelRegistry
    from paddle_tpu.testing.models import export_tiny_lm

    lm = tmp_path / "lm"
    export_tiny_lm(str(lm), seed=13)
    reg = ModelRegistry(str(tmp_path / "registry"))
    plain = reg.publish("lm", str(lm), model_kind="generative")
    tuned = reg.publish("lm", str(lm), model_kind="generative")
    _add_tune_dir(reg.resolve("lm", tuned)[0])
    reg.verify("lm", tuned)

    def tokens(version):
        engine = GenerationEngine(reg.resolve("lm", version)[0], max_seqs=2,
                                  max_len=48)
        engine.warmup()
        assert "tune_digest" not in engine.stats()
        handle, toks, finished = engine.start([3, 5, 7], 8,
                                              {"mode": "greedy"})
        out = list(toks)
        while not finished:
            for h, t, f in engine.step():
                if h is handle:
                    out += t
                    finished = f
        return out

    assert tokens(tuned) == tokens(plain)


def test_a_bit_flipped_in_a_listed_tune_file_fails_verify(tmp_path):
    from paddle_tpu.serving import ModelRegistry

    reg = ModelRegistry(str(tmp_path / "registry"))
    v = reg.publish("m", _save_tiny_model(tmp_path))
    table = _add_tune_dir(reg.resolve("m", v)[0])
    reg.verify("m", v)
    with open(table, "r+b") as f:
        f.seek(20)
        byte = f.read(1)
        f.seek(20)
        f.write(bytes([byte[0] ^ 1]))
    with pytest.raises(ValueError, match="corrupt.*tune/"):
        reg.verify("m", v)
    os.unlink(table)
    with pytest.raises(ValueError, match="torn.*tune/"):
        reg.verify("m", v)


def test_profiler_spans_distinguish_tiers():
    """Dispatch sites wrap in pallas/<kernel> vs jnp/<kernel> spans
    (kind="kernel"), so chrome traces attribute tier time per op."""
    from paddle_tpu.core import profiler
    from paddle_tpu.ops.pallas import kernel_span

    profiler.enable_profiler()
    try:
        with kernel_span("pallas", "conv_bn"):
            pass
        with kernel_span("jnp", "optimizer"):
            pass
        evs = profiler.events()
    finally:
        profiler.disable_profiler(sorted_key=None)
    names = {(kind, name) for kind, name, *_ in evs}
    assert ("kernel", "pallas/conv_bn") in names
    assert ("kernel", "jnp/optimizer") in names


def test_lstm_op_runs_under_pallas_tier():
    """kernel_tier=pallas engages the whole-recurrence LSTM kernel through
    the op layer (interpret on CPU) and matches the jnp tier."""
    def run(tier_name):
        fluid.set_flags({"kernel_tier": tier_name})
        from paddle_tpu.fluid import framework
        framework.reset_unique_name()
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[1], dtype="int64", lod_level=1)
            e = fluid.layers.embedding(x, size=[10, 8])
            proj = fluid.layers.fc(e, size=8 * 4)
            h, _ = fluid.layers.dynamic_lstm(proj, size=8 * 4)
            pred = fluid.layers.fc(fluid.layers.sequence_last_step(h),
                                   size=1)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(2)
        seqs = [rng.randint(0, 10, (ln, 1)).astype("int64")
                for ln in (3, 5, 2)]
        return exe.run(main, feed={"x": seqs}, fetch_list=[pred],
                       scope=scope)[0]

    base = run("jnp")
    pallas = run("pallas")
    np.testing.assert_allclose(pallas, base, rtol=5e-3, atol=1e-4)
