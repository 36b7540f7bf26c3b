"""The gated short-convolution mixer (``gated_short_conv``: its jnp twin and
the gated form of the ``causal_conv1d`` Pallas family, interpreted here),
grouped-query attention with QK-norm at heads of 64 (the values padded to
128 lanes for the kernels), sigmoid top-4 routing with a selection bias and
no shared expert, and the LFM2-MoE layer stack, against the plain reference
(paddle_tpu/testing/reference/lfm2_moe.py) at a tiny size on the CPU with
seeded random weights: hidden 64, attention 4 / 2 heads of 16, 3 taps, 32
experts top 4 (8 held in the share), the published layer pattern from layer
1 (conv + dense, attention + experts, then conv + experts), 40 tokens."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.testing.models import build_lfm2_moe_lm
from paddle_tpu.testing.reference import lfm2_moe as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 40
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                  "conv"] * 9 + ["full_attention", "conv"]
TINY = dict(
    hidden_size=64, norm_eps=1e-5, num_hidden_layers=4, first_layer=1,
    layer_types=LAYER_TYPES, num_dense_layers=2, num_attention_heads=4,
    num_key_value_heads=2, conv_L_cache=3, intermediate_size=96,
    moe_intermediate_size=32, num_experts_routed=32, num_experts=32,
    expert_offset=0, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=1, use_expert_bias=True,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    vocab_size=96, init_std=0.3, selection_bias_init_std=0.1,
    bias_update_rate=0.001, balance_loss_coef=1e-4, row_buffer_factor=2.0)
# the chip's share: experts 8..15 of 32
SHARE = dict(TINY, num_experts=8, expert_offset=8, row_buffer_factor=4.0)
TOLERANCE = 1e-4            # float32 on the CPU: roundings only
# embedding; norm, 3 conv + norm, 3 dense; norm, 6 attention + norm, 5
# sparse; twice norm, 3 conv + norm, 5 sparse; final norm, head
N_PARAMS = 1 + 8 + 13 + 2 * 10 + 2


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


@pytest.fixture
def tier():
    """Set the kernel tier for one test and put it back after."""
    def set_tier(name):
        fluid.set_flags({"kernel_tier": name})
    yield set_tier
    fluid.set_flags({"kernel_tier": "auto"})


def _dispatches(family):
    from paddle_tpu.ops.pallas import dispatch_counts
    return dispatch_counts().get(family, {}).get("interpret", 0)


def _layer_run(build, width, out_width, seed=5, t=T):
    """One layer over a fed ``x`` [1, t, width] with the loss sum(out * w):
    (out, dx, {param: grad}, [param values] in creation order)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, t, width],
                              append_batch_size=False)
        x.stop_gradient = False
        out = build(x)
        w = fluid.layers.data("w", shape=[1, t, out_width],
                              append_batch_size=False)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, w))
    pairs = fluid.backward.append_backward(loss)
    startup.random_seed = seed
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    params = main.global_block().all_parameters()
    weights = [np.asarray(scope.find_var(p.name)) for p in params]
    rng = np.random.RandomState(seed)
    xv = rng.randn(1, t, width).astype(np.float32)
    wv = rng.randn(1, t, out_width).astype(np.float32)
    got = exe.run(main, feed={"x": xv, "w": wv},
                  fetch_list=[out, framework.grad_var_name(x.name)]
                  + [g for _, g in pairs], scope=scope)
    grads = dict(zip([p.name for p, _ in pairs], got[2:]))
    return (got[0][0], got[1][0], [grads[p.name] for p in params], weights,
            xv[0], wv[0], main)


def _against(f, weights, xv, wv):
    """The reference ``f(weights, x)``, its input's and weights' gradients
    for the loss sum(out * w), at ``highest``."""
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(f, [jnp.asarray(a) for a in weights],
                            jnp.asarray(xv))
        dw, dx = vjp(jnp.asarray(wv))
    return want, dx, dw


# ------------------------------------------------------ the conv mixer
@pytest.mark.parametrize("kernel_tier", ["jnp", "pallas"])
def test_short_conv_mixer_matches_the_reference_with_gradients(tier,
                                                               kernel_tier):
    """``short_conv_mixer``: W_in, ``gated_short_conv`` and W_out, forward,
    the input's gradient and each weight's against the reference's
    ``conv_mixer``; on the Pallas tier the gated kernels (interpreted) do
    the convolution both ways."""
    tier(kernel_tier)
    before = _dispatches("causal_conv1d")
    got, dx, grads, weights, xv, wv, main = _layer_run(
        lambda x: fluid.layers.short_conv_mixer(x, 3), 128, 128, t=64)
    assert [op.type for op in main.global_block().ops][:3] == [
        "mul", "gated_short_conv", "mul"]
    assert [w.shape for w in weights] == [(128, 384), (3, 128), (128, 128)]
    assert _dispatches("causal_conv1d") - before == (
        2 if kernel_tier == "pallas" else 0)

    def f(w, x):
        layer = dict(zip(ref.CONV, w))
        return ref.conv_mixer(TINY, layer, x, ref._Precision("exact"), None)

    want, want_dx, want_dw = _against(f, weights, xv, wv)
    assert _err(got, want) < TOLERANCE
    assert _err(dx, want_dx) < TOLERANCE
    for a, b in zip(grads, want_dw):
        assert a.dtype == np.float32 and _err(a, b) < TOLERANCE


@pytest.mark.parametrize("dtype,shape", [
    (jnp.float32, (2, 64, 256)), (jnp.bfloat16, (1, 256, 384)),
    (jnp.float32, (1, 40, 128))], ids=["f32_b2", "bf16", "f32_short"])
def test_the_gated_kernels_agree_with_the_twin(dtype, shape):
    """The two kernels against the formula and ``jax.vjp`` of it: batch 2
    (the filter's gradient summed over it), blocks of 256 and 128 channels,
    a length of less than one piece; ``X@GRAD`` in X's type and [B | C |
    x] layout, ``Filter@GRAD`` float32."""
    from paddle_tpu.ops import linear_attention_ops as la
    from paddle_tpu.ops.pallas import causal_conv1d as cc

    b, t, h = shape
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, t, 3 * h), dtype)
    w = jnp.asarray(rng.randn(3, h) * 0.5, jnp.float32)
    g = jnp.asarray(rng.randn(b, t, h), dtype)
    assert cc.gated_supported(x, w)
    assert not cc.gated_supported(x[..., :-128], w)
    want, vjp = jax.vjp(lambda a, f: la._gated_short_conv(None, a, f), x, w)
    want_dx, want_dw = vjp(g)
    got = cc.gated_short_conv_fwd(x, w)
    dx, dw = cc.gated_short_conv_bwd(x, w, g)
    assert (got.dtype, dx.dtype, dw.dtype) == (dtype, dtype, jnp.float32)
    assert dx.shape == x.shape and dw.shape == w.shape
    # float32: roundings; bfloat16: one rounding of the output
    limit = 1e-6 if dtype == jnp.float32 else 1e-2
    assert _err(got, want) < limit
    assert _err(dx, want_dx) < limit
    assert _err(dw, want_dw) < 1e-5


def test_the_mixer_is_the_gated_formula_by_hand():
    """The op against the formula written out token by token with numpy:
    the LAST tap on the current token, zeros before the first, B first in
    the input, no activation."""
    from paddle_tpu.ops import linear_attention_ops as la

    rng = np.random.RandomState(1)
    h, t = 4, 6
    x = rng.randn(1, t, 3 * h).astype(np.float32)
    w = rng.randn(3, h).astype(np.float32)
    b, c, v = x[0, :, :h], x[0, :, h:2 * h], x[0, :, 2 * h:]
    u = b * v
    want = np.zeros((t, h), np.float32)
    for i in range(t):
        for s in range(3):
            if i - s >= 0:
                want[i] += w[2 - s] * u[i - s]
    want = c * want
    got = np.asarray(la._gated_short_conv(None, jnp.asarray(x),
                                          jnp.asarray(w)))[0]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# the SiLU form at cells 6 and 7's shapes and the attention op at
# cells 5 and 8's, both ways on the kernel route: sha256 of the jaxpr (the
# kernels' own bodies, grids and block specs among it). The Mosaic payload
# also carries source locations, which hold the checkout's path and are not
# compared.
KERNEL_ROUTES = {
    "conv_kimi": ("ec36312e1bf28cbc", "2db8f42c75a74f0b"),
    "conv_nemotron": ("29d36922d4c2aa8f", "a31638ad80ac0c26"),
    "attention_mellum2_w1024": ("994f582f52a391d2", "5fe1f1bc9fb8b845"),
    "attention_mellum2_w0": ("051ef76fed95fb35", "1ed54c1d2ff565fc"),
    "attention_joyai": ("b333c82bee9d2002", "da62a04d5492bd16"),
}


class _Ctx:
    def __init__(self, attrs, env):
        self.attrs, self.env, self.out = attrs, env, {}

    def input(self, slot):
        return self.env[slot]

    def has_input(self, slot):
        return slot in self.env

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def set_output(self, slot, value):
        self.out[slot] = value


def _hash(x):
    return hashlib.sha256(str(x).encode()).hexdigest()[:16]


def _conv_route(channels, bias):
    from paddle_tpu.ops import linear_attention_ops as la
    s = jax.ShapeDtypeStruct
    x, w = s((1, 4096, channels), jnp.bfloat16), s((4, channels), jnp.float32)
    args = (x, w) + ((s((channels,), jnp.float32),) if bias else ())
    fwd = jax.make_jaxpr(lambda *a: la._conv_forward(None, *a))(*args)
    bwd = jax.make_jaxpr(lambda a, d: la._conv_backward(None, list(a), d))(
        args, x)
    return fwd, bwd


def _attention_route(q, k, v, attrs):
    from paddle_tpu.ops import attention_ops as ao

    def fwd(q, k, v):
        c = _Ctx(attrs, dict(Q=q, K=k, V=v))
        ao.causal_self_attention(c)
        return c.out

    kept = jax.eval_shape(fwd, q, k, v)

    def bwd(q, k, v, o, lse, d):
        c = _Ctx(attrs, {"Q": q, "K": k, "V": v, "Out": o, "LogSumExp": lse,
                         "Out@GRAD": d})
        ao.causal_self_attention_grad(c)
        return c.out

    return (jax.make_jaxpr(fwd)(q, k, v),
            jax.make_jaxpr(bwd)(q, k, v, kept["Out"], kept["LogSumExp"],
                                kept["Out"]))


@pytest.mark.parametrize("route", sorted(KERNEL_ROUTES))
def test_the_accepted_cells_kernel_routes_trace_what_they_traced(tier,
                                                                 route):
    tier("pallas")
    s = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    if route == "conv_kimi":
        traced = _conv_route(4096, False)
    elif route == "conv_nemotron":
        traced = _conv_route(6144, True)
    elif route.startswith("attention_mellum2"):
        window = int(route.rsplit("_w", 1)[1])
        traced = _attention_route(
            s((1, 8192, 4096), bf), s((1, 8192, 512), bf),
            s((1, 8192, 512), bf),
            dict(num_heads=32, num_kv_heads=4, window=window))
    else:
        traced = _attention_route(
            s((1, 4096, 6144), bf), s((1, 4096, 6144), bf),
            s((1, 4096, 4096), bf), dict(num_heads=32))
    assert all("pallas_call" in str(j) for j in traced)
    assert tuple(_hash(j) for j in traced) == KERNEL_ROUTES[route]


# ------------------------------------------- attention at heads of 64
def _gqa(x, qk_norm=True):
    return fluid.layers.grouped_query_attention(
        x, 4, 2, 64, rope_theta=1e6, qk_norm=qk_norm, epsilon=1e-5,
        param_attr=fluid.ParamAttr(
            initializer=fluid.initializer.Normal(0.0, 0.1)))


@pytest.mark.parametrize("kernel_tier", ["jnp", "pallas"])
def test_qk_normed_attention_at_heads_of_64_matches_the_reference(
        tier, kernel_tier):
    """``grouped_query_attention(qk_norm=True)`` at 4 / 2 heads of 64 over
    128 tokens: forward, the input's gradient and each weight's (the two
    norms' scales among them) against the reference's ``attention``; on
    the Pallas tier the banded kernels take it, values padded to 128
    lanes and cut back."""
    cfg = dict(TINY, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=2)
    tier(kernel_tier)
    before = _dispatches("attention")
    got, dx, grads, weights, xv, wv, main = _layer_run(_gqa, 256, 256,
                                                       t=128)
    assert _dispatches("attention") - before == (
        2 if kernel_tier == "pallas" else 0)
    assert [w.shape for w in weights] == [
        (256, 256), (256, 128), (256, 128), (64,), (64,), (256, 256)]

    def f(w, x):
        layer = dict(zip(ref.ATTENTION, w))
        return ref.attention(cfg, layer, x, ref._Precision("exact"), None)

    want, want_dx, want_dw = _against(f, weights, xv, wv)
    assert _err(got, want) < TOLERANCE
    assert _err(dx, want_dx) < TOLERANCE
    for a, b in zip(grads, want_dw):
        assert _err(a, b) < TOLERANCE
    layer = dict(zip(ref.ATTENTION, [jnp.asarray(a) for a in weights]))
    left_out = ref.attention(cfg, layer, jnp.asarray(xv),
                             ref._Precision("exact"), "qk_norm_left_out")
    assert _err(got, left_out) > 100 * TOLERANCE


def test_values_of_64_lanes_reach_the_kernels():
    """The route asks the kernels about the values as the op hands them
    over, padded to 128 lanes: heads of 64 are the kernels', and the
    padding is exact (zeros add nothing; the output is cut back)."""
    from paddle_tpu.ops import attention_ops as ao
    from paddle_tpu.ops.pallas import attention as att

    s = jax.ShapeDtypeStruct
    q = s((1, 8192, 32 * 64), jnp.bfloat16)
    kv = s((1, 8192, 8 * 64), jnp.bfloat16)
    wide_q = s((1, 8192, 32 * 128), jnp.bfloat16)
    assert not att.attention_supported(wide_q, 32, 8, 0, kv)
    assert ao._wide(kv, 8).shape == (1, 8192, 8 * 128)
    assert ao._wide(q, 32).shape == wide_q.shape
    assert att.attention_supported(wide_q, 32, 8, 0, ao._wide(kv, 8))
    v = jnp.asarray(np.random.RandomState(0).randn(1, 4, 2 * 64),
                    jnp.float32)
    padded = ao._lane_padded(v, 2)
    assert padded.shape == (1, 4, 256)
    np.testing.assert_array_equal(np.asarray(padded)[..., 64:128], 0.0)
    np.testing.assert_array_equal(
        np.asarray(ao._cut_heads(padded, v, 2)), np.asarray(v))


def test_qk_norm_is_named_and_left_out_by_default():
    """QK-norm's ops (and their grad ops) carry ``op_namescope`` qk_norm;
    without the option the layer adds no norm."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", shape=[1, 8, 256], append_batch_size=False)
        x.stop_gradient = False
        loss = fluid.layers.reduce_sum(_gqa(x))
    fluid.backward.append_backward(loss)
    tagged = [op.type for op in main.global_block().ops
              if op.attrs.get("op_namescope") == "qk_norm"]
    assert tagged[:6] == ["reshape", "rms_norm", "reshape"] * 2
    assert tagged.count("rms_norm_grad") == 2
    plain = fluid.Program()
    with fluid.program_guard(plain, fluid.Program()):
        x = fluid.layers.data("x", shape=[1, 8, 256], append_batch_size=False)
        _gqa(x, qk_norm=False)
    assert "rms_norm" not in [op.type for op in plain.global_block().ops]


# ------------------------------------------------------------ routing
def test_routing_is_top_4_by_score_and_bias_weighted_by_score_alone():
    """``routed_experts`` with sigmoid scores and a seeded selection bias,
    every expert held: the top 4 of score + bias, weights the chosen scores
    renormalised (times 1), no shared expert; weighting by score + bias or
    not renormalising are further than the tolerance."""
    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=16,
               num_experts_routed=16, num_experts=16)
    rng = np.random.RandomState(2)
    layer = {"router": rng.randn(32, 16).astype(np.float32) * 0.3,
             "bias": rng.randn(16).astype(np.float32) * 0.3}
    for n in ("e_gate", "e_up"):
        layer[n] = rng.randn(16, 32, 16).astype(np.float32) * 0.3
    layer["e_down"] = rng.randn(16, 16, 32).astype(np.float32) * 0.3
    x = rng.randn(1, T, 32).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", shape=[1, T, 32], append_batch_size=False)
        out, load, _ = fluid.layers.routed_experts(
            xv, num_experts=16, top_k=4, expert_width=16,
            row_buffer_factor=4.0, scoring_func="sigmoid",
            selection_bias=True)
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    for p, v in zip(main.global_block().all_parameters(),
                    [layer[n] for n in ref.SPARSE]):
        scope.set(p.name, jnp.asarray(v))
    got, got_load = exe.run(main, feed={"x": x}, fetch_list=[out, load],
                            scope=scope)
    pr = ref._Precision("exact")
    layer = {k: jnp.asarray(v) for k, v in layer.items()}
    want, _, want_load, _, _ = ref.experts(cfg, layer, jnp.asarray(x[0]), pr,
                                           None)
    assert _err(got[0], want) < TOLERANCE
    np.testing.assert_array_equal(got_load, np.asarray(want_load))
    for mutation in ("bias_in_weights", "not_renormalised"):
        other = ref.experts(cfg, layer, jnp.asarray(x[0]), pr, mutation)[0]
        assert _err(got[0], other) > 100 * TOLERANCE, mutation


# ------------------------------------------------------- the whole model
def _feeds(seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, TINY["vocab_size"], T + 1).astype(np.int64)
    return {"tokens": ids[None, :T, None], "labels": ids[None, 1:, None]}


def _system(cfg, seed=3):
    """One step's loss, logits and every gradient from the program's own
    seeded start-up weights, and those weights in creation order."""
    main, startup, loss, logits, loads = build_lfm2_moe_lm(cfg, T)
    startup.random_seed = main.random_seed = seed
    pairs = fluid.backward.append_backward(loss)
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    params = main.global_block().all_parameters()
    weights = [np.asarray(scope.find_var(p.name)) for p in params]
    feed = _feeds()
    fetch = [loss, logits] + loads
    out = exe.run(main, feed=feed, fetch_list=fetch + [g for _, g in pairs],
                  scope=scope)
    n = len(fetch)
    return dict(loss=float(np.asarray(out[0]).reshape(())),
                logits=np.asarray(out[1])[0],
                loads=[np.asarray(x) for x in out[2:n]],
                grads=dict(zip([p.name for p, _ in pairs], out[n:])),
                params=params, weights=weights, main=main,
                feed={k: v[0, :, 0] for k, v in feed.items()})


def _ref(cfg, got, **kw):
    f = got["feed"]
    return ref.run(cfg, got["weights"], f["tokens"], f["labels"], **kw)


@pytest.fixture(scope="module")
def share():
    return _system(SHARE)


@pytest.mark.parametrize("cfg", [TINY, SHARE], ids=["whole", "share"])
def test_program_matches_reference_loss_logits_and_every_gradient(cfg):
    """The loss, the logits, every held expert's rows and every gradient;
    the selection biases have none in the program and a zero one in the
    reference."""
    got = _system(cfg)
    loss, logits, loads, _, grads, _ = _ref(cfg, got, with_grads=True)
    assert abs(got["loss"] - float(loss)) < TOLERANCE * float(loss)
    assert _err(got["logits"], logits) < TOLERANCE
    assert len(loads) == len(got["loads"]) == 3
    for a, b in zip(got["loads"], loads):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert len(got["params"]) == len(grads) == N_PARAMS
    for p, b in zip(got["params"], grads):
        if p.trainable:
            a = got["grads"][p.name]
            assert a.shape == b.shape and _err(a, b) < TOLERANCE, p.name
        else:
            assert p.name not in got["grads"] and not np.asarray(b).any()


def test_the_program_reads_the_layer_kinds_and_adds_no_shared_expert(share):
    """From layer 1: conv + dense, attention + experts, conv + experts; a
    routed layer is ``routed_experts`` and its bias update alone (no shared
    expert's gated MLP: one ``swish``, the dense layer's)."""
    assert ref.layer_kinds(SHARE) == [
        ("conv", "dense"), ("full_attention", "sparse"), ("conv", "sparse"),
        ("conv", "sparse")]
    ops = [op.type for op in share["main"].global_block().ops]
    assert ops.count("gated_short_conv") == 3
    assert ops.count("causal_self_attention") == 1
    assert ops.count("routed_experts") == 3
    assert ops.count("expert_bias_update") == 3
    assert ops.count("swish") == 1


@pytest.mark.parametrize("mutation", ref.MUTATIONS)
def test_a_mutated_reference_is_further_than_the_tolerance(share, mutation):
    """Each piece of the mathematics is visible: the reference with the
    piece broken is further from the system, in the logits or the loss,
    than the tolerance allows."""
    loss, logits = _ref(SHARE, share, mutate=mutation)[:2]
    worst = max(_err(share["logits"], logits),
                abs(share["loss"] - float(loss)) / share["loss"])
    assert worst > 10 * TOLERANCE, mutation


def test_eight_shares_add_up_to_the_uncut_layer():
    """64 experts top 4 as the configuration routes: eight
    ``routed_experts`` ops holding experts 0-7, 8-15, ..., 56-63 give parts
    that sum to the reference's uncut layer (no shared expert to count
    once); each share's loads are its slice of the whole's."""
    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=16,
               num_experts_routed=64, num_experts=64)
    rng = np.random.RandomState(7)
    h, f = 32, 16
    layer = {"router": rng.randn(h, 64).astype(np.float32) * 0.3,
             "bias": rng.randn(64).astype(np.float32) * 0.1}
    for n in ("e_gate", "e_up"):
        layer[n] = rng.randn(64, h, f).astype(np.float32) * 0.3
    layer["e_down"] = rng.randn(64, f, h).astype(np.float32) * 0.3
    x = rng.randn(1, T, h).astype(np.float32)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", shape=[1, T, h], append_batch_size=False)
        parts = [fluid.layers.routed_experts(
            xv, num_experts=64, top_k=4, expert_width=f, held_experts=8,
            expert_offset=o, row_buffer_factor=8.0,
            scoring_func="sigmoid", selection_bias=True)[:2]
            for o in range(0, 64, 8)]
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    values = []
    for o in range(0, 64, 8):
        values += [layer["router"], layer["bias"]] + [
            layer[n][o:o + 8] for n in ("e_gate", "e_up", "e_down")]
    for p, v in zip(main.global_block().all_parameters(), values):
        assert tuple(scope.find_var(p.name).shape) == v.shape, p.name
        scope.set(p.name, jnp.asarray(v))
    out = exe.run(main, feed={"x": x}, fetch_list=[v for p in parts
                                                   for v in p], scope=scope)
    whole, _, load, _, _ = ref.experts(
        cfg, {k: jnp.asarray(v) for k, v in layer.items()},
        jnp.asarray(x[0]), ref._Precision("exact"), None)
    total = sum(out[i][0] for i in range(0, 16, 2))
    assert _err(total, whole) < TOLERANCE
    np.testing.assert_array_equal(
        np.concatenate([out[i] for i in range(1, 16, 2)]), np.asarray(load))
    assert _err(total - out[0][0], whole) > 0.01      # one share missing


# The tiny programs of cells 5-8 (their rehearsal tests' sizes) as they
# were before the LFM2 configuration came: sha256 of the ProgramDesc
# (``to_dict``) and of the train step's jaxpr under AMP. Equal hashes mean
# that the attention op's padded values, the sparse part without a shared
# expert and the new layers traced nothing new there. A change to one of
# these programs on purpose writes its new hashes.
CELL_PROGRAMS = {
    "mellum2_12b_ep8.staged_len8192_b1":
        ("test_perf_rehearsal_mellum2", "02ebb3e08a23c6f2",
         "e97741f74d9b01be"),
    "kimi_linear_48b_ep32.staged_len4096_b1":
        ("test_perf_rehearsal_kimi_linear", "af2d75476fd3ca19",
         "cc87e806c0f4002c"),
    "nemotron3_nano_30b_ep16.staged_len4096_b1":
        ("test_perf_rehearsal_nemotron_h", "35840fc5a4036b87",
         "25025d552f13bbf3"),
    "joyai_llm_flash_ep32.staged_len4096_b1":
        ("test_perf_rehearsal_joyai", "b1f23774c9f31aac",
         "32a9d39b23c1ef66"),
}


@pytest.mark.parametrize("cell", sorted(CELL_PROGRAMS))
def test_the_other_model_cells_trace_what_they_traced(cell):
    import importlib

    from paddle_tpu.obs.perf import program_jaxpr

    module, desc_hash, jaxpr_hash = CELL_PROGRAMS[cell]
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tests", "perf_harness"))
    try:
        from benchmark import harness
        tiny = importlib.import_module(module)
    finally:
        sys.path.remove(ROOT)
        sys.path.remove(os.path.join(ROOT, "tests", "perf_harness"))
    counters = dict(framework._name_counters)
    framework.reset_unique_name()
    try:
        c = harness.load_cell(harness.load_manifest(), cell, tiny.TINY_CFG,
                              tiny.TINY_TRAFFIC)
        main, startup, loss, probe = c.model.build(c.cfg)
        desc = json.dumps(main.to_dict(), sort_keys=True, default=str)
    finally:
        framework._name_counters.clear()
        framework._name_counters.update(counters)
    exe, scope = fluid.Executor(mode="jit", amp=True), fluid.Scope()
    exe.run(startup, scope=scope)
    tok = np.zeros((1, int(tiny.TINY_TRAFFIC["length"]), 1), np.int64)
    feed = {"tokens": tok, "labels": tok}
    if "joyai" in cell:
        feed["mtp_labels"] = tok
    jaxpr = str(program_jaxpr(main, feed, [loss, probe], executor=exe,
                              scope=scope))
    assert _hash(desc) == desc_hash
    assert _hash(jaxpr) == jaxpr_hash


# ------------------------------------------------------------ serving
def _bundle(dirname, mixer):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data("tokens", shape=[1, 8, 1], dtype="int64",
                                   append_batch_size=False)
        x = fluid.layers.embedding(tokens, size=[32, 256])
        x = fluid.layers.elementwise_add(x, mixer(x))
        logits = fluid.layers.fc(x, size=32, num_flatten_dims=2)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    fluid.io.save_inference_model(str(dirname), ["tokens"], [logits], exe,
                                  main, scope=scope)
    return str(dirname)


def test_generation_engine_refuses_the_gated_conv_and_qk_norm(tmp_path):
    """The conv mixer's state (its last taps of B * x) and QK-norm are
    refused by name instead of decoded wrongly; attention without QK-norm
    at equal heads is taken as before."""
    from paddle_tpu.serving.generate import GenerationEngine

    with pytest.raises(ValueError, match="gated_short_conv.*last taps"):
        GenerationEngine(_bundle(tmp_path / "conv", lambda x: (
            fluid.layers.short_conv_mixer(x, 3))))
    with pytest.raises(ValueError, match="QK-norm"):
        GenerationEngine(_bundle(tmp_path / "qk", lambda x: (
            fluid.layers.grouped_query_attention(x, 4, 4, 64,
                                                 qk_norm=True))))
    GenerationEngine(_bundle(tmp_path / "plain", lambda x: (
        fluid.layers.grouped_query_attention(x, 4, 4, 64))))
