"""The Pallas family ``causal_conv1d`` (ops/pallas/causal_conv1d.py), on the
CPU in interpret mode: the two kernels against the jnp op and ``jax.vjp`` of
it; causality; float32 filter and bias gradients that sum over the batch;
what ``supported`` refuses and what a refusal costs (one counted fallback to
the twin); and, under ``kernel_tier=jnp``, the op, its grad op and the tiny
Kimi-Linear and Nemotron train programs tracing what they traced at the
parent of the PR that brought the kernels (PR 37)."""

import collections
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.ops import linear_attention_ops as la
from paddle_tpu.ops import pallas as tier
from paddle_tpu.ops.pallas import causal_conv1d as cc

T, CHANNELS = 160, 256      # five pieces of 32 tokens, two 128-lane columns


@pytest.fixture(autouse=True)
def _reset():
    tier.reset_fallback_counts()
    yield
    fluid.set_flags({"kernel_tier": "auto"})
    tier.reset_fallback_counts()


def _case(bias, batch, taps, dtype, t=T, channels=CHANNELS, seed=0):
    rng = np.random.RandomState(seed)
    x, dout = (jnp.asarray(rng.randn(batch, t, channels), dtype)
               for _ in range(2))
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (taps, channels)), jnp.float32)
    b = jnp.asarray(rng.uniform(-0.5, 0.5, channels), jnp.float32) \
        if bias else None
    return x, w, b, dout


def _twin(x, w, b, dout):
    args = (x, w) + (() if b is None else (b,))
    out, back = jax.vjp(lambda *a: la._causal_conv1d(None, *a), *args)
    return out, back(dout)


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


@pytest.mark.parametrize(
    "bias,batch,taps,dtype,width",
    list(itertools.product([False, True], [1, 2], [2, 4],
                           [jnp.bfloat16, jnp.float32], [128, 256])),
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_both_kernels_equal_the_jnp_op_and_its_vjp(bias, batch, taps, dtype,
                                                   width):
    """Forward within one rounding of X's type, ``X@GRAD`` too; the filter's
    and the bias's gradients are float32 sums in another order."""
    x, w, b, dout = _case(bias, batch, taps, dtype)
    assert cc.supported(x, w)
    assert cc._rows(T, cc._tile(dtype)) == 32
    want, grads = _twin(x, w, b, dout)
    one_rounding = 8e-3 if dtype == jnp.bfloat16 else 1e-6
    got = cc.causal_conv1d_fwd(x, w, b, width=width)
    assert (got.shape, got.dtype) == (x.shape, x.dtype)
    assert _err(got, want) < one_rounding
    dx, dw, db = cc.causal_conv1d_bwd(x, w, b, dout, width=width)
    assert (dx.shape, dx.dtype) == (x.shape, x.dtype)
    assert _err(dx, grads[0]) < one_rounding
    assert (dw.shape, dw.dtype) == (w.shape, jnp.float32)
    assert _err(dw, grads[1]) < 1e-5
    if bias:
        assert (db.shape, db.dtype) == (b.shape, jnp.float32)
        assert _err(db, grads[2]) < 1e-5
    else:
        assert db is None


@pytest.mark.parametrize("t", [16, 48, 128, 192])
def test_any_length_in_whole_sublane_tiles(t):
    """The walk's piece follows the length (16, 16, 128 and 64 tokens
    here): one piece, pieces of a single tile, whole ``ROWS`` and a
    halving of it."""
    x, w, b, dout = _case(True, 1, 4, jnp.bfloat16, t=t, channels=128)
    assert cc.supported(x, w)
    want, grads = _twin(x, w, b, dout)
    assert _err(cc.causal_conv1d_fwd(x, w, b), want) < 8e-3
    dx, dw, db = cc.causal_conv1d_bwd(x, w, b, dout)
    assert _err(dx, grads[0]) < 8e-3
    assert _err(dw, grads[1]) < 1e-5 and _err(db, grads[2]) < 1e-5


@pytest.mark.parametrize("taps", [2, 4, 8])
def test_causal_and_zeros_before_the_first_token(taps):
    """Changing token t (and all after it) changes no output before t, at a
    piece's edge and inside one; the first ``taps - 1`` tokens see zeros
    where the sequence has no token; ``X@GRAD`` of a token depends on no
    ``Out@GRAD`` before it."""
    x, w, b, dout = _case(True, 1, taps, jnp.float32)
    out = np.asarray(cc.causal_conv1d_fwd(x, w, b))
    for t in (32, 77):
        other = x.at[:, t:].set(_case(True, 1, taps, jnp.float32,
                                      seed=1)[0][:, t:])
        moved = np.asarray(cc.causal_conv1d_fwd(other, w, b))
        assert np.array_equal(moved[:, :t], out[:, :t])
        assert not np.array_equal(moved[:, t], out[:, t])
        dx = np.asarray(cc.causal_conv1d_bwd(x, w, b, dout)[0])
        late = dout.at[:, :t].set(0.0)
        assert np.array_equal(
            np.asarray(cc.causal_conv1d_bwd(x, w, b, late)[0])[:, t:],
            dx[:, t:])
    for t in range(taps - 1):
        y = b + sum(x[0, t - s] * w[taps - 1 - s] for s in range(t + 1))
        assert _err(out[0, t], jax.nn.silu(y)) < 1e-6


def test_filter_and_bias_gradients_are_float32_and_sum_over_the_batch():
    x, w, b, dout = _case(True, 2, 4, jnp.bfloat16)
    _, dw, db = cc.causal_conv1d_bwd(x, w, b, dout)
    assert dw.dtype == db.dtype == jnp.float32
    rows = [cc.causal_conv1d_bwd(x[i:i + 1], w, b, dout[i:i + 1])
            for i in range(2)]
    assert _err(dw, rows[0][1] + rows[1][1]) < 1e-6
    assert _err(db, rows[0][2] + rows[1][2]) < 1e-6
    assert _err(rows[0][1], rows[1][1]) > 0.1      # the rows differ


def test_the_block_width_follows_the_shape_and_the_budget():
    def x(t, channels, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, t, channels), dtype)
    assert cc.block_width(x(4096, 4096), True) == 256
    assert cc.block_width(x(4096, 6144), True) == 256
    assert cc.block_width(x(4096, 384), True) == 128
    # 8192 float32 tokens: the backward's three blocks of 256 channels,
    # each twice, are 48 MiB, the whole budget before its two scratch
    # columns of 4 MiB each; the forward's two are 32 MiB and one column
    long = x(8192, 1024, jnp.float32)
    assert cc.vmem_bytes(8192, 256, jnp.float32, True) == (
        48 << 20) + 2 * (8192 + 16) * 512
    assert cc.vmem_bytes(8192, 256, jnp.float32, False) == (
        32 << 20) + (8192 + 16) * 512
    assert cc.block_width(long, True) == 128
    assert cc.block_width(long, False) == 256


def test_supported_refuses_what_the_kernels_cannot_take():
    w = jax.ShapeDtypeStruct((4, 256), jnp.float32)

    def x(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    assert cc.supported(x(1, 64, 256), w)
    assert cc.supported(x(2, 4096, 6144), w)
    assert not cc.supported(x(1, 64, 192), w)           # off a lane multiple
    assert not cc.supported(x(1, 40, 256), w)           # half a bfloat16 tile
    assert cc.supported(x(1, 40, 256, dtype=jnp.float32), w)
    assert not cc.supported(x(1, 64, 256, dtype=jnp.int32), w)
    assert not cc.supported(x(1, 64, 256, dtype=jnp.float16), w)
    assert not cc.supported(x(64, 256), w)
    assert not cc.supported(x(1, 64, 256),
                            jax.ShapeDtypeStruct((9, 256), jnp.float32))
    # a [T, 128] block of the whole time axis over the budget: 48 MiB hold
    # six float32 blocks and two scratch columns of 12280 tokens, not of
    # 12288
    assert cc.supported(x(1, 12280, 128, dtype=jnp.float32), w)
    assert not cc.supported(x(1, 12288, 128, dtype=jnp.float32), w)
    assert cc.supported(x(1, 16384, 128), w)
    assert not cc.supported(x(1, 1 << 17, 128), w)


def _op_and_grad(x, taps, bias):
    """``causal_conv1d`` and its grad op through a Program: the output and
    the gradients of ``sum(out ** 2)`` to X, the filter and the bias."""
    from paddle_tpu.fluid import framework
    framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    uniform = lambda: fluid.ParamAttr(       # noqa: E731
        initializer=fluid.initializer.Uniform(-0.5, 0.5))
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", shape=list(x.shape),
                               append_batch_size=False)
        xv.stop_gradient = False
        out = fluid.layers.causal_conv1d(
            xv, taps, param_attr=uniform(), bias_attr=bias and uniform())
        fluid.backward.append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, out)))
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    names = [out.name, "x@GRAD"] + [
        p.name + "@GRAD" for p in main.global_block().all_parameters()]
    return [np.asarray(a) for a in exe.run(main, feed={"x": x}, scope=scope,
                                           fetch_list=names)]


@pytest.mark.parametrize("bias", [False, True])
def test_the_op_and_its_grad_op_take_the_same_route(bias):
    """Under ``pallas`` both dispatch the family's kernels (two counted
    dispatches, interpreted here); a shape ``supported`` refuses gives the
    twin's exact results and two counted fallbacks (the op's and the grad
    op's), no dispatch; under ``jnp`` nothing is counted at all."""
    rng = np.random.RandomState(3)
    results = {}
    for channels in (128, 96):
        x = rng.randn(2, 32, channels).astype(np.float32)
        for name in ("jnp", "pallas"):
            before = tier.dispatch_counts().get("causal_conv1d",
                                                {"interpret": 0})
            fluid.set_flags({"kernel_tier": name})
            results[name] = _op_and_grad(x, 4, bias)
            now = tier.dispatch_counts().get("causal_conv1d",
                                             {"interpret": 0, "native": 0})
            took = now["interpret"] - before["interpret"]
            assert took == (2 if (name, channels) == ("pallas", 128) else 0)
            assert now.get("native", 0) == 0
        assert len(results["jnp"]) == 3 + bias
        for a, b in zip(results["pallas"], results["jnp"]):
            if channels == 128:
                assert _err(a, b) < 1e-5
            else:
                assert np.array_equal(a, b)
    assert tier.fallback_counts() == {"causal_conv1d": 2}


def _parents_grad_op(args, dout):
    """``causal_conv1d_grad`` as the parent computed it: the barrier, then
    ``jax.vjp`` of the forward function."""
    args, dout = jax.lax.optimization_barrier((args, dout))
    out, back = jax.vjp(lambda *a: la._causal_conv1d(None, *a), *args)
    return back(dout.astype(out.dtype))


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("tier_name", ["jnp", "auto"])
def test_off_the_kernels_the_op_and_its_grad_op_trace_what_they_traced(
        bias, tier_name):
    """Equation for equation, on a shape the kernels would take: what the
    compiler is handed on the CPU and under ``kernel_tier=jnp`` is what the
    parent handed it."""
    fluid.set_flags({"kernel_tier": tier_name})
    x, w, b, dout = _case(bias, 1, 4, jnp.bfloat16, t=32, channels=128)
    args = [x, w] + ([b] if bias else [])
    assert str(jax.make_jaxpr(lambda *a: la._causal_conv1d(None, *a))(
        *args)) == str(jax.make_jaxpr(
            lambda *a: la._conv_forward(None, *a))(*args))
    assert str(jax.make_jaxpr(_parents_grad_op)(args, dout)) == str(
        jax.make_jaxpr(lambda a, d: la._conv_backward(None, a, d))(
            args, dout))
    assert tier.fallback_counts() == {}


# how often each primitive occurred in the jaxpr of the tiny Kimi-Linear and
# Nemotron train steps (the chip's share, forward + backward + Adam under
# AMP, 40 tokens) at the parent of PR 37, where ``causal_conv1d`` had no
# kernel route; the whole texts were equal too (sha256 ac07110d... and
# 54dcef80... on both trees)
KIMI_PRIMITIVES = {
    "abs": 7, "add": 754, "add_any": 129, "and": 24,
    "broadcast_in_dim": 746, "concatenate": 9, "convert_element_type": 860,
    "cumsum": 18, "custom_jvp_call": 6, "div": 291, "dot_general": 211,
    "dynamic_slice": 6, "eq": 58, "exp": 80, "gather": 32, "ge": 24,
    "gt": 3, "iota": 92, "is_finite": 1, "jit": 271, "le": 8, "log": 2,
    "log1p": 6, "logistic": 36, "lt": 63, "lt_to": 6, "max": 25, "min": 21,
    "mul": 1530, "ne": 42, "neg": 43, "optimization_barrier": 18,
    "pad": 159, "ragged_dot_general": 27, "reduce_max": 2,
    "reduce_sum": 298, "rem": 18, "reshape": 787, "rsqrt": 38, "scan": 12,
    "scatter-add": 12, "select_n": 180, "sign": 40, "slice": 218, "sort": 3,
    "split": 4, "sqrt": 170, "square": 4, "squeeze": 97, "stop_gradient": 1,
    "sub": 382, "top_k": 3, "transpose": 187, "triangular_solve": 12}
NEMOTRON_PRIMITIVES = {
    "abs": 7, "add": 479, "add_any": 51, "and": 24, "broadcast_in_dim": 521,
    "concatenate": 6, "convert_element_type": 491, "cumsum": 24,
    "custom_jvp_call": 12, "div": 204, "dot_general": 121,
    "dynamic_slice": 15, "eq": 40, "exp": 49, "gather": 32, "ge": 27,
    "gt": 6, "iota": 68, "is_finite": 1, "jit": 250, "le": 2, "log": 2,
    "log1p": 6, "logistic": 15, "lt": 66, "lt_to": 6, "max": 34, "min": 21,
    "mul": 902, "ne": 42, "neg": 34, "optimization_barrier": 9, "pad": 93,
    "ragged_dot_general": 18, "reduce_max": 2, "reduce_sum": 202, "rem": 18,
    "reshape": 503, "rsqrt": 22, "scan": 6, "scatter-add": 15,
    "select_n": 168, "sign": 40, "slice": 88, "sort": 3, "split": 6,
    "sqrt": 106, "square": 6, "squeeze": 49, "stop_gradient": 1, "sub": 260,
    "top_k": 3, "transpose": 106}


def _train_step_primitives(build, cfg, length=40):
    from paddle_tpu.obs.perf import program_jaxpr

    main, startup, loss, _, _ = build(cfg, length)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(1e-3).minimize(loss, startup)
    exe, scope = fluid.Executor(mode="jit", amp=True), fluid.Scope()
    exe.run(startup, scope=scope)
    tok = np.zeros((1, length, 1), np.int64)
    jaxpr = program_jaxpr(main, {"tokens": tok, "labels": tok}, [loss],
                          executor=exe, scope=scope)

    def count(j, into):
        for eqn in j.eqns:
            into[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                count(sub, into)
        return into
    return dict(count(jaxpr.jaxpr, collections.Counter()))


@pytest.mark.parametrize("model", ["kimi_linear", "nemotron_h"])
def test_under_jnp_the_tiny_train_programs_trace_what_they_traced(model):
    from paddle_tpu.testing import models
    if model == "kimi_linear":
        from test_kimi_linear_ops import SHARE
        build, want = models.build_kimi_linear_lm, KIMI_PRIMITIVES
    else:
        from test_nemotron_h_ops import SHARE
        build, want = models.build_nemotron_h_lm, NEMOTRON_PRIMITIVES
    fluid.set_flags({"kernel_tier": "jnp"})
    assert _train_step_primitives(build, SHARE) == want
    assert tier.fallback_counts() == {}
