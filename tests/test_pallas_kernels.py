"""Pallas fused RNN cell kernels vs the jnp lowering.

The kernels mirror the reference's hand-scheduled fused LSTM/GRU CUDA
kernels (paddle/cuda/src/hl_cuda_lstm.cu, hl_gpu_lstm.cuh); parity with
the plain jnp path is the numeric contract (the reference pins its CUDA
kernels to CPU kernels the same way, gserver/tests CPU-vs-GPU compares).
Interpret mode runs the SAME kernel bodies on CPU; on TPU they compile
natively.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    fluid.set_flags({"kernel_tier": "auto"})


def test_gru_seq_kernel_matches_jnp_twin():
    """Whole-recurrence GRU kernel vs its jnp twin (same bf16-matmul
    recipe): carries and grads (dx, dw, dh0) must match tightly."""
    from paddle_tpu.ops.pallas.rnn import gru_seq_pallas, _gru_step_jnp

    rng = np.random.RandomState(2)
    L, b, H = 5, 4, 8
    x = jnp.asarray(rng.normal(0, 1, (L, b, 3 * H)).astype("float32"))
    lens = jnp.asarray([5, 2, 4, 1], jnp.int32)
    alive = (jnp.arange(L)[:, None] < lens[None, :]) \
        .astype(jnp.float32)[..., None]
    w = jnp.asarray(rng.normal(0, 0.5, (H, 3 * H)).astype("float32"))
    h0 = jnp.asarray(rng.normal(0, 1, (b, H)).astype("float32"))

    def jnp_seq(x, alive, w, h0):
        def step(h, inp):
            xt, at = inp
            h = _gru_step_jnp(xt, h, w, at)
            return h, h
        _, hs = jax.lax.scan(step, h0, (x, alive))
        return hs

    got = gru_seq_pallas(x, alive, w, h0)
    exp = jnp_seq(x, alive, w, h0)
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-6)

    g_got = jax.grad(lambda x, w, h0: jnp.sum(
        gru_seq_pallas(x, alive, w, h0) ** 2), argnums=(0, 1, 2))(x, w, h0)
    g_exp = jax.grad(lambda x, w, h0: jnp.sum(
        jnp_seq(x, alive, w, h0) ** 2), argnums=(0, 1, 2))(x, w, h0)
    for a, b_, name in zip(g_got, g_exp, ("dx", "dw", "dh0")):
        np.testing.assert_allclose(a, b_, rtol=2e-4, atol=1e-5,
                                   err_msg=name)


def test_lstm_op_parity_with_pallas_flag():
    """dynamic_lstm end-to-end: fwd outputs AND trained weights identical
    with the pallas cell on vs off."""
    layers = fluid.layers

    def run(use_pallas):
        fluid.set_flags({"kernel_tier": "pallas" if use_pallas else "jnp"})
        from paddle_tpu.fluid import framework
        from paddle_tpu.core import scope as scope_mod
        framework.reset_unique_name()
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[1], dtype="int64", lod_level=1)
            e = layers.embedding(x, size=[12, 8])
            proj = layers.fc(e, size=16 * 4)
            h, c = layers.dynamic_lstm(proj, size=16 * 4)
            pred = layers.fc(layers.sequence_last_step(h), size=1)
            label = layers.data("y", shape=[1])
            loss = layers.mean(layers.square(
                layers.elementwise_sub(pred, label)))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss, startup)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(3)
        seqs = [rng.randint(0, 12, (int(rng.randint(2, 6)), 1))
                .astype("int64") for _ in range(5)]
        feed = {"x": seqs, "y": rng.normal(0, 1, (5, 1)).astype("float32")}
        losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                                scope=scope)[0]) for _ in range(5)]
        return losses

    base = run(False)
    pallas = run(True)
    # the whole-recurrence kernel computes its MXU matmuls in bf16 with f32
    # accumulation (the TPU lane contract) while the jnp scan on CPU runs
    # f32 — parity to bf16 resolution; exact parity vs the bf16 jnp twin is
    # pinned in test_lstm_seq_kernel_matches_jnp_twin
    np.testing.assert_allclose(pallas, base, rtol=5e-4, atol=1e-5)
    assert base[-1] < base[0]


def test_lstm_seq_kernel_matches_jnp_twin():
    """Whole-recurrence kernel vs its jnp twin (same bf16-matmul recipe):
    carries AND gradients (dx, dw, dh0, dc0) must match tightly."""
    from paddle_tpu.ops.pallas.rnn import (lstm_seq_pallas,
                                               _lstm_step_jnp)

    rng = np.random.RandomState(4)
    L, b, H = 6, 4, 8
    x = jnp.asarray(rng.normal(0, 1, (L, b, 4 * H)).astype("float32"))
    lens = jnp.asarray([6, 3, 5, 1], jnp.int32)
    alive = (jnp.arange(L)[:, None] < lens[None, :]) \
        .astype(jnp.float32)[..., None]
    w = jnp.asarray(rng.normal(0, 0.5, (H, 4 * H)).astype("float32"))
    h0 = jnp.asarray(rng.normal(0, 1, (b, H)).astype("float32"))
    c0 = jnp.asarray(rng.normal(0, 1, (b, H)).astype("float32"))

    def jnp_seq(x, alive, w, h0, c0):
        def step(carry, inp):
            h, c = carry
            xt, at = inp
            h, c = _lstm_step_jnp(xt, h, c, w, at)
            return (h, c), (h, c)
        _, (hs, cs) = jax.lax.scan(step, (h0, c0), (x, alive))
        return hs, cs

    got_h, got_c = lstm_seq_pallas(x, alive, w, h0, c0)
    exp_h, exp_c = jnp_seq(x, alive, w, h0, c0)
    np.testing.assert_allclose(got_h, exp_h, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_c, exp_c, rtol=1e-5, atol=1e-6)

    def loss_pallas(x, w, h0, c0):
        hs, cs = lstm_seq_pallas(x, alive, w, h0, c0)
        return jnp.sum(hs ** 2) + jnp.sum(cs * alive)

    def loss_jnp(x, w, h0, c0):
        hs, cs = jnp_seq(x, alive, w, h0, c0)
        return jnp.sum(hs ** 2) + jnp.sum(cs * alive)

    g_got = jax.grad(loss_pallas, argnums=(0, 1, 2, 3))(x, w, h0, c0)
    g_exp = jax.grad(loss_jnp, argnums=(0, 1, 2, 3))(x, w, h0, c0)
    for a, b_, name in zip(g_got, g_exp, ("dx", "dw", "dh0", "dc0")):
        np.testing.assert_allclose(a, b_, rtol=2e-4, atol=1e-5,
                                   err_msg=name)

def _lstm_bwd_case(L, b, H, lens, with_state, seed=6):
    """Inputs of the whole-sequence backward and what jax.grad of the jnp
    twin scan says the gradients are, for cotangents on BOTH outputs."""
    from paddle_tpu.ops.pallas.rnn import _lstm_step_jnp

    rng = np.random.RandomState(seed)

    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.normal(0, scale, shape).astype("float32"))

    x = arr(L, b, 4 * H)
    lens = jnp.asarray(lens, jnp.int32)
    alive = (jnp.arange(L)[:, None] < lens[None, :]) \
        .astype(jnp.float32)[..., None]
    w = arr(H, 4 * H, scale=0.5 / np.sqrt(H / 8))
    h0, c0 = (arr(b, H), arr(b, H)) if with_state \
        else (jnp.zeros((b, H)), jnp.zeros((b, H)))
    dhs, dcs = arr(L, b, H), arr(L, b, H)

    def twin(x, w, h0, c0):
        def step(carry, inp):
            h, c = _lstm_step_jnp(inp[0], *carry, w, inp[1])
            return (h, c), (h, c)
        return jax.lax.scan(step, (h0, c0), (x, alive))[1]

    (hs, cs), vjp = jax.vjp(twin, x, w, h0, c0)
    dx, dw, dh0, dc0 = vjp((dhs, dcs))
    return (x, alive, w, h0, c0, hs, cs, dhs, dcs), (dx, dw, dh0, dc0)


def _assert_lstm_grads(got, exp):
    for a, e, name in zip(got, exp, ("dx", "dw", "dh0", "dc0")):
        np.testing.assert_allclose(a, e, rtol=2e-4, atol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("masked", [False, True],
                         ids=["carries", "masked_outputs"])
@pytest.mark.parametrize("with_state", [False, True],
                         ids=["zero_state", "h0_c0"])
@pytest.mark.parametrize("path", ["kernel", "scan"])
def test_lstm_seq_bwd_matches_grad_of_jnp_twin(path, with_state, masked):
    """The backward from saved carries — the reverse kernel (interpret
    mode) and the scan it falls back to, each with the weight gradient
    taken after the loop — against jax.grad of the jnp twin scan: ragged
    lengths with a length-1 and a full-length row, dhs and dcs both
    non-zero, from the carries and from the op's masked outputs."""
    from paddle_tpu.ops.pallas import rnn

    L, b, H = 6, 4, 8
    args, exp = _lstm_bwd_case(L, b, H, [6, 3, 5, 1], with_state)
    x, alive, w, h0, c0, hs, cs, dhs, dcs = args
    if masked:
        hs, cs = hs * alive, cs * alive
    if path == "kernel":
        got = rnn.lstm_seq_bwd(x, alive, w, h0, c0, hs, cs, dhs, dcs)
    else:
        dx, dh0, dc0 = rnn._lstm_seq_bwd_scan(
            x, alive, w.astype(jnp.bfloat16), h0, c0, hs, cs, dhs, dcs)
        got = (dx, rnn._lstm_dw(h0, hs, dx).astype(w.dtype), dh0, dc0)
    _assert_lstm_grads(got, exp)


def test_lstm_seq_bwd_over_vmem_budget_falls_back_counted():
    """A batch whose blocks exceed the kernel's VMEM budget (counted from
    the shape alone) takes the scan, says so on the fallback counter, and
    still gives the twin's gradients."""
    from paddle_tpu.ops import pallas as tier
    from paddle_tpu.ops.pallas import rnn

    b, H = 1024, 512
    assert rnn.lstm_bwd_fits(256, H) and not rnn.lstm_bwd_fits(b, H)
    args, exp = _lstm_bwd_case(2, b, H, [2] * (b - 1) + [1], True)
    tier.reset_fallback_counts()
    before = tier.dispatch_counts().get("lstm_bwd", {})
    try:
        got = rnn.lstm_seq_bwd(*args)
        assert tier.fallback_counts() == {"lstm_bwd": 1}
        assert tier.dispatch_counts().get("lstm_bwd", {}) == before
    finally:
        tier.reset_fallback_counts()
    # products over 2048 bf16 operands: where hand-written and autodiff
    # arithmetic differ in a last float32 bit an operand rounds the other
    # way, a 2**-9 step of one term; against each gradient's largest
    # value that reads 3e-5..4.5e-4 over three seeds. The element-wise
    # 2e-4 is held at the small shape above.
    for a, e, name in zip(got, exp, ("dx", "dw", "dh0", "dc0")):
        assert np.abs(a - e).max() <= 1.5e-3 * np.abs(e).max(), name


def test_gru_op_parity_with_pallas_flag():
    layers = fluid.layers

    def run(use_pallas):
        fluid.set_flags({"kernel_tier": "pallas" if use_pallas else "jnp"})
        from paddle_tpu.fluid import framework
        framework.reset_unique_name()
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 9
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[1], dtype="int64", lod_level=1)
            e = layers.embedding(x, size=[10, 6])
            proj = layers.fc(e, size=12 * 3)
            h = layers.dynamic_gru(proj, size=12)
            pred = layers.fc(layers.sequence_last_step(h), size=1)
            label = layers.data("y", shape=[1])
            loss = layers.mean(layers.square(
                layers.elementwise_sub(pred, label)))
            fluid.optimizer.Adam(learning_rate=0.05).minimize(loss, startup)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(4)
        seqs = [rng.randint(0, 10, (int(rng.randint(2, 6)), 1))
                .astype("int64") for _ in range(5)]
        feed = {"x": seqs, "y": rng.normal(0, 1, (5, 1)).astype("float32")}
        return [float(exe.run(main, feed=feed, fetch_list=[loss],
                              scope=scope)[0]) for _ in range(5)]

    base = run(False)
    pallas = run(True)
    # bf16-MXU in-kernel matmuls vs the f32 CPU scan (same contract as the
    # LSTM parity test above); exact parity vs the bf16 twin is pinned in
    # test_gru_seq_kernel_matches_jnp_twin
    np.testing.assert_allclose(pallas, base, rtol=1e-3, atol=5e-4)
    assert base[-1] < base[0]


def test_pallas_ctc_matches_scan_path():
    """The Pallas whole-recurrence CTC forward is numerically pinned to the
    lax.scan path (losses AND gradients), ragged x/y lengths included."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.ops.ctc_ops import _ctc_loss

    rng = np.random.RandomState(0)
    b, T, C, U = 4, 11, 7, 4
    logits = jnp.asarray(rng.normal(0, 1, (b, T, C)).astype("float32"))
    x_lens = jnp.asarray([11, 7, 9, 5], jnp.int32)
    labels = jnp.asarray(rng.randint(1, C, (b, U)), jnp.int32)
    # repeated labels exercise the can_skip mask
    labels = labels.at[0, 1].set(labels[0, 0])
    y_lens = jnp.asarray([4, 2, 3, 1], jnp.int32)

    ref, ref_grad = jax.value_and_grad(
        lambda lg: jnp.sum(_ctc_loss(lg, x_lens, labels, y_lens, 0)))(logits)

    set_flags({"kernel_tier": "pallas"})
    try:
        got, got_grad = jax.value_and_grad(
            lambda lg: jnp.sum(_ctc_loss(lg, x_lens, labels, y_lens, 0)))(
                logits)
    finally:
        set_flags({"kernel_tier": "auto"})

    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_grad), np.asarray(ref_grad),
                               rtol=1e-4, atol=1e-5)
