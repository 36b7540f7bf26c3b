"""LSTM / GRU op tests against step-by-step numpy recurrences.

Mirrors /root/reference/python/paddle/fluid/tests/unittests/test_lstm_op.py
and test_gru_op.py in spirit: a python recurrence over each ragged sequence
is the ground truth. Gate layouts are this framework's documented contract
(ops/rnn_ops.py): LSTM [i, f, c, o]; GRU [u, r, c] with
h = u*c + (1-u)*h_prev (reference gru_unit_op.h: h = u*(c - h_prev) + h_prev).
"""

import numpy as np
import pytest

from op_test import OpTest


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def lstm_ref(x, lod, w, b):
    """x: [total, 4H] pre-projected; returns hidden/cell flat arrays."""
    H = w.shape[0]
    hs, cs = np.zeros((len(x), H), "float32"), np.zeros((len(x), H), "float32")
    offs = lod[0]
    for i in range(len(offs) - 1):
        h = np.zeros(H, "float32")
        c = np.zeros(H, "float32")
        for t in range(offs[i], offs[i + 1]):
            g = x[t] + h @ w + (b[0] if b is not None else 0.0)
            ig, fg = sigmoid(g[:H]), sigmoid(g[H:2 * H])
            cand, og = np.tanh(g[2 * H:3 * H]), sigmoid(g[3 * H:])
            c = fg * c + ig * cand
            h = og * np.tanh(c)
            hs[t], cs[t] = h, c
    return hs, cs


def gru_ref(x, lod, w, b):
    H = w.shape[0]
    hs = np.zeros((len(x), H), "float32")
    offs = lod[0]
    wu, wr, wc = w[:, :H], w[:, H:2 * H], w[:, 2 * H:]
    for i in range(len(offs) - 1):
        h = np.zeros(H, "float32")
        for t in range(offs[i], offs[i + 1]):
            g = x[t] + (b[0] if b is not None else 0.0)
            u = sigmoid(g[:H] + h @ wu)
            r = sigmoid(g[H:2 * H] + h @ wr)
            c = np.tanh(g[2 * H:] + (r * h) @ wc)
            h = u * c + (1 - u) * h
            hs[t] = h
    return hs


class TestLstm(OpTest):
    op_type = "lstm"

    def setup_method(self, method):
        rng = np.random.RandomState(21)
        H = 4
        lod = [[0, 3, 7]]
        x = rng.uniform(-0.5, 0.5, (7, 4 * H)).astype("float32")
        w = rng.uniform(-0.3, 0.3, (H, 4 * H)).astype("float32")
        b = rng.uniform(-0.2, 0.2, (1, 4 * H)).astype("float32")
        hs, cs = lstm_ref(x, lod, w, b)
        self.inputs = {"Input": (x, lod), "Weight": w, "Bias": b}
        self.attrs = {"use_peepholes": False, "is_reverse": False,
                      "gate_activation": "sigmoid",
                      "cell_activation": "tanh",
                      "candidate_activation": "tanh"}
        self.outputs = {"Hidden": (hs, lod), "Cell": (cs, lod)}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["Input", "Weight", "Bias"], "Hidden",
                        max_relative_error=0.06)


class TestLstmReverse(OpTest):
    op_type = "lstm"

    def setup_method(self, method):
        rng = np.random.RandomState(23)
        H = 3
        lod = [[0, 2, 5]]
        x = rng.uniform(-0.5, 0.5, (5, 4 * H)).astype("float32")
        w = rng.uniform(-0.3, 0.3, (H, 4 * H)).astype("float32")
        b = rng.uniform(-0.2, 0.2, (1, 4 * H)).astype("float32")
        # reverse each sequence, run forward, reverse outputs back
        xr = x.copy()
        offs = lod[0]
        for i in range(len(offs) - 1):
            xr[offs[i]:offs[i + 1]] = x[offs[i]:offs[i + 1]][::-1]
        hs, cs = lstm_ref(xr, lod, w, b)
        for i in range(len(offs) - 1):
            hs[offs[i]:offs[i + 1]] = hs[offs[i]:offs[i + 1]][::-1]
            cs[offs[i]:offs[i + 1]] = cs[offs[i]:offs[i + 1]][::-1]
        self.inputs = {"Input": (x, lod), "Weight": w, "Bias": b}
        self.attrs = {"is_reverse": True}
        self.outputs = {"Hidden": (hs, lod), "Cell": (cs, lod)}

    def test_output(self):
        self.check_output(atol=1e-5)


class TestGru(OpTest):
    op_type = "gru"

    def setup_method(self, method):
        rng = np.random.RandomState(29)
        H = 4
        lod = [[0, 3, 7]]
        x = rng.uniform(-0.5, 0.5, (7, 3 * H)).astype("float32")
        w = rng.uniform(-0.3, 0.3, (H, 3 * H)).astype("float32")
        b = rng.uniform(-0.2, 0.2, (1, 3 * H)).astype("float32")
        hs = gru_ref(x, lod, w, b)
        self.inputs = {"Input": (x, lod), "Weight": w, "Bias": b}
        self.attrs = {"is_reverse": False}
        self.outputs = {"Hidden": (hs, lod)}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["Input", "Weight", "Bias"], "Hidden",
                        max_relative_error=0.06)


class TestLstmUnit(OpTest):
    op_type = "lstm_unit"

    def setup_method(self, method):
        rng = np.random.RandomState(31)
        b_, H = 5, 4
        x = rng.uniform(-0.5, 0.5, (b_, 4 * H)).astype("float32")
        c_prev = rng.uniform(-0.5, 0.5, (b_, H)).astype("float32")
        fb = 0.5
        i, f = sigmoid(x[:, :H]), sigmoid(x[:, H:2 * H] + fb)
        cand, o = np.tanh(x[:, 2 * H:3 * H]), sigmoid(x[:, 3 * H:])
        c = f * c_prev + i * cand
        h = o * np.tanh(c)
        self.inputs = {"X": x, "C_prev": c_prev}
        self.attrs = {"forget_bias": fb}
        self.outputs = {"C": c, "H": h}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["X", "C_prev"], ["C", "H"],
                        max_relative_error=0.03)


class TestGruUnit(OpTest):
    op_type = "gru_unit"

    def setup_method(self, method):
        rng = np.random.RandomState(37)
        b_, H = 5, 4
        x = rng.uniform(-0.5, 0.5, (b_, 3 * H)).astype("float32")
        h_prev = rng.uniform(-0.5, 0.5, (b_, H)).astype("float32")
        w = rng.uniform(-0.3, 0.3, (H, 3 * H)).astype("float32")
        b = rng.uniform(-0.2, 0.2, (1, 3 * H)).astype("float32")
        g = x + b
        u = sigmoid(g[:, :H] + h_prev @ w[:, :H])
        r = sigmoid(g[:, H:2 * H] + h_prev @ w[:, H:2 * H])
        c = np.tanh(g[:, 2 * H:] + (r * h_prev) @ w[:, 2 * H:])
        h = u * c + (1 - u) * h_prev
        self.inputs = {"Input": x, "HiddenPrev": h_prev, "Weight": w,
                       "Bias": b}
        self.outputs = {"Gate": np.concatenate([u, r, c], axis=1),
                        "ResetHiddenPrev": r * h_prev, "Hidden": h}

    def test_output(self):
        self.check_output(atol=1e-5, no_check_set=["Gate", "ResetHiddenPrev"])

    def test_grad(self):
        self.check_grad(["Input", "HiddenPrev", "Weight"], "Hidden",
                        max_relative_error=0.06)


def test_dynamic_lstmp_trains_and_projects():
    """LSTM with recurrent projection (reference lstmp_op): the projection
    output has proj_size features, the recurrence runs over it, and the
    model trains end to end."""
    import paddle_tpu.fluid as fluid
    layers = fluid.layers
    H, P = 12, 5
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 6
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[1], dtype="int64", lod_level=1)
        e = layers.embedding(x, size=[10, 8])
        proj_in = layers.fc(e, size=4 * H)
        proj, cell = layers.dynamic_lstmp(proj_in, size=4 * H, proj_size=P)
        last = layers.sequence_last_step(proj)
        pred = layers.fc(last, size=1)
        label = layers.data("y", shape=[1])
        loss = layers.mean(layers.square(
            layers.elementwise_sub(pred, label)))
        fluid.optimizer.Adam(learning_rate=0.03).minimize(loss, startup)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(1)
    seqs = [rng.randint(0, 10, (int(rng.randint(2, 6)), 1)).astype("int64")
            for _ in range(6)]
    feed = {"x": seqs, "y": rng.normal(0, 1, (6, 1)).astype("float32")}
    out = exe.run(main, feed=feed, fetch_list=[proj, cell], scope=scope)
    assert out[0].data.shape[-1] == P       # projected width
    assert out[1].data.shape[-1] == H       # cell width
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0]) for _ in range(40)]
    assert losses[-1] < 0.2 * losses[0], losses[::10]


def test_lstm_peepholes_train_and_differ_from_plain():
    """use_peepholes=True (the reference DEFAULT): i/f gates see the
    previous cell state, o sees the new one, weights live in the 7H bias
    (lstm_op.cc:74, math/detail/lstm_kernel.h:37-40). The model must train
    AND produce different outputs from the plain LSTM once the peephole
    weights move off zero."""
    import paddle_tpu.fluid as fluid
    layers = fluid.layers

    def build(peep):
        from paddle_tpu.fluid import framework
        framework.reset_unique_name()
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 4
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[1], dtype="int64", lod_level=1)
            e = layers.embedding(x, size=[10, 8])
            h, c = layers.dynamic_lstm(layers.fc(e, size=32), size=32,
                                       use_peepholes=peep)
            pred = layers.fc(layers.sequence_last_step(h), size=1)
            y = layers.data("y", shape=[1])
            loss = layers.mean(layers.square(
                layers.elementwise_sub(pred, y)))
            fluid.optimizer.Adam(learning_rate=0.05).minimize(loss, startup)
        return main, startup, loss

    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, 10, (int(rng.randint(2, 6)), 1)).astype("int64")
            for _ in range(6)]
    feed = {"x": seqs, "y": rng.normal(0, 1, (6, 1)).astype("float32")}

    def train(peep):
        main, startup, loss = build(peep)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                                scope=scope)[0]) for _ in range(40)]
        return main, scope, losses

    main, scope, losses = train(True)
    assert losses[-1] < 0.25 * losses[0], losses[::10]
    # the peephole bias is 7H wide and its diagonal weights trained away
    # from zero
    bias_name = [p.name for p in main.all_parameters()
                 if p.shape and p.shape[-1] == 7 * 8][0]
    b = np.asarray(scope.find_var(bias_name))
    assert np.abs(b[0, 4 * 8:]).max() > 1e-4
    # and the trajectory DIFFERS from the plain LSTM once peepholes move
    _, _, plain_losses = train(False)
    assert not np.allclose(losses[5:], plain_losses[5:], rtol=1e-4)


def test_simple_rnn_matches_numpy_and_trains():
    """Vanilla recurrence (v2 recurrent_layer): numpy-pinned forward over
    ragged lens, reversed variant, and gradient flow."""
    import paddle_tpu.fluid as fluid
    rng = np.random.RandomState(0)
    b, L, H = 3, 5, 4
    lens = np.array([5, 3, 4], "int32")
    seqs = [rng.normal(0, 1, (int(l), H)).astype("float32") for l in lens]

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[H], lod_level=1)
        out = fluid.layers.dynamic_vanilla_rnn(
            x, size=H, act="tanh",
            param_attr=fluid.ParamAttr(name="rw"),
            bias_attr=fluid.ParamAttr(name="rb"))
        rev = fluid.layers.dynamic_vanilla_rnn(
            x, size=H, act="tanh", is_reverse=True,
            param_attr=fluid.ParamAttr(name="rw"),
            bias_attr=fluid.ParamAttr(name="rb"))
        loss = fluid.layers.mean(fluid.layers.sequence_pool(out, "sum"))
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    got, got_rev, gw = exe.run(
        main, feed={"x": seqs}, fetch_list=[out, rev, "rw@GRAD"],
        scope=scope)

    w = np.asarray(scope.find_var("rw"))
    bias = np.asarray(scope.find_var("rb")).reshape(-1)

    def ref_run(seq):
        h = np.zeros(H, "float32")
        outs = []
        for t in range(len(seq)):
            h = np.tanh(seq[t] + bias + h @ w)
            outs.append(h)
        return np.stack(outs)

    from paddle_tpu.core.lod import lodarray_to_flat
    flat, _ = lodarray_to_flat(got)
    expect = np.concatenate([ref_run(s) for s in seqs])
    np.testing.assert_allclose(flat, expect, rtol=1e-5, atol=1e-6)

    # reversed recurrence = run on the flipped sequence, flip back
    flat_rev, _ = lodarray_to_flat(got_rev)
    expect_rev = np.concatenate([ref_run(s[::-1])[::-1] for s in seqs])
    np.testing.assert_allclose(flat_rev, expect_rev, rtol=1e-5, atol=1e-6)

    assert np.abs(np.asarray(gw)).sum() > 0  # gradient reaches the weight


def test_simple_rnn_without_bias():
    """bias_attr=False builds a bias-free recurrence (the reference
    recurrent_layer contract) and its parameter list has no bias."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], lod_level=1)
        out = fluid.layers.dynamic_vanilla_rnn(x, size=4, bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.sequence_pool(out, "sum"))
        fluid.append_backward(loss)
    names = [p.name for p in main.all_parameters()]
    assert len(names) == 1 and not any("b_0" in n for n in names), names
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    seqs = [np.ones((3, 4), "float32")]
    got, gb = exe.run(main, feed={"x": seqs},
                      fetch_list=[loss, names[0] + "@GRAD"], scope=scope)
    assert np.isfinite(float(got))
    # grad restores the (size, size) parameter shape
    assert np.asarray(gb).shape == (4, 4)


# ---------------------------------------------------------------------------
# lstm_grad from the forward's saved Hidden/Cell (the Pallas tier's path)
# ---------------------------------------------------------------------------

def _two_layer_lstm(hidden=8):
    """embedding -> fc -> lstm -> fc -> lstm(is_reverse) -> average pool
    -> fc -> mse; both lstm ops carry a bias. Returns the programs, the loss
    and, per lstm op, the names of its Input/Weight/Bias gradients."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    layers = fluid.layers
    framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[1], dtype="int64", lod_level=1)
        net = layers.embedding(x, size=[12, 6])
        for rev in (False, True):
            proj = layers.fc(net, size=hidden * 4)
            net, _ = layers.dynamic_lstm(proj, size=hidden * 4,
                                         is_reverse=rev)
        # every step's output feeds the loss (the reversed layer's LAST
        # step is its first and would not see the recurrent weight)
        pred = layers.fc(layers.sequence_pool(net, "average"), size=1)
        label = layers.data("y", shape=[1])
        loss = layers.mean(layers.square(
            layers.elementwise_sub(pred, label)))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss, startup)
    grads = [n + "@GRAD" for op in main.global_block().ops
             if op.type == "lstm"
             for n in (op.input("Input")[0], op.input("Weight")[0],
                       op.input("Bias")[0])]
    return main, startup, loss, grads


def _two_layer_feed():
    rng = np.random.RandomState(5)
    lens = (1, 6, 3, 4, 6)           # a length-1 row and full-length rows
    return {"x": [rng.randint(0, 12, (n, 1)).astype("int64") for n in lens],
            "y": rng.normal(0, 1, (len(lens), 1)).astype("float32")}


@pytest.fixture
def _tier_reset():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import pallas as tier
    yield
    fluid.set_flags({"kernel_tier": "auto"})
    tier.reset_fallback_counts()


def test_two_layer_lstm_grads_agree_across_tiers(_tier_reset):
    """Weight@GRAD, Input@GRAD and Bias@GRAD of both layers (one reversed)
    under kernel_tier=pallas — lstm_grad from the carries the forward op
    saved, the outer pieces transposed by hand — against kernel_tier=jnp, jax.vjp over the
    scan. The Pallas tier's products take bf16 operands where the CPU's
    jnp scan multiplies in float32, so the two agree to bf16's resolution
    (the kernel-level tests hold the tight 2e-4 against the bf16 twin);
    a wrong mask, reversal, bias sum or transpose is off by its own size."""
    import paddle_tpu.fluid as fluid

    def run(tier_name):
        fluid.set_flags({"kernel_tier": tier_name})
        main, startup, loss, grads = _two_layer_lstm()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        outs = exe.run(main, feed=_two_layer_feed(),
                       fetch_list=[loss] + grads, scope=scope,
                       return_numpy=False)
        vals = [np.asarray(getattr(o, "data", o)) for o in outs]
        return grads, vals[1:]

    names, base = run("jnp")
    _, got = run("pallas")
    assert len(names) == 6
    for name, a, b in zip(names, got, base):
        assert a.shape == b.shape, name
        scale = np.abs(b).max()
        assert scale > 0, name
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=4e-3 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("is_reverse", [False, True])
@pytest.mark.parametrize("with_state", [False, True])
def test_lstm_grad_from_carries_matches_vjp_of_compute(
        _tier_reset, is_reverse, with_state):
    """The outer pieces alone, tightly: the hand-written transposes around
    lstm_seq_bwd against jax.vjp over _lstm_compute under the SAME tier
    (both run the same whole-sequence backward inside), from the carries
    the forward saved."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import rnn_ops

    fluid.set_flags({"kernel_tier": "pallas"})
    rng = np.random.RandomState(7)
    b, L, H = 4, 5, 8
    lens = jnp.asarray([5, 1, 3, 5], jnp.int32)
    x = jnp.asarray(rng.normal(0, 1, (b, L, 4 * H)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.4, (H, 4 * H)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, (4 * H,)), jnp.float32)
    h0 = c0 = None
    if with_state:
        h0 = jnp.asarray(rng.normal(0, 1, (b, H)), jnp.float32)
        c0 = jnp.asarray(rng.normal(0, 1, (b, H)), jnp.float32)
    dhs = jnp.asarray(rng.normal(0, 1, (b, L, H)), jnp.float32)
    dcs = jnp.asarray(rng.normal(0, 1, (b, L, H)), jnp.float32)
    attrs = {"is_reverse": is_reverse}

    operands = [x, w, bias] + ([h0, c0] if with_state else [])

    def f(x, w, bias, h0=None, c0=None):
        return rnn_ops._lstm_compute(x, lens, w, bias, h0, c0, attrs)[:2]

    _, vjp = jax.vjp(f, *operands)
    exp = vjp((dhs, dcs))
    carries = rnn_ops._lstm_compute(x, lens, w, bias, h0, c0, attrs)[2]
    assert carries[0].shape == (L, b, H)
    got = rnn_ops._lstm_grad_from_carries(x, lens, w, bias, h0, c0, carries,
                                          dhs, dcs, attrs)
    for a, e, name in zip(got, exp, ("dx", "dw", "dbias", "dh0", "dc0")):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-6, err_msg=name)


def test_train_step_runs_each_lstm_kernel_once(_tier_reset):
    """The jaxpr of the whole train step holds one forward pallas_call for
    each lstm op and one backward one: lstm_grad starts from the saved
    BatchHidden/BatchCell and does not run the forward again."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.obs.perf import program_jaxpr

    fluid.set_flags({"kernel_tier": "pallas"})
    main, startup, loss, _ = _two_layer_lstm()
    n_lstm = sum(op.type == "lstm" for op in main.global_block().ops)
    assert n_lstm == 2
    exe = fluid.Executor(fluid.CPUPlace(), mode="jit")
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = exe._prepare_feed(main.global_block(), _two_layer_feed())
    text = str(program_jaxpr(main, feed, [loss], executor=exe, scope=scope))
    calls = text.count("pallas_call[")
    backward = text.count("name=lstm_bwd")
    assert backward == n_lstm, (backward, calls)
    assert calls - backward == n_lstm, (backward, calls)
