"""Recurrent ops: dynamic_lstm, dynamic_gru, lstm_unit, gru_unit.

Reference: /root/reference/paddle/fluid/operators/lstm_op.cc (dynamic LSTM
over a ragged batch reordered by math/sequence2batch.h, fused gate kernels in
math/detail/lstm_kernel.h), gru_op.cc, lstm_unit_op.cc, gru_unit_op.cc.

TPU-native design: the reference reorders the ragged batch time-major and
launches one fused CUDA kernel per step (hl_cuda_lstm.cu hand-scheduled
kernels); here each RNN is ONE ``lax.scan`` over the padded LoDArray with a
length mask — XLA fuses the gate math, and the scanned matmul hits the MXU.
Gate layouts (documented contract of this framework):

* LSTM projected input / recurrent weight column order: [i, f, c, o]
  (input, forget, candidate, output), weight shape [H, 4H]. NOTE: the
  reference stores [c, i, f, o] (lstm_op.cc:125) — reference-trained
  weights must be permuted via
  ``paddle_tpu.utils.convert_reference_lstm_weight`` on import.
* GRU projected input order: [u, r, c] (update, reset, candidate);
  weight [H, 3H] = [W_u | W_r | W_c] like the reference gru_op
  ("the first 2H columns are update/reset, the last H candidate").
  h_t = u * c_t + (1 - u) * h_{t-1}, matching the reference kernel
  ``h = u * (c - h_prev) + h_prev`` (gru_unit_op.h; math/detail/gru_kernel.h).

Gradients flow through ``jax.vjp`` over the scan (XLA reverse-scan), the
functional analog of the reference's hand-written LstmGradKernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.lod import LoDArray
from ..core.registry import register_op, OpSpec, same_shape
from .common import G, data_of


def _act(name):
    return {
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "relu": jax.nn.relu,
        "identity": lambda x: x,
    }[name or "identity"]


def _reverse_padded(data, lens):
    """Reverse each row's valid prefix in place (padding stays at the end):
    the is_reverse attr of lstm/gru ops."""
    L = data.shape[1]
    idx = lens[:, None] - 1 - jnp.arange(L)[None, :]
    valid = idx >= 0
    idx = jnp.where(valid, idx, jnp.arange(L)[None, :])
    idx = jnp.broadcast_to(
        idx.reshape(idx.shape + (1,) * (data.ndim - 2)),
        idx.shape + data.shape[2:]).astype(jnp.int32)
    return jnp.take_along_axis(data, idx, axis=1)


def _lstm_on_pallas(gate_act, cell_act, cand_act, has_peepholes):
    """Does this call's recurrence run the Pallas kernel? The forward op
    and its grad op ask the same question and get the same answer."""
    from .pallas import use_pallas

    # the Pallas fused cell implements the standard activation set (the
    # reference's hand-scheduled hl_cuda_lstm.cu does the same); other
    # activations / peepholes fall back to the scan with a counter bump
    return use_pallas("lstm", not has_peepholes
                      and (gate_act, cell_act, cand_act)
                      == ("sigmoid", "tanh", "tanh"))


def _alive_mask(L, lens, dtype):
    """[L, b, 1] prefix mask: 1 where step t is inside the row's length."""
    return (jnp.arange(L)[:, None] < lens[None, :]).astype(dtype)[..., None]


def _lstm_pallas(x, lens, w, h0, c0):
    """The whole-recurrence kernel: ONE launch for the full sequence with
    the recurrent weight VMEM-resident across steps (see
    ops/pallas/rnn.lstm_seq_pallas). Returns the masked hidden/cell
    [b, L, H] and the kernel's own carries [L, b, H], which lstm_grad
    starts from."""
    from .pallas import kernel_span
    from .pallas.rnn import lstm_seq_pallas

    with kernel_span("pallas", "lstm"):
        xt = jnp.swapaxes(x, 0, 1)                   # [L, b, 4H]
        alive = _alive_mask(x.shape[1], lens, x.dtype)
        hs, cs = lstm_seq_pallas(xt, alive, w, h0, c0)
        return (jnp.swapaxes(hs * alive, 0, 1),
                jnp.swapaxes(cs * alive, 0, 1), (hs, cs))


def _lstm_scan(x, lens, w, h0, c0, gate_act, cell_act, cand_act,
               peepholes=None):
    """x: [b, L, 4H] projected inputs (+bias already added); w: [H, 4H].
    ``peepholes``: optional (w_ic, w_fc, w_oc) each [H] — the reference's
    diagonal cell->gate connections (math/detail/lstm_kernel.h:37-40:
    i/f see the PREVIOUS cell state, o sees the NEW one). Returns
    hidden [b, L, H], cell [b, L, H]."""
    if _lstm_on_pallas(gate_act, cell_act, cand_act, peepholes is not None):
        return _lstm_pallas(x, lens, w, h0, c0)[:2]
    return _lstm_jnp_scan(x, lens, w, h0, c0, gate_act, cell_act, cand_act,
                          peepholes)


def _lstm_jnp_scan(x, lens, w, h0, c0, gate_act, cell_act, cand_act,
                   peepholes):
    """The jnp twin: one lax.scan over time, any activations, peepholes."""
    H = x.shape[-1] // 4
    ga, ca, cda = _act(gate_act), _act(cell_act), _act(cand_act)

    def step(carry, inp):
        h_prev, c_prev, t = carry
        xt = inp                                     # [b, 4H]
        gates = xt + h_prev @ w                      # MXU matmul
        alive = (t < lens)[:, None].astype(x.dtype)
        gi = gates[:, :H]
        gf = gates[:, H:2 * H]
        go = gates[:, 3 * H:]
        if peepholes is not None:
            w_ic, w_fc, w_oc = peepholes
            gi = gi + c_prev * w_ic[None, :]
            gf = gf + c_prev * w_fc[None, :]
        i = ga(gi)
        f = ga(gf)
        cand = cda(gates[:, 2 * H:3 * H])
        c = f * c_prev + i * cand
        if peepholes is not None:
            go = go + c * w_oc[None, :]
        o = ga(go)
        h = o * ca(c)
        h = alive * h + (1 - alive) * h_prev
        c = alive * c + (1 - alive) * c_prev
        return (h, c, t + 1), (h * alive, c * alive)

    xt = jnp.swapaxes(x, 0, 1)                       # [L, b, 4H]
    (_, _, _), (hs, cs) = jax.lax.scan(
        step, (h0, c0, jnp.zeros((), jnp.int32)), xt)
    return jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1)


def _lstm_acts(attrs):
    return (attrs.get("gate_activation", "sigmoid"),
            attrs.get("cell_activation", "tanh"),
            attrs.get("candidate_activation", "tanh"))


def _lstm_scan_inputs(x, lens, bias, h0, c0, attrs):
    """What the recurrence itself consumes: x with the gate bias added and,
    for is_reverse, each row's valid prefix reversed; zero initial states
    where none were given; the peephole weights out of a 7H bias."""
    b, L, H4 = x.shape
    H = H4 // 4
    peepholes = None
    if bias is not None:
        x = x + bias[None, None, :H4]
        if bias.shape[-1] == 7 * H:
            # reference bias layout with use_peepholes (lstm_op.cc:74):
            # [4H gate bias | W_ic | W_fc | W_oc]
            peepholes = (bias[4 * H:5 * H], bias[5 * H:6 * H],
                         bias[6 * H:7 * H])
    if h0 is None:
        h0 = jnp.zeros((b, H), x.dtype)
    if c0 is None:
        c0 = jnp.zeros((b, H), x.dtype)
    if attrs.get("is_reverse", False):
        x = _reverse_padded(x, lens)
    return x, h0, c0, peepholes


def _lstm_compute(x, lens, w, bias, h0, c0, attrs):
    """(hidden, cell, carries): the op's outputs [b, L, H] and, where the
    kernel ran, its carries [L, b, H] in the recurrence's own order (rows
    reversed for is_reverse); None on the jnp path."""
    x, h0, c0, peepholes = _lstm_scan_inputs(x, lens, bias, h0, c0, attrs)
    acts = _lstm_acts(attrs)
    carries = None
    if _lstm_on_pallas(*acts, peepholes is not None):
        hs, cs, carries = _lstm_pallas(x, lens, w, h0, c0)
    else:
        hs, cs = _lstm_jnp_scan(x, lens, w, h0, c0, *acts, peepholes)
    if attrs.get("is_reverse", False):
        hs = _reverse_padded(hs, lens)
        cs = _reverse_padded(cs, lens)
    return hs, cs, carries


def _lstm_grad_maker(op):
    inputs = {"Input": op.input("Input"), "Weight": op.input("Weight"),
              "Hidden@GRAD": G(op.output("Hidden")),
              "Cell@GRAD": G(op.output("Cell"))}
    # the carries the forward saved, where its op has the slots (the
    # reference's lstm_grad takes BatchGate/BatchCellPreAct the same way,
    # lstm_op.cc): the grad op then does not run the forward again
    for slot in ("BatchHidden", "BatchCell"):
        if op.output(slot):
            inputs[slot] = op.output(slot)
    outputs = {"Input@GRAD": G(op.input("Input")),
               "Weight@GRAD": G(op.input("Weight"))}
    for slot in ("Bias", "H0", "C0"):
        if op.input(slot):
            inputs[slot] = op.input(slot)
            outputs[slot + "@GRAD"] = G(op.input(slot))
    return [OpSpec("lstm_grad", inputs, outputs, dict(op.attrs))]


def _rnn_infer(out_slots):
    def infer(op, block):
        x = block.var(op.input("Input")[0])
        w = block.var(op.input("Weight")[0])
        if x.shape is None or w.shape is None:
            return
        H = w.shape[0]
        for slot in out_slots:
            for name in op.output(slot):
                v = block.var(name)
                v.shape = tuple(x.shape[:-1]) + (H,)
                v.dtype = x.dtype
                v.lod_level = x.lod_level
    return infer


@register_op("lstm", infer_shape=_rnn_infer(("Hidden", "Cell")),
             grad=_lstm_grad_maker)
def lstm(ctx):
    xv = ctx.input("Input")
    x = xv.data if isinstance(xv, LoDArray) else data_of(xv)
    lens = xv.lens if isinstance(xv, LoDArray) else \
        jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    w = data_of(ctx.input("Weight"))
    bias = data_of(ctx.input("Bias")) if ctx.has_input("Bias") else None
    if bias is not None:
        bias = bias.reshape(-1)
    h0 = data_of(ctx.input("H0")) if ctx.has_input("H0") else None
    c0 = data_of(ctx.input("C0")) if ctx.has_input("C0") else None
    hs, cs, carries = _lstm_compute(x, lens, w, bias, h0, c0, ctx.op.attrs)
    ctx.set_output("Hidden", LoDArray(hs, lens))
    ctx.set_output("Cell", LoDArray(cs, lens))
    if carries is not None:
        ctx.set_output("BatchHidden", carries[0])
        ctx.set_output("BatchCell", carries[1])


def _lstm_grad_from_carries(x, lens, w, bias, h0, c0, carries, dhs, dcs,
                            attrs):
    """The Pallas path's gradients from the carries its forward saved
    ([L, b, H], the recurrence's own order): the whole-sequence backward of
    ops/pallas/rnn.py inside, the transposes of _lstm_compute's outer
    pieces by hand around it. dhs/dcs are the gradients of Hidden/Cell
    [b, L, H]. Returns (dx, dw, dbias, dh0, dc0), dbias for the 4H gate
    bias."""
    from .pallas.rnn import lstm_seq_bwd

    x, h0, c0, _ = _lstm_scan_inputs(x, lens, bias, h0, c0, attrs)
    rev = attrs.get("is_reverse", False)
    if rev:
        # _reverse_padded permutes each row onto itself and is its own
        # inverse, hence its own transpose: the incoming gradients go back
        # to the recurrence's order the way x went there
        dhs, dcs = _reverse_padded(dhs, lens), _reverse_padded(dcs, lens)
    alive = _alive_mask(x.shape[1], lens, x.dtype)
    # the output mask's transpose is the same mask on the gradients
    dx, dw, dh0, dc0 = lstm_seq_bwd(
        jnp.swapaxes(x, 0, 1), alive, w, h0, c0, *carries,
        jnp.swapaxes(dhs, 0, 1) * alive, jnp.swapaxes(dcs, 0, 1) * alive)
    dx = jnp.swapaxes(dx, 0, 1)
    if rev:
        dx = _reverse_padded(dx, lens)
    return dx, dw, dx.sum((0, 1)), dh0, dc0


@register_op("lstm_grad")
def lstm_grad(ctx):
    xv = ctx.input("Input")
    x = xv.data if isinstance(xv, LoDArray) else data_of(xv)
    lens = xv.lens if isinstance(xv, LoDArray) else \
        jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    w = data_of(ctx.input("Weight"))
    attrs = dict(ctx.op.attrs)

    def gd(slot):
        v = ctx.input(slot)
        return v.data if isinstance(v, LoDArray) else data_of(v)

    # differentiate wrt every forward input the op actually consumed
    operands = {"Input": x, "Weight": w}
    if ctx.has_input("Bias"):
        operands["Bias"] = data_of(ctx.input("Bias")).reshape(-1)
    if ctx.has_input("H0"):
        operands["H0"] = data_of(ctx.input("H0"))
    if ctx.has_input("C0"):
        operands["C0"] = data_of(ctx.input("C0"))
    names = list(operands)
    if ctx.has_input("BatchHidden") and ctx.has_input("BatchCell"):
        # the forward ran the kernel: start from the carries it saved
        grads = dict(zip(
            ("Input", "Weight", "Bias", "H0", "C0"),
            _lstm_grad_from_carries(
                x, lens, w, operands.get("Bias"), operands.get("H0"),
                operands.get("C0"),
                (data_of(ctx.input("BatchHidden")),
                 data_of(ctx.input("BatchCell"))),
                gd("Hidden@GRAD"), gd("Cell@GRAD"), attrs)))
    else:
        # the jnp twin's path (peepholes, other activations, the CPU; an
        # lstm op built without the two slots): jax.vjp over the scan,
        # which saves its own residuals
        def f(*args):
            kw = dict(zip(names, args))
            return _lstm_compute(kw["Input"], lens, kw["Weight"],
                                 kw.get("Bias"), kw.get("H0"), kw.get("C0"),
                                 attrs)[:2]

        _, vjp = jax.vjp(f, *operands.values())
        grads = dict(zip(names, vjp((gd("Hidden@GRAD"), gd("Cell@GRAD")))))
    dx = grads["Input"]
    ctx.set_output("Input@GRAD",
                   LoDArray(dx, lens) if isinstance(xv, LoDArray) else dx)
    ctx.set_output("Weight@GRAD", grads["Weight"])
    for slot in ("Bias", "H0", "C0"):
        if slot in operands:
            g = grads[slot]
            ctx.set_output(slot + "@GRAD",
                           g.reshape(1, -1) if slot == "Bias" else g)


# ---------------------------------------------------------------------------
# dynamic GRU
# ---------------------------------------------------------------------------

def _gru_compute(x, lens, w, bias, h0, attrs):
    b, L, H3 = x.shape
    H = H3 // 3
    if bias is not None:
        x = x + bias[None, None, :]
    if h0 is None:
        h0 = jnp.zeros((b, H), x.dtype)
    ga = _act(attrs.get("gate_activation", "sigmoid"))
    ca = _act(attrs.get("activation", "tanh"))
    wu, wr, wc = w[:, :H], w[:, H:2 * H], w[:, 2 * H:]
    rev = attrs.get("is_reverse", False)
    if rev:
        x = _reverse_padded(x, lens)

    from .pallas import kernel_span, use_pallas
    if use_pallas("gru",
                  attrs.get("gate_activation", "sigmoid") == "sigmoid"
                  and attrs.get("activation", "tanh") == "tanh"):
        # whole-recurrence kernel (see ops/pallas/rnn.gru_seq_pallas)
        from .pallas.rnn import gru_seq_pallas
        with kernel_span("pallas", "gru"):
            xs = jnp.swapaxes(x, 0, 1)               # [L, b, 3H]
            alive = _alive_mask(L, lens, x.dtype)
            hs = gru_seq_pallas(xs, alive, w, h0) * alive
            hs = jnp.swapaxes(hs, 0, 1)
        if rev:
            hs = _reverse_padded(hs, lens)
        return hs

    def step(carry, inp):
        h_prev, t = carry
        xt = inp
        alive = (t < lens)[:, None].astype(x.dtype)
        r = ga(xt[:, H:2 * H] + h_prev @ wr)
        rc = (r * h_prev) @ wc                       # MXU matmul
        u = ga(xt[:, :H] + h_prev @ wu)
        c = ca(xt[:, 2 * H:] + rc)
        h = u * c + (1.0 - u) * h_prev
        h = alive * h + (1 - alive) * h_prev
        return (h, t + 1), h * alive

    xt = jnp.swapaxes(x, 0, 1)
    _, hs = jax.lax.scan(step, (h0, jnp.zeros((), jnp.int32)), xt)
    hs = jnp.swapaxes(hs, 0, 1)
    if rev:
        hs = _reverse_padded(hs, lens)
    return hs


def _gru_grad_maker(op):
    inputs = {"Input": op.input("Input"), "Weight": op.input("Weight"),
              "Hidden@GRAD": G(op.output("Hidden"))}
    outputs = {"Input@GRAD": G(op.input("Input")),
               "Weight@GRAD": G(op.input("Weight"))}
    for slot in ("Bias", "H0"):
        if op.input(slot):
            inputs[slot] = op.input(slot)
            outputs[slot + "@GRAD"] = G(op.input(slot))
    return [OpSpec("gru_grad", inputs, outputs, dict(op.attrs))]


@register_op("gru", infer_shape=_rnn_infer(("Hidden",)), grad=_gru_grad_maker)
def gru(ctx):
    xv = ctx.input("Input")
    x = xv.data if isinstance(xv, LoDArray) else data_of(xv)
    lens = xv.lens if isinstance(xv, LoDArray) else \
        jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    w = data_of(ctx.input("Weight"))
    bias = data_of(ctx.input("Bias")).reshape(-1) \
        if ctx.has_input("Bias") else None
    h0 = data_of(ctx.input("H0")) if ctx.has_input("H0") else None
    hs = _gru_compute(x, lens, w, bias, h0, ctx.op.attrs)
    ctx.set_output("Hidden", LoDArray(hs, lens))


@register_op("gru_grad")
def gru_grad(ctx):
    xv = ctx.input("Input")
    x = xv.data if isinstance(xv, LoDArray) else data_of(xv)
    lens = xv.lens if isinstance(xv, LoDArray) else \
        jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    w = data_of(ctx.input("Weight"))
    dh = ctx.input("Hidden@GRAD")
    dh_data = dh.data if isinstance(dh, LoDArray) else data_of(dh)
    attrs = dict(ctx.op.attrs)

    operands = {"Input": x, "Weight": w}
    if ctx.has_input("Bias"):
        operands["Bias"] = data_of(ctx.input("Bias")).reshape(-1)
    if ctx.has_input("H0"):
        operands["H0"] = data_of(ctx.input("H0"))
    names = list(operands)

    def f(*args):
        kw = dict(zip(names, args))
        return _gru_compute(kw["Input"], lens, kw["Weight"], kw.get("Bias"),
                            kw.get("H0"), attrs)

    _, vjp = jax.vjp(f, *operands.values())
    grads = dict(zip(names, vjp(dh_data)))
    dx = grads["Input"]
    ctx.set_output("Input@GRAD",
                   LoDArray(dx, lens) if isinstance(xv, LoDArray) else dx)
    ctx.set_output("Weight@GRAD", grads["Weight"])
    if "Bias" in grads:
        ctx.set_output("Bias@GRAD", grads["Bias"].reshape(1, -1))
    if "H0" in grads:
        ctx.set_output("H0@GRAD", grads["H0"])


# ---------------------------------------------------------------------------
# single-step units (StaticRNN building blocks)
# ---------------------------------------------------------------------------

@register_op("lstm_unit", grad=lambda op: [OpSpec(
    "lstm_unit_grad",
    {"X": op.input("X"), "C_prev": op.input("C_prev"),
     "C@GRAD": G(op.output("C")), "H@GRAD": G(op.output("H"))},
    {"X@GRAD": G(op.input("X")), "C_prev@GRAD": G(op.input("C_prev"))},
    dict(op.attrs))])
def lstm_unit(ctx):
    """One fused LSTM cell step: X=[b,4H] pre-activations, C_prev=[b,H]
    (lstm_unit_op.cc; forget_bias attr added into the forget gate)."""
    x = data_of(ctx.input("X"))
    c_prev = data_of(ctx.input("C_prev"))
    H = c_prev.shape[-1]
    fb = ctx.attr("forget_bias", 0.0)
    i = jax.nn.sigmoid(x[:, :H])
    f = jax.nn.sigmoid(x[:, H:2 * H] + fb)
    cand = jnp.tanh(x[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(x[:, 3 * H:])
    c = f * c_prev + i * cand
    h = o * jnp.tanh(c)
    ctx.set_output("C", c)
    ctx.set_output("H", h)


def _lstm_unit_fwd(x, c_prev, fb):
    H = c_prev.shape[-1]
    i = jax.nn.sigmoid(x[:, :H])
    f = jax.nn.sigmoid(x[:, H:2 * H] + fb)
    cand = jnp.tanh(x[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(x[:, 3 * H:])
    c = f * c_prev + i * cand
    return c, o * jnp.tanh(c)


@register_op("lstm_unit_grad")
def lstm_unit_grad(ctx):
    x = data_of(ctx.input("X"))
    c_prev = data_of(ctx.input("C_prev"))
    fb = ctx.attr("forget_bias", 0.0)
    dc = data_of(ctx.input("C@GRAD"))
    dh = data_of(ctx.input("H@GRAD"))
    _, vjp = jax.vjp(lambda a, b: _lstm_unit_fwd(a, b, fb), x, c_prev)
    dx, dcp = vjp((dc, dh))
    ctx.set_output("X@GRAD", dx)
    ctx.set_output("C_prev@GRAD", dcp)


def _gru_unit_fwd(x, h_prev, w, bias, gate_act, cand_act):
    H = h_prev.shape[-1]
    if bias is not None:
        x = x + bias.reshape(1, -1)
    u = gate_act(x[:, :H] + h_prev @ w[:, :H])
    r = gate_act(x[:, H:2 * H] + h_prev @ w[:, H:2 * H])
    c = cand_act(x[:, 2 * H:] + (r * h_prev) @ w[:, 2 * H:])
    h = u * c + (1.0 - u) * h_prev
    return u, r, c, h


def _gru_unit_grad_maker(op):
    inputs = {"Input": op.input("Input"), "HiddenPrev": op.input("HiddenPrev"),
              "Weight": op.input("Weight"),
              "Hidden@GRAD": G(op.output("Hidden"))}
    outputs = {"Input@GRAD": G(op.input("Input")),
               "HiddenPrev@GRAD": G(op.input("HiddenPrev")),
               "Weight@GRAD": G(op.input("Weight"))}
    if op.input("Bias"):
        inputs["Bias"] = op.input("Bias")
        outputs["Bias@GRAD"] = G(op.input("Bias"))
    return [OpSpec("gru_unit_grad", inputs, outputs, dict(op.attrs))]


def _gru_unit_acts(ctx):
    """Resolve the gate/candidate activations; the reference gru_unit_op
    encodes them as enum ints (0 identity, 1 sigmoid, 2 tanh, 3 relu) while
    the layer API passes strings — accept both."""
    codes = {0: "identity", 1: "sigmoid", 2: "tanh", 3: "relu"}

    def resolve(attr, default):
        v = ctx.attr(attr, default)
        return _act(codes[v] if isinstance(v, int) else v)

    return resolve("gate_activation", "sigmoid"), resolve("activation", "tanh")


@register_op("gru_unit", grad=_gru_unit_grad_maker)
def gru_unit(ctx):
    x = data_of(ctx.input("Input"))
    h_prev = data_of(ctx.input("HiddenPrev"))
    w = data_of(ctx.input("Weight"))
    bias = data_of(ctx.input("Bias")) if ctx.has_input("Bias") else None
    ga, ca = _gru_unit_acts(ctx)
    u, r, c, h = _gru_unit_fwd(x, h_prev, w, bias, ga, ca)
    ctx.set_output("Gate", jnp.concatenate([u, r, c], axis=-1))
    ctx.set_output("ResetHiddenPrev", r * h_prev)
    ctx.set_output("Hidden", h)


@register_op("gru_unit_grad")
def gru_unit_grad(ctx):
    x = data_of(ctx.input("Input"))
    h_prev = data_of(ctx.input("HiddenPrev"))
    w = data_of(ctx.input("Weight"))
    has_bias = ctx.has_input("Bias")
    bias = data_of(ctx.input("Bias")) if has_bias else None
    dh = data_of(ctx.input("Hidden@GRAD"))
    ga, ca = _gru_unit_acts(ctx)

    if has_bias:
        _, vjp = jax.vjp(
            lambda a, b, ww, bb: _gru_unit_fwd(a, b, ww, bb, ga, ca)[3],
            x, h_prev, w, bias)
        dx, dhp, dw, db = vjp(dh)
        ctx.set_output("Bias@GRAD", db)
    else:
        _, vjp = jax.vjp(
            lambda a, b, ww: _gru_unit_fwd(a, b, ww, None, ga, ca)[3],
            x, h_prev, w)
        dx, dhp, dw = vjp(dh)
    ctx.set_output("Input@GRAD", dx)
    ctx.set_output("HiddenPrev@GRAD", dhp)
    ctx.set_output("Weight@GRAD", dw)


# ---------------------------------------------------------------------------
# lstmp — LSTM with recurrent projection (reference lstmp_op.{cc,h}:
# r_t = proj_act(P^T h_t); the recurrence runs over the PROJECTED state,
# Weight [P, 4H], ProjWeight [H, P]; outputs Projection + Cell)
# ---------------------------------------------------------------------------

def _lstmp_compute(x, lens, w, proj_w, bias, h0, c0, attrs):
    b, L, H4 = x.shape
    H = H4 // 4
    P = proj_w.shape[1]
    if bias is not None:
        x = x + bias[None, None, :H4]
    ga = _act(attrs.get("gate_activation", "sigmoid"))
    ca = _act(attrs.get("cell_activation", "tanh"))
    cda = _act(attrs.get("candidate_activation", "tanh"))
    pa = _act(attrs.get("proj_activation", "tanh"))
    r0 = jnp.zeros((b, P), x.dtype) if h0 is None else h0 @ proj_w
    c0 = jnp.zeros((b, H), x.dtype) if c0 is None else c0
    rev = attrs.get("is_reverse", False)
    if rev:
        x = _reverse_padded(x, lens)

    def step(carry, inp):
        r_prev, c_prev, t = carry
        gates = inp + r_prev @ w                    # w: [P, 4H]
        i = ga(gates[:, :H])
        f = ga(gates[:, H:2 * H])
        cand = cda(gates[:, 2 * H:3 * H])
        o = ga(gates[:, 3 * H:])
        c = f * c_prev + i * cand
        h = o * ca(c)
        r = pa(h @ proj_w)                          # [b, P]
        alive = (t < lens)[:, None].astype(x.dtype)
        r = alive * r + (1 - alive) * r_prev
        c = alive * c + (1 - alive) * c_prev
        return (r, c, t + 1), (r * alive, c * alive)

    xt = jnp.swapaxes(x, 0, 1)
    _, (rs, cs) = jax.lax.scan(step, (r0, c0, jnp.zeros((), jnp.int32)), xt)
    rs = jnp.swapaxes(rs, 0, 1)
    cs = jnp.swapaxes(cs, 0, 1)
    if rev:
        rs = _reverse_padded(rs, lens)
        cs = _reverse_padded(cs, lens)
    return rs, cs


def _lstmp_grad_maker(op):
    inputs = {"Input": op.input("Input"), "Weight": op.input("Weight"),
              "ProjWeight": op.input("ProjWeight"),
              "Projection@GRAD": G(op.output("Projection")),
              "Cell@GRAD": G(op.output("Cell"))}
    outputs = {"Input@GRAD": G(op.input("Input")),
               "Weight@GRAD": G(op.input("Weight")),
               "ProjWeight@GRAD": G(op.input("ProjWeight"))}
    for slot in ("Bias", "H0", "C0"):
        if op.input(slot):
            inputs[slot] = op.input(slot)
            outputs[slot + "@GRAD"] = G(op.input(slot))
    return [OpSpec("lstmp_grad", inputs, outputs, dict(op.attrs))]


def _lstmp_infer(op, block):
    x = block.var(op.input("Input")[0])
    w = block.var(op.input("Weight")[0])
    pw = block.var(op.input("ProjWeight")[0])
    if x.shape is None or w.shape is None or pw.shape is None:
        return
    H, P = pw.shape
    for slot, width in (("Projection", P), ("Cell", H)):
        for name in op.output(slot):
            v = block.var(name)
            v.shape = tuple(x.shape[:-1]) + (width,)
            v.dtype = x.dtype
            v.lod_level = x.lod_level


@register_op("lstmp", infer_shape=_lstmp_infer, grad=_lstmp_grad_maker)
def lstmp(ctx):
    xv = ctx.input("Input")
    x = xv.data if isinstance(xv, LoDArray) else data_of(xv)
    lens = xv.lens if isinstance(xv, LoDArray) else \
        jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    w = data_of(ctx.input("Weight"))
    proj_w = data_of(ctx.input("ProjWeight"))
    bias = data_of(ctx.input("Bias")).reshape(-1) \
        if ctx.has_input("Bias") else None
    h0 = data_of(ctx.input("H0")) if ctx.has_input("H0") else None
    c0 = data_of(ctx.input("C0")) if ctx.has_input("C0") else None
    rs, cs = _lstmp_compute(x, lens, w, proj_w, bias, h0, c0, ctx.op.attrs)
    ctx.set_output("Projection", LoDArray(rs, lens))
    ctx.set_output("Cell", LoDArray(cs, lens))


@register_op("lstmp_grad")
def lstmp_grad(ctx):
    xv = ctx.input("Input")
    x = xv.data if isinstance(xv, LoDArray) else data_of(xv)
    lens = xv.lens if isinstance(xv, LoDArray) else \
        jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    attrs = dict(ctx.op.attrs)
    operands = {"Input": x, "Weight": data_of(ctx.input("Weight")),
                "ProjWeight": data_of(ctx.input("ProjWeight"))}
    if ctx.has_input("Bias"):
        operands["Bias"] = data_of(ctx.input("Bias")).reshape(-1)
    if ctx.has_input("H0"):
        operands["H0"] = data_of(ctx.input("H0"))
    if ctx.has_input("C0"):
        operands["C0"] = data_of(ctx.input("C0"))
    names = list(operands)

    def f(*args):
        kw = dict(zip(names, args))
        return _lstmp_compute(kw["Input"], lens, kw["Weight"],
                              kw["ProjWeight"], kw.get("Bias"),
                              kw.get("H0"), kw.get("C0"), attrs)

    def gd(slot):
        v = ctx.input(slot)
        return v.data if isinstance(v, LoDArray) else data_of(v)

    outs, vjp = jax.vjp(f, *[operands[n] for n in names])
    d_rs = gd("Projection@GRAD").astype(outs[0].dtype)
    d_cs = gd("Cell@GRAD").astype(outs[1].dtype)
    grads = vjp((d_rs.reshape(outs[0].shape), d_cs.reshape(outs[1].shape)))
    for n, g in zip(names, grads):
        if n == "Input":
            ctx.set_output("Input@GRAD",
                           LoDArray(g, lens) if isinstance(xv, LoDArray)
                           else g)
        elif n == "Bias":
            # restore the (1, 4H) parameter shape (lstm_grad does the same)
            ctx.set_output("Bias@GRAD", g.reshape(1, -1))
        else:
            ctx.set_output(n + "@GRAD", g)


# ---------------------------------------------------------------------------
# simple_rnn — the vanilla recurrence of the legacy recurrent_layer
# (reference gserver/layers/RecurrentLayer.cpp: h_t = act(x_t + h_{t-1} W
# + b); there is no standalone fluid op for it — the fluid generation
# reached it through StaticRNN blocks — so this TPU-native op gives the
# v2 DSL's recurrent_layer a direct scan lowering)
# ---------------------------------------------------------------------------

def _simple_rnn_compute(x, lens, w, bias, h0, attrs):
    b, L, H = x.shape
    act = _act(attrs.get("activation", "tanh"))
    rev = bool(attrs.get("is_reverse", False))
    if bias is not None:
        x = x + bias[None, None, :]
    if h0 is None:
        h0 = jnp.zeros((b, H), x.dtype)
    if rev:
        # reversed recurrence over ragged rows: flip the VALID prefix of
        # each row (the reference runs the layer backwards per sequence)
        x = _reverse_padded(x, lens)
    xt = jnp.swapaxes(x, 0, 1)                        # [L, b, H]

    def step(carry, inp):
        h_prev, t = carry
        h = act(inp + h_prev @ w)
        alive = (t < lens)[:, None].astype(x.dtype)
        h = alive * h + (1 - alive) * h_prev
        return (h, t + 1), h * alive

    (_, _), hs = jax.lax.scan(step, (h0, jnp.zeros((), jnp.int32)), xt)
    hs = jnp.swapaxes(hs, 0, 1)
    if rev:
        hs = _reverse_padded(hs, lens)
    return hs


def _simple_rnn_grad_maker(op):
    inputs = {"Input": op.input("Input"), "Weight": op.input("Weight"),
              "Out@GRAD": G(op.output("Out"))}
    outputs = {"Input@GRAD": G(op.input("Input")),
               "Weight@GRAD": G(op.input("Weight"))}
    if op.input("Bias"):
        inputs["Bias"] = op.input("Bias")
        outputs["Bias@GRAD"] = G(op.input("Bias"))
    return [OpSpec("simple_rnn_grad", inputs, outputs, dict(op.attrs))]


@register_op("simple_rnn", infer_shape=_rnn_infer(("Out",)),
             grad=_simple_rnn_grad_maker)
def simple_rnn(ctx):
    xv = ctx.input("Input")
    x = xv.data if isinstance(xv, LoDArray) else data_of(xv)
    lens = xv.lens if isinstance(xv, LoDArray) else \
        jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    w = data_of(ctx.input("Weight"))
    bias = data_of(ctx.input("Bias")).reshape(-1) \
        if ctx.has_input("Bias") else None
    hs = _simple_rnn_compute(x, lens, w, bias, None, ctx.op.attrs)
    ctx.set_output("Out", LoDArray(hs, lens))


@register_op("simple_rnn_grad")
def simple_rnn_grad(ctx):
    xv = ctx.input("Input")
    x = xv.data if isinstance(xv, LoDArray) else data_of(xv)
    lens = xv.lens if isinstance(xv, LoDArray) else \
        jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    attrs = dict(ctx.op.attrs)
    operands = {"Input": x, "Weight": data_of(ctx.input("Weight"))}
    if ctx.has_input("Bias"):
        operands["Bias"] = data_of(ctx.input("Bias")).reshape(-1)
    names = list(operands)

    def f(*args):
        kw = dict(zip(names, args))
        return _simple_rnn_compute(kw["Input"], lens, kw["Weight"],
                                   kw.get("Bias"), None, attrs)

    dyv = ctx.input("Out@GRAD")
    dy = dyv.data if isinstance(dyv, LoDArray) else data_of(dyv)
    _, vjp = jax.vjp(f, *operands.values())
    grads = dict(zip(names, vjp(dy)))
    ctx.set_output("Input@GRAD", LoDArray(grads["Input"], lens))
    ctx.set_output("Weight@GRAD", grads["Weight"])
    if "Bias" in grads:
        # restore the (1, H) parameter shape
        ctx.set_output("Bias@GRAD", grads["Bias"].reshape(1, -1))
