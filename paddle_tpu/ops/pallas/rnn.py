"""Whole-recurrence LSTM/GRU Pallas kernels (the hand-tuned RNN hot spots).

The reference hand-schedules fused CUDA kernels for exactly these spots
(/root/reference/paddle/cuda/src/hl_cuda_lstm.cu, hl_gpu_lstm.cuh); the
Pallas analogs go further than per-cell fusion: the LSTM/GRU run their
WHOLE sequence as one kernel — grid over time, recurrent weight
VMEM-resident across steps (lax.scan re-reads it from HBM every
iteration), h/c carries in VMEM scratch, bf16 MXU gate matmuls with f32
accumulation.

The LSTM's backward is whole-sequence too (PR 27): from the carries the
forward saved, a second kernel runs the recurrence in reverse (the
forward's mirror: w resident for both products, dh/dc carries in VMEM)
and emits dx; the weight gradient is one product over the sequence after
it. On a TPU v5 lite at the benchmark's LSTM cells (batch 256, hidden
512; PERF.md section 5): forward 5.0 us and backward 9.7 us a recurrent
step at length 512, against 27.6 us for the same backward as a lax.scan
and 28 us for the per-step-vjp scan it replaced (which re-ran the forward
kernel besides); the training step of length 512 went from 62.3 to
41.0 ms — lstm is in AUTO_PALLAS. The GRU
recurrence runs 1.61x its scan twin (PR 21) but its backward is still a
reverse lax.scan of per-step vjps carrying dw, and no cell runs it, so gru
is not.

Numerics incl. all gradients are pinned against jnp twins
(tests/test_pallas_kernels.py, interpret mode on CPU, native on TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import kernel_span, on_cpu as _on_cpu, record_fallback


def _lstm_cell_jnp(gates, c_prev, h_prev, alive):
    hdim = gates.shape[-1] // 4
    i = jax.nn.sigmoid(gates[:, :hdim])
    f = jax.nn.sigmoid(gates[:, hdim:2 * hdim])
    cand = jnp.tanh(gates[:, 2 * hdim:3 * hdim])
    o = jax.nn.sigmoid(gates[:, 3 * hdim:])
    c = f * c_prev + i * cand
    h = o * jnp.tanh(c)
    return (alive * h + (1 - alive) * h_prev,
            alive * c + (1 - alive) * c_prev)


# ---------------------------------------------------------------------------
# Whole-recurrence LSTM: one kernel for the ENTIRE sequence
# ---------------------------------------------------------------------------

def _lstm_seq_kernel(x_ref, alive_ref, w_ref, h0_ref, c0_ref,
                     hs_ref, cs_ref, h_s, c_s):
    """Grid over time. The recurrent weight w stays VMEM-resident across
    every grid step (XLA's lax.scan body re-reads it from HBM each
    iteration — for hid 512 that is ~4 MB x seq_len per layer) and the h/c
    carries live in VMEM scratch, so the whole recurrence is ONE kernel
    launch instead of seq_len (matmul + fusion) pairs. The per-step matmul
    runs on the MXU in bf16 with f32 accumulation (the lane's
    default_matmul_precision contract)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_s[...] = h0_ref[...]
        c_s[...] = c0_ref[...]

    h_prev = h_s[...]
    c_prev = c_s[...]
    gates = x_ref[0] + jax.lax.dot(
        h_prev.astype(w_ref.dtype), w_ref[...],
        preferred_element_type=jnp.float32).astype(h_prev.dtype)
    hdim = h_prev.shape[-1]
    alive = alive_ref[0]
    i = jax.nn.sigmoid(gates[:, :hdim])
    f = jax.nn.sigmoid(gates[:, hdim:2 * hdim])
    cand = jnp.tanh(gates[:, 2 * hdim:3 * hdim])
    o = jax.nn.sigmoid(gates[:, 3 * hdim:])
    c = f * c_prev + i * cand
    h = o * jnp.tanh(c)
    h = alive * h + (1 - alive) * h_prev
    c = alive * c + (1 - alive) * c_prev
    h_s[...] = h
    c_s[...] = c
    hs_ref[0] = h
    cs_ref[0] = c


def _lstm_seq_fwd_pallas(x, alive, w, h0, c0):
    """x [L, b, 4H] (projected inputs + bias), alive [L, b, 1] float,
    w [H, 4H]; returns CARRY sequences hs/cs [L, b, H] (unmasked — the
    caller applies the output mask)."""
    from jax.experimental.pallas import tpu as pltpu

    L, b, H4 = x.shape
    H = H4 // 4
    wb = w.astype(jnp.bfloat16)   # MXU operand; bf16 halves its VMEM stay
    return pl.pallas_call(
        _lstm_seq_kernel,
        grid=(L,),
        in_specs=[
            pl.BlockSpec((1, b, H4), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, b, 1), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
            pl.BlockSpec((b, H), lambda t: (0, 0)),
            pl.BlockSpec((b, H), lambda t: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, b, H), lambda t: (t, 0, 0)),
                   pl.BlockSpec((1, b, H), lambda t: (t, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((L, b, H), x.dtype),
                   jax.ShapeDtypeStruct((L, b, H), x.dtype)],
        scratch_shapes=[pltpu.VMEM((b, H), x.dtype),
                        pltpu.VMEM((b, H), x.dtype)],
        interpret=_on_cpu(),
    )(x, alive, wb, h0, c0)


def _mxu_dot(a, b, contract=((1,), (0,))):
    """The lane's product: bf16 operands, float32 accumulation."""
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), (contract, ((), ())),
        preferred_element_type=jnp.float32)


@jax.custom_vjp
def _gate_matmul(h_prev, w):
    return _mxu_dot(h_prev, w)


def _gate_matmul_bwd(res, g):
    # both transposed products by the same recipe, float32 results. JAX's
    # own transpose of a bf16-typed dot would round each result to bf16
    # (the cotangent of a bf16 operand is bf16): per step, before the sum
    # over time — a precision the whole-sequence backward does not have.
    h_prev, w = res
    return (_mxu_dot(g, w, ((1,), (1,))).astype(h_prev.dtype),
            _mxu_dot(h_prev, g, ((0,), (0,))).astype(w.dtype))


_gate_matmul.defvjp(lambda h_prev, w: (_mxu_dot(h_prev, w), (h_prev, w)),
                    _gate_matmul_bwd)


def _lstm_step_jnp(xt, h_prev, c_prev, w, alive):
    """One reference step on CARRIES (the jnp twin whose jax.grad pins the
    kernels' gradients): the bf16-MXU gate matmul + the shared cell math.
    Returns (h_carry, c_carry)."""
    gates = xt + _gate_matmul(h_prev, w).astype(h_prev.dtype)
    return _lstm_cell_jnp(gates, c_prev, h_prev, alive)


# ---------------------------------------------------------------------------
# Whole-sequence LSTM backward, from the forward's saved carries
# ---------------------------------------------------------------------------

def _lstm_bwd_step(xt, alive, h_prev, c_prev, wb, dh, dc):
    """One step of the reverse recurrence, shared by the kernel body and
    the scan. ``dh``/``dc`` are the gradients of step t's CARRIES (the
    next step's plus the output's); gates are recomputed from
    x[t] + h[t-1] @ w exactly as the forward computed them. Returns
    (dgates, dh_prev, dc_prev); since gates = x[t] + ..., dgates IS dx[t].
    ``wb`` [H, 4H] bf16 serves both products (the second contracts its 4H
    axis: no transposed copy)."""
    gates = xt + _mxu_dot(h_prev, wb).astype(h_prev.dtype)
    hdim = h_prev.shape[-1]
    i = jax.nn.sigmoid(gates[:, :hdim])
    f = jax.nn.sigmoid(gates[:, hdim:2 * hdim])
    cand = jnp.tanh(gates[:, 2 * hdim:3 * hdim])
    o = jax.nn.sigmoid(gates[:, 3 * hdim:])
    tc = jnp.tanh(f * c_prev + i * cand)
    dh_new = alive * dh
    dc_new = alive * dc + dh_new * o * (1 - tc * tc)
    dgates = jnp.concatenate(
        [dc_new * cand * i * (1 - i), dc_new * c_prev * f * (1 - f),
         dc_new * i * (1 - cand * cand), dh_new * tc * o * (1 - o)], axis=-1)
    dh_prev = (1 - alive) * dh \
        + _mxu_dot(dgates, wb, ((1,), (1,))).astype(dh.dtype)
    dc_prev = dc_new * f + (1 - alive) * dc
    return dgates, dh_prev, dc_prev


def _lstm_seq_bwd_scan(x, alive, wb, h0, c0, hs, cs, dhs, dcs):
    """The reverse recurrence as a lax.scan carrying (dh, dc) only: what
    the kernel below falls back to where its blocks do not fit VMEM."""
    h_prevs = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    c_prevs = jnp.concatenate([c0[None], cs[:-1]], axis=0)

    def bstep(carry, inp):
        xt, at, hp, cp, dh_out, dc_out = inp
        dxt, dhp, dcp = _lstm_bwd_step(xt, at, hp, cp, wb,
                                       carry[0] + dh_out, carry[1] + dc_out)
        return (dhp, dcp), dxt

    (dh0, dc0), dx = jax.lax.scan(
        bstep, (jnp.zeros_like(h0), jnp.zeros_like(c0)),
        (x, alive, h_prevs, c_prevs, dhs, dcs), reverse=True)
    return dx, dh0, dc0


def _lstm_seq_bwd_kernel(x_ref, alive_ref, hp_ref, cp_ref, dhs_ref, dcs_ref,
                         w_ref, h0_ref, c0_ref, dx_ref, dh_ref, dc_ref):
    """The forward kernel's mirror: grid over time in REVERSE (grid step i
    is time L-1-i), w VMEM-resident for both products, the dh/dc carries
    live in the resident dh0/dc0 output blocks (same block every step, so
    they are written back once, after the last). hp/cp are hs/cs one step
    back (the index map clamps at 0; step 0 takes h0/c0 instead)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    first = i == pl.num_programs(0) - 1              # time step 0
    h_prev = jnp.where(first, h0_ref[...], hp_ref[0])
    c_prev = jnp.where(first, c0_ref[...], cp_ref[0])
    dgates, dh_prev, dc_prev = _lstm_bwd_step(
        x_ref[0], alive_ref[0], h_prev, c_prev, w_ref[...],
        dh_ref[...] + dhs_ref[0], dc_ref[...] + dcs_ref[0])
    dx_ref[0] = dgates
    dh_ref[...] = dh_prev
    dc_ref[...] = dc_prev


# The scoped-VMEM limit the backward kernel asks of Mosaic and the budget
# lstm_bwd_fits() admits shapes against — one number, as in conv_bn.py. The
# compiler's default (16 MiB on a v5e) is what the FORWARD kernel runs out
# of at batch 512; the backward streams twice the bytes a step and would
# not fit it at batch 256.
_BWD_VMEM_LIMIT = 64 * 1024 * 1024


def lstm_bwd_vmem_bytes(b, H, itemsize=4):
    """VMEM bytes of one grid step of the backward kernel, from the shape:
    every block double-buffered (the resident ones too: Pallas allocates
    two), plus the step's temporaries, about two [b, 4H] values (gates and
    dgates with its bf16 copy; the [b, H] gate values reuse them). At
    hidden 512 this counts 24.25 MiB for batch 256 and 85.0 MiB for 1024,
    where Mosaic's own scoped allocation reads 23.05M and 82.19M."""
    row4, row1 = b * 4 * H * itemsize, b * H * itemsize
    streamed = 2 * row4 + 4 * row1 + b * 128 * itemsize   # x, dx, 4 carries,
    resident = H * 4 * H * 2 + 4 * row1                   # alive (lane-padded)
    return 2 * (streamed + resident) + 2 * row4


def lstm_bwd_fits(b, H, itemsize=4):
    return lstm_bwd_vmem_bytes(b, H, itemsize) <= _BWD_VMEM_LIMIT


def _lstm_seq_bwd_pallas(x, alive, wb, h0, c0, hs, cs, dhs, dcs):
    from jax.experimental.pallas import tpu as pltpu

    L, b, H4 = x.shape
    H = H4 // 4

    def at(i):
        return (L - 1 - i, 0, 0)

    def before(i):
        return (jnp.maximum(L - 2 - i, 0), 0, 0)

    def fixed(i):
        return (0, 0)

    return pl.pallas_call(
        _lstm_seq_bwd_kernel,
        name="lstm_bwd",
        grid=(L,),
        in_specs=[
            pl.BlockSpec((1, b, H4), at),
            pl.BlockSpec((1, b, 1), at),
            pl.BlockSpec((1, b, H), before),
            pl.BlockSpec((1, b, H), before),
            pl.BlockSpec((1, b, H), at),
            pl.BlockSpec((1, b, H), at),
            pl.BlockSpec((H, H4), fixed),
            pl.BlockSpec((b, H), fixed),
            pl.BlockSpec((b, H), fixed),
        ],
        out_specs=[pl.BlockSpec((1, b, H4), at),
                   pl.BlockSpec((b, H), fixed),
                   pl.BlockSpec((b, H), fixed)],
        out_shape=[jax.ShapeDtypeStruct((L, b, H4), x.dtype),
                   jax.ShapeDtypeStruct((b, H), x.dtype),
                   jax.ShapeDtypeStruct((b, H), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=_on_cpu(),
    )(x, alive, hs, cs, dhs, dcs, wb, h0, c0)


def _lstm_dw(h0, hs, dx):
    """sum_t h[t-1]^T @ dx[t] over the whole sequence, float32."""
    dw = _mxu_dot(h0, dx[0], ((0,), (0,)))
    if dx.shape[0] > 1:
        dw = dw + _mxu_dot(hs[:-1], dx[1:], ((0, 1), (0, 1)))
    return dw


def lstm_seq_bwd(x, alive, w, h0, c0, hs, cs, dhs, dcs):
    """(dx, dw, dh0, dc0) of the whole recurrence from its saved outputs.

    ``hs``/``cs`` [L, b, H] may be the kernel's carries or the op's MASKED
    outputs (carries * alive): step t reads h[t-1], c[t-1] only through
    terms that ``alive[t]`` multiplies, a row alive at t was alive at t-1
    (alive is a prefix mask), and there masked and unmasked agree.

    The reverse recurrence (one Mosaic kernel; a scan carrying (dh, dc)
    where the kernel's blocks exceed the VMEM budget) emits dx only: gates
    = x[t] + h[t-1] @ w, so dx[t] IS dgates[t] and the weight gradient is
    one product over the whole sequence after the loop, bf16 operands with
    float32 accumulation like every other product of the lane."""
    b, H = h0.shape
    wb = w.astype(jnp.bfloat16)
    args = (x, alive, wb, h0, c0, hs, cs, dhs, dcs)
    if lstm_bwd_fits(b, H, x.dtype.itemsize):
        with kernel_span("pallas", "lstm_bwd"):
            dx, dh0, dc0 = _lstm_seq_bwd_pallas(*args)
    else:
        record_fallback("lstm_bwd")
        with kernel_span("jnp", "lstm_bwd"):
            dx, dh0, dc0 = _lstm_seq_bwd_scan(*args)
    return dx, _lstm_dw(h0, hs, dx).astype(w.dtype), dh0, dc0


@jax.custom_vjp
def lstm_seq_pallas(x, alive, w, h0, c0):
    return _lstm_seq_fwd_pallas(x, alive, w, h0, c0)


def _lstm_seq_fwd(x, alive, w, h0, c0):
    hs, cs = _lstm_seq_fwd_pallas(x, alive, w, h0, c0)
    return (hs, cs), (x, alive, w, h0, c0, hs, cs)


def _lstm_seq_bwd(res, cts):
    x, alive, w, h0, c0, hs, cs = res
    dx, dw, dh0, dc0 = lstm_seq_bwd(x, alive, w, h0, c0, hs, cs, *cts)
    return dx, None, dw, dh0, dc0


lstm_seq_pallas.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)


# ---------------------------------------------------------------------------
# Whole-recurrence GRU (same pattern as lstm_seq_pallas)
# ---------------------------------------------------------------------------

def _gru_seq_kernel(x_ref, alive_ref, w_ref, h0_ref, hs_ref, h_s):
    """Grid over time; w [H, 3H] = [W_u | W_r | W_c] VMEM-resident, h carry
    in VMEM scratch. Gate math matches _gru_cell_jnp / the scan path
    (gru_unit_op.h: h = u*c + (1-u)*h_prev)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_s[...] = h0_ref[...]

    h_prev = h_s[...]
    xt = x_ref[0]
    alive = alive_ref[0]
    hdim = h_prev.shape[-1]
    w = w_ref[...]
    hb = h_prev.astype(w.dtype)
    ur = jax.lax.dot(hb, w[:, :2 * hdim],
                     preferred_element_type=jnp.float32).astype(h_prev.dtype)
    u = jax.nn.sigmoid(xt[:, :hdim] + ur[:, :hdim])
    r = jax.nn.sigmoid(xt[:, hdim:2 * hdim] + ur[:, hdim:])
    rc = jax.lax.dot((r * h_prev).astype(w.dtype), w[:, 2 * hdim:],
                     preferred_element_type=jnp.float32).astype(h_prev.dtype)
    c = jnp.tanh(xt[:, 2 * hdim:] + rc)
    h = u * c + (1.0 - u) * h_prev
    h = alive * h + (1 - alive) * h_prev
    h_s[...] = h
    hs_ref[0] = h


def _gru_seq_fwd_pallas(x, alive, w, h0):
    from jax.experimental.pallas import tpu as pltpu

    L, b, H3 = x.shape
    H = H3 // 3
    wb = w.astype(jnp.bfloat16)
    return pl.pallas_call(
        _gru_seq_kernel,
        grid=(L,),
        in_specs=[
            pl.BlockSpec((1, b, H3), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, b, 1), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
            pl.BlockSpec((b, H), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b, H), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, b, H), x.dtype),
        scratch_shapes=[pltpu.VMEM((b, H), x.dtype)],
        interpret=_on_cpu(),
    )(x, alive, wb, h0)


def _gru_step_jnp(xt, h_prev, w, alive):
    """jnp twin of one kernel step on CARRIES (bf16 matmul recipe)."""
    hdim = h_prev.shape[-1]
    wb = w.astype(jnp.bfloat16)
    ur = jax.lax.dot(h_prev.astype(jnp.bfloat16), wb[:, :2 * hdim],
                     preferred_element_type=jnp.float32).astype(h_prev.dtype)
    u = jax.nn.sigmoid(xt[:, :hdim] + ur[:, :hdim])
    r = jax.nn.sigmoid(xt[:, hdim:2 * hdim] + ur[:, hdim:])
    rc = jax.lax.dot((r * h_prev).astype(jnp.bfloat16), wb[:, 2 * hdim:],
                     preferred_element_type=jnp.float32).astype(h_prev.dtype)
    c = jnp.tanh(xt[:, 2 * hdim:] + rc)
    h = u * c + (1.0 - u) * h_prev
    return alive * h + (1 - alive) * h_prev


@jax.custom_vjp
def gru_seq_pallas(x, alive, w, h0):
    return _gru_seq_fwd_pallas(x, alive, w, h0)


def _gru_seq_fwd(x, alive, w, h0):
    hs = _gru_seq_fwd_pallas(x, alive, w, h0)
    return hs, (x, alive, w, h0, hs)


def _gru_seq_bwd(res, dhs):
    x, alive, w, h0, hs = res
    h_prevs = jnp.concatenate([h0[None], hs[:-1]], axis=0)

    def bstep(carry, inp):
        dh_next, dw = carry
        xt, at, hp, dh_out = inp
        _, vjp = jax.vjp(
            lambda xv, hv, wv: _gru_step_jnp(xv, hv, wv, at), xt, hp, w)
        dxt, dhp, dwt = vjp(dh_next + dh_out)
        return (dhp, dw + dwt), dxt

    (dh0, dw), dx = jax.lax.scan(
        bstep, (jnp.zeros_like(h0), jnp.zeros_like(w)),
        (x, alive, h_prevs, dhs), reverse=True)
    return dx, None, dw, dh0


gru_seq_pallas.defvjp(_gru_seq_fwd, _gru_seq_bwd)
