"""Whole-recurrence LSTM/GRU Pallas kernels (the hand-tuned RNN hot spots).

Migrated unchanged from the seed ``ops/pallas_kernels.py`` into the kernel
tier (the old module remains as a deprecation shim). The reference
hand-schedules fused CUDA kernels for exactly these spots
(/root/reference/paddle/cuda/src/hl_cuda_lstm.cu, hl_gpu_lstm.cuh); the
Pallas analogs go further than per-cell fusion: the LSTM/GRU run their
WHOLE sequence as one kernel — grid over time, recurrent weight
VMEM-resident across steps (lax.scan re-reads it from HBM every
iteration), h/c carries in VMEM scratch, bf16 MXU gate matmuls with f32
accumulation. On a TPU v5 lite at the bench RNN-lane shape (bs64 len100
hid512; PR 21, tools/kernel_probe.py): the LSTM recurrence runs 1.22x the
scan twin (bitwise-equal output) and the lane's whole train step ties
(3.386 vs 3.389 ms) — lstm is in AUTO_PALLAS; the GRU recurrence runs
1.61x but its train step has not been measured, so gru is not.

Numerics incl. all gradients are pinned against jnp twins
(tests/test_pallas_kernels.py, interpret mode on CPU, native on TPU).
Gradients use jax.custom_vjp: a reverse lax.scan of per-step vjps over the
saved carries, recomputing gates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import on_cpu as _on_cpu


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


def _lstm_step_jnp(xt, h_prev, c_prev, w, alive):
    """One reference step on CARRIES (the jnp twin the backward
    differentiates): the bf16-MXU gate matmul + the shared cell math.
    Returns (h_carry, c_carry)."""
    gates = xt + jax.lax.dot(
        h_prev.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32).astype(h_prev.dtype)
    return _lstm_cell_jnp(gates, c_prev, h_prev, alive)


@jax.custom_vjp
def lstm_seq_pallas(x, alive, w, h0, c0):
    return _lstm_seq_fwd_pallas(x, alive, w, h0, c0)


def _lstm_seq_fwd(x, alive, w, h0, c0):
    hs, cs = _lstm_seq_fwd_pallas(x, alive, w, h0, c0)
    return (hs, cs), (x, alive, w, h0, c0, hs, cs)


def _lstm_seq_bwd(res, cts):
    """Reverse scan of per-step jax.vjp over the SAVED carries: gates are
    recomputed from x[t] + h[t-1] @ w (one extra matmul per step — the
    trade XLA's scan makes by saving gates instead; recompute keeps the
    saved-residual HBM footprint at 2 arrays)."""
    x, alive, w, h0, c0, hs, cs = res
    dhs, dcs = cts
    h_prevs = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    c_prevs = jnp.concatenate([c0[None], cs[:-1]], axis=0)

    def bstep(carry, inp):
        dh_next, dc_next, dw = carry
        xt, at, hp, cp, dh_out, dc_out = inp
        _, vjp = jax.vjp(
            lambda xv, hv, cv, wv: _lstm_step_jnp(xv, hv, cv, wv, at),
            xt, hp, cp, w)
        dxt, dhp, dcp, dwt = vjp((dh_next + dh_out, dc_next + dc_out))
        return (dhp, dcp, dw + dwt), dxt

    zero = jnp.zeros_like(h0)
    (dh0, dc0, dw), dx = jax.lax.scan(
        bstep, (zero, jnp.zeros_like(c0), jnp.zeros_like(w)),
        (x, alive, h_prevs, c_prevs, dhs, dcs), reverse=True)
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
