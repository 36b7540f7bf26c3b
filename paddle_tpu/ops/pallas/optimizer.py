"""Fused optimizer megakernels: one Pallas launch updates ALL dense params.

The per-param optimizer ops (ops/optimizer_ops.py) trace into the step
computation, but XLA still emits one small fused kernel per parameter —
the ResNet-50 step dispatches ~160 of them (the profile's
multiply_subtract_fusion tail). Here the optimizer state lives in flat
f32 arenas (params / grads / accumulators concatenated and padded to a
lane-aligned tile grid) and ONE kernel walks the arena tiles applying the
update — SGD, momentum and Adam, each elementwise over its tile, scalars
(learning rate, bias-correction) prefetched into SMEM.

On the chip it does not pay yet (TPU v5 lite, PR 21,
tools/kernel_probe.py): over ResNet-50's parameter census (267 tensors,
25.6 M elements) the arena momentum step, including the per-step concat
and split it needs, takes 68.4 ms against 26.0 ms for the per-param twin
(standalone jit calls; both sides include the host's handling of ~800
arguments) — so ``optimizer`` is NOT in ``AUTO_PALLAS``.

The jnp twins are the exact per-param update expressions shared with the
per-param ops (optimizer_ops._sgd_dense & co.), so ``kernel_tier=jnp``
reproduces the per-param program bitwise; the Pallas arena path is pinned
against the twins in tests/test_fused_optimizer.py (interpret on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import on_cpu as _on_cpu


# arena tile: one grid step processes TILE elements as an [8, 128] f32
# block (the f32 register tile), so any param mix packs without padding
# waste beyond the final tile
_TILE = 8 * 128


def flatten_arena(arrays):
    """Concat raveled f32 arrays into a [n_tiles, 1024]-shaped arena (zero
    padded tail). Returns (arena2d, total_elems)."""
    flat = jnp.concatenate([a.ravel() for a in arrays])
    total = flat.shape[0]
    pad = (-total) % _TILE
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, 128), total


def split_arena(arena2d, shapes, dtype=None):
    """Invert :func:`flatten_arena`: slice each param back out."""
    flat = arena2d.reshape(-1)
    out, off = [], 0
    for s in shapes:
        n = 1
        for d in s:
            n *= int(d)
        a = flat[off:off + n].reshape(s)
        out.append(a.astype(dtype) if dtype is not None else a)
        off += n
    return out


def _rows(arena2d):
    return arena2d.shape[0]


def _arena_call(kernel, outs, scalars, *arenas):
    """Shared pallas_call wiring: grid over row-tiles of the arena(s),
    scalars ride a (1, k) SMEM block."""
    from jax.experimental.pallas import tpu as pltpu

    rows = _rows(arenas[0])
    tile_rows = _TILE // 128
    grid = (rows // tile_rows,)
    sc = jnp.stack([jnp.asarray(s, jnp.float32).reshape(())
                    for s in scalars]).reshape(1, -1)
    block = pl.BlockSpec((tile_rows, 128), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, sc.shape[1]), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)]
        + [block] * len(arenas),
        out_specs=[block] * outs,
        out_shape=[jax.ShapeDtypeStruct(arenas[0].shape, jnp.float32)] * outs,
        interpret=_on_cpu(),
    )(sc, *arenas)


def _sgd_kernel(sc_ref, p_ref, g_ref, p_out):
    p_out[...] = p_ref[...] - sc_ref[0, 0] * g_ref[...]


def sgd_arena_pallas(p, g, lr):
    """p_new = p - lr*g over [rows, 128] f32 arenas."""
    (out,) = _arena_call(_sgd_kernel, 1, [lr], p, g)
    return out


def _momentum_kernel(sc_ref, p_ref, g_ref, v_ref, p_out, v_out, *,
                     nesterov):
    lr = sc_ref[0, 0]
    mu = sc_ref[0, 1]
    g = g_ref[...]
    v_new = mu * v_ref[...] + g
    if nesterov:
        p_out[...] = p_ref[...] - (g + mu * v_new) * lr
    else:
        p_out[...] = p_ref[...] - lr * v_new
    v_out[...] = v_new


def momentum_arena_pallas(p, g, v, lr, mu, nesterov=False):
    """(p_new, v_new): the momentum op's dense update over arenas."""
    kernel = functools.partial(_momentum_kernel, nesterov=bool(nesterov))
    p_out, v_out = _arena_call(kernel, 2, [lr, mu], p, g, v)
    return p_out, v_out


def _adam_kernel(sc_ref, p_ref, g_ref, m1_ref, m2_ref,
                 p_out, m1_out, m2_out, *, b1, b2, eps):
    lr = sc_ref[0, 0]   # already bias-corrected (the adam op's lr_eff)
    g = g_ref[...]
    m1n = b1 * m1_ref[...] + (1 - b1) * g
    m2n = b2 * m2_ref[...] + (1 - b2) * g * g
    p_out[...] = p_ref[...] - lr * m1n / (jnp.sqrt(m2n) + eps)
    m1_out[...] = m1n
    m2_out[...] = m2n


def adam_arena_pallas(p, g, m1, m2, lr_eff, b1, b2, eps):
    """(p_new, m1_new, m2_new); lr_eff carries the sqrt(1-b2^t)/(1-b1^t)
    bias correction (a traced scalar — it rides the SMEM block)."""
    kernel = functools.partial(_adam_kernel, b1=float(b1), b2=float(b2),
                               eps=float(eps))
    return _arena_call(kernel, 3, [lr_eff], p, g, m1, m2)
