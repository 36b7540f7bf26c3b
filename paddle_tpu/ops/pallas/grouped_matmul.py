"""Grouped matrix products over the experts a chip holds: the Pallas family
``grouped_matmul``.

The rows of ``routed_experts``' buffer are sorted by expert and every
expert's group starts on a tile of ``TILE`` rows, so a tile of rows meets ONE
expert's weights. The kernels walk the tiles in order with the tile -> expert
map prefetched into scalar memory; an expert's whole weight matrix is the
block, so it stays resident in VMEM while that expert's tiles stream past
and is fetched once per expert and product. Tiles past the last group are
skipped (their index maps stay on the last real tile, so nothing moves).

* ``gmm(rows [R, a], w [held, a, b])   -> [R, b]``
* ``gmm_t(rows [R, b], w [held, a, b]) -> [R, a]``  (the input gradient)
* ``tgmm(rows [R, a], grads [R, b])    -> [held, a, b]`` (the weights'
  gradient: each expert's tiles accumulate into its resident output block)

Rows of padding inside a group's last tile are multiplied like any other;
the caller keeps them zero where they would be summed (``tgmm``) and masks
what comes out of them. An expert with no row has no tile: ``tgmm`` leaves
its block unwritten and the caller masks it by the load.

The jnp twin of all three is ``jax.lax.ragged_dot`` over the same aligned
groups (ops/moe_ops.py), which is also what runs where the family is not
on the tier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_cpu

TILE = 256
VMEM_LIMIT = 64 * 1024 * 1024


LANES = 64              # widths come in whole halves of a 128-lane tile


def supported(rows, w):
    """Rows in whole tiles, widths in whole halves of a 128-lane tile (every
    block spans a whole width, so the last lane tile may be half full: 1856
    = 14.5 tiles compiles for a v5e and equals the twin,
    tests/test_kernel_aot.py and tools/kernel_probe.py), and an expert's
    weights (twice, for the pipeline) well inside the VMEM budget."""
    a, b = w.shape[1:]
    itemsize = jnp.dtype(w.dtype).itemsize
    return (rows.shape[0] % TILE == 0 and a % LANES == 0 and b % LANES == 0
            and 2 * a * b * itemsize <= VMEM_LIMIT // 3)


def _tile(t, tiles):
    """The tile a grid step reads: its own, or the last real one."""
    return jnp.maximum(jnp.minimum(t, tiles[0] - 1), 0)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=VMEM_LIMIT)


def _gmm_kernel(expert_ref, tiles_ref, x_ref, w_ref, o_ref, *, transposed):
    del expert_ref

    @pl.when(pl.program_id(0) < tiles_ref[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transposed \
            else (((1,), (0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _gmm(rows, w, tile_expert, tiles, out_dtype, transposed):
    n_rows, width = rows.shape
    held, a, b = w.shape
    out_width = a if transposed else b

    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        name="grouped_matmul_t" if transposed else "grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_rows // TILE,),
            in_specs=[
                pl.BlockSpec((TILE, width),
                             lambda t, e, n: (_tile(t, n), 0)),
                pl.BlockSpec((None, a, b),
                             lambda t, e, n: (e[_tile(t, n)], 0, 0)),
            ],
            out_specs=pl.BlockSpec((TILE, out_width),
                                   lambda t, e, n: (t, 0))),
        out_shape=jax.ShapeDtypeStruct((n_rows, out_width), out_dtype),
        compiler_params=_params(), interpret=on_cpu(),
    )(tile_expert, tiles, rows, w)


def gmm(rows, w, tile_expert, tiles, out_dtype=jnp.float32):
    return _gmm(rows, w, tile_expert, tiles, out_dtype, False)


def gmm_t(rows, w, tile_expert, tiles, out_dtype=jnp.float32):
    return _gmm(rows, w, tile_expert, tiles, out_dtype, True)


def _tgmm_kernel(expert_ref, tiles_ref, x_ref, g_ref, o_ref):
    t = pl.program_id(0)
    first = (t == 0) | (expert_ref[t] != expert_ref[jnp.maximum(t - 1, 0)])

    @pl.when(t < tiles_ref[0])
    def _():
        part = jax.lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(first)
        def _():
            o_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            o_ref[...] += part


def tgmm(rows, grads, held, tile_expert, tiles):
    n_rows, a = rows.shape
    b = grads.shape[1]

    return pl.pallas_call(
        _tgmm_kernel, name="grouped_matmul_w",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_rows // TILE,),
            in_specs=[
                pl.BlockSpec((TILE, a), lambda t, e, n: (_tile(t, n), 0)),
                pl.BlockSpec((TILE, b), lambda t, e, n: (_tile(t, n), 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, a, b), lambda t, e, n: (e[_tile(t, n)], 0, 0))),
        out_shape=jax.ShapeDtypeStruct((held, a, b), jnp.float32),
        compiler_params=_params(), interpret=on_cpu(),
    )(tile_expert, tiles, rows, grads)
