"""Rows -> tokens for ``routed_experts``: the Pallas family ``moe_combine``.

``combine`` gives every token the float32 sum of the buffer's rows that
hold one of its assignments, each times its row's weight: the forward's
combine and the backward's scatter of the rows' gradients (weights of one)
are the same operation. It is a gather and a sum over blocks of tokens,
not one update after another over the whole static buffer.

What it leans on (ops/moe_ops.route): the buffer is sorted by held expert,
tokens ascend inside an expert's group and occur there at most once. So
the rows of one block of ``TOKEN_BLOCK`` tokens are ONE contiguous range
in each group, and ``schedule`` finds all of them by a count and a running
sum (no sort, no scatter). The kernel walks (block of tokens, block of
``ROW_BLOCK`` rows) pairs in that order with the schedule prefetched into
scalar memory: the token block's output stays resident in VMEM while its
row blocks stream past, each of the range's rows is added to its token's
line there (rows ascend, so a token's rows are summed by ascending
expert), and the block is written once when the walk moves on. Rows
outside every range (padding, tiles past the last group) are never read; a
token without a row gets zeros; a token block without any still gets its
one step. Steps past the schedule's end stay on the last block, so nothing
moves.

The jnp twin is ``jnp.zeros(...).at[token].add(rows * weight)`` over the
whole buffer (ops/moe_ops.combine_jnp), which is also what runs where the
family is not on the tier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_cpu

TOKEN_BLOCK = 1024
ROW_BLOCK = 64
VMEM_LIMIT = 64 * 1024 * 1024
SMEM_LIMIT = 512 * 1024       # what the prefetched scalars may take


def _token_block(n):
    return min(TOKEN_BLOCK, n)


def _steps(n_rows, n, held):
    """The grid: every (token block, expert) pair takes a step and every
    row block's edge inside a range one more."""
    return n // _token_block(n) * held + n_rows // ROW_BLOCK


def supported(rows, n, held):
    """Whole 128-lane width, rows in whole blocks, tokens in whole blocks
    of whole sublanes; both blocks (twice, for the pipeline) inside the
    VMEM budget and the schedule inside scalar memory."""
    n_rows, width = rows.shape
    block = _token_block(n)
    return (rows.dtype == jnp.float32 and width % 128 == 0
            and n_rows % ROW_BLOCK == 0 and block % 8 == 0
            and n % block == 0
            and 2 * (block + ROW_BLOCK) * width * 4 <= VMEM_LIMIT // 2
            and 4 * (2 * n_rows + 4 * _steps(n_rows, n, held) + 1)
            <= SMEM_LIMIT)


def schedule(token, starts, tile_expert, n):
    """The walk, as int32 vectors of one entry a grid step: the token
    block, the row block, and the first and one past the last row of the
    block that belong to the step's (token block, expert) range; and the
    number of steps in use. ``token`` [R] is each row's token or -1,
    ``starts`` [held] each group's first row, ``tile_expert`` the expert of
    each tile of rows (ops/moe_ops.layout)."""
    n_rows, held = token.shape[0], starts.shape[0]
    block = _token_block(n)
    n_blocks, row_blocks = n // block, n_rows // ROW_BLOCK
    pairs = n_blocks * held
    expert = jnp.repeat(tile_expert, n_rows // tile_expert.shape[0])
    pair = jnp.where(token >= 0, (token // block) * held + expert, pairs)
    count = jnp.sum(pair[:, None] == jnp.arange(pairs), axis=0,
                    dtype=jnp.int32).reshape(n_blocks, held)
    lo = jnp.minimum(starts + jnp.cumsum(count, axis=0) - count, n_rows)
    hi = jnp.minimum(lo + count, n_rows)
    first = jnp.minimum(lo // ROW_BLOCK, row_blocks - 1)
    walks = jnp.where(hi > lo, (hi - 1) // ROW_BLOCK - first + 1, 0)
    # a token block without a row still takes one (empty) step
    walks = jnp.concatenate(
        [jnp.maximum(walks[:, :1], jnp.all(walks == 0, axis=1,
                                           keepdims=True)),
         walks[:, 1:]], axis=1)
    lo, hi, first, walks = (a.reshape(-1) for a in (lo, hi, first, walks))
    ends = jnp.cumsum(walks)
    used = ends[-1:]
    # each step's pair by a compare against every pair's steps and a sum
    # (a gather of so few runs one element after another on a TPU)
    step = jnp.minimum(jnp.arange(_steps(n_rows, n, held)), used - 1)
    mine = (step[:, None] >= ends - walks) & (step[:, None] < ends)

    def of_pair(values):
        return jnp.sum(jnp.where(mine, values, 0), axis=1, dtype=jnp.int32)

    row_block = of_pair(first - (ends - walks)) + step
    base = row_block * ROW_BLOCK
    return (of_pair(jnp.arange(pairs) // held), row_block,
            jnp.clip(of_pair(lo) - base, 0, ROW_BLOCK),
            jnp.clip(of_pair(hi) - base, 0, ROW_BLOCK),
            used.astype(jnp.int32))


def _kernel(block_ref, row_block_ref, lo_ref, hi_ref, used_ref, line_ref,
            weight_ref, x_ref, o_ref):
    s = pl.program_id(0)

    @pl.when((s == 0) | (block_ref[s] != block_ref[jnp.maximum(s - 1, 0)]))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < used_ref[0])
    def _():
        base = row_block_ref[s] * ROW_BLOCK

        def add(r, carry):
            line = pl.ds(line_ref[base + r], 1)
            o_ref[line, :] += x_ref[pl.ds(r, 1), :] * weight_ref[base + r]
            return carry

        jax.lax.fori_loop(lo_ref[s], hi_ref[s], add, 0)


@functools.partial(jax.jit, static_argnames="n")
def combine(rows, weight, token, starts, tile_expert, n):
    """[R, h] float32 rows -> [n, h] float32: out[t] = sum of rows[r] *
    weight[r] over the rows with token[r] == t, in ascending r. Jitted, so
    that a program's sites (two a layer) share one trace of the kernel."""
    n_rows, width = rows.shape
    block = _token_block(n)
    walk = schedule(token, starts, tile_expert, n)
    line = jnp.where(token >= 0, token % block, 0).astype(jnp.int32)
    prefetch = walk + (line, weight.astype(jnp.float32))

    return pl.pallas_call(
        _kernel, name="moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(_steps(n_rows, n, starts.shape[0]),),
            in_specs=[pl.BlockSpec((ROW_BLOCK, width),
                                   lambda s, b, rb, *_: (rb[s], 0))],
            out_specs=pl.BlockSpec((block, width),
                                   lambda s, b, *_: (b[s], 0))),
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=on_cpu(),
    )(*prefetch, rows)
