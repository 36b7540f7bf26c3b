"""Banded causal attention with grouped key/value heads: the Pallas family
``attention`` and its blocked jnp twin.

Query head ``j`` reads key/value head ``j // (num_heads // num_kv_heads)``
(blocked groups, the source family's ``repeat_kv``). Position ``i`` sees
``j`` with ``0 <= i - j`` and, where ``window > 0``, ``i - j < window``.
Neither path holds a ``[T, T]`` tensor: queries go in blocks, a block
meets only the key blocks its band touches (a window layer's work is
``T x window``, a full layer's ``T^2 / 2``), and the backward pass rebuilds
each block's probabilities from the forward's saved log-sum-exp. The
kernels' grid is the band itself: ``band_schedule`` lists the (query block,
key block) pairs when the op is traced, the tables are prefetched into
scalar memory, and a grid step is one pair. The backward is one kernel,
``attention_bwd``, that walks the band once with ``dq``, ``dk`` and ``dv``
of the whole sequence in VMEM; at lengths where those do not fit
(``backward_form``) two kernels walk it, ``attention_dq`` by query block
and ``attention_dkv`` by key block, rebuilding the same scores each.

Arrays keep the model's layout, ``[b, T, heads * head_dim]``: a block
spec's last index picks the head's columns, so nothing is transposed.
Both paths return ``(out, residual)`` and take the residual back in the
backward pass; it is the per-row log-sum-exp, ``[b, heads, T]`` from the
twin and lane-replicated ``[b, heads, T, 128]`` from the kernels (a row
statistic has to lie along sublanes where it meets a ``[bq, bk]`` score
block, and a ``[T, 1]`` array is padded to 128 lanes in HBM anyway).

The twin is the CPU's path and the kernels' reference in the tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_cpu

NEG = -1e30             # masked score: exp(NEG - m) is 0.0 for any real m
LANES = 128
TWIN_BLOCK = 512
KERNEL_BLOCKS = (512, 256, 128)


# ------------------------------------------------------------------ the band
def _visible(qpos, kpos, window):
    mask = kpos <= qpos
    if window:
        mask = mask & (qpos - kpos < window)
    return mask


FIRST, LAST = 1, 2                # bits of a schedule entry's flags
MAX_ENTRIES = 32768     # of one kernel's schedule: its four int32 tables are
                        # then half a v5e's scalar memory (1 MiB)


class Schedule(NamedTuple):
    """The blocks a band touches, one entry a grid step, in the order a
    kernel walks them: int32 tables of the entry's query block, key block,
    query head within its group (the ``key`` and ``head`` orders; zeros
    otherwise) and flags. ``FIRST`` / ``LAST``: the entry opens / closes
    its accumulator's run. ``skipped`` is what a rectangular grid of
    (blocks x one head's longest run) for each head would have walked
    besides."""
    q: np.ndarray
    k: np.ndarray
    head: np.ndarray
    flags: np.ndarray
    skipped: int


def band_schedule(T, block, window, by="query", group=1):
    """The one place that says what the band is. Block ``(i, j)`` holds the
    differences ``qpos - kpos`` from ``lo`` to ``hi``; the visible ones
    are an interval that starts at 0, so some pair of the block is visible
    where the block's difference nearest 0 is.

    ``by="query"`` (``attention_fwd``, ``attention_dq``): a run is a query
    block's key blocks, ascending, so the diagonal comes last.
    ``by="key"`` (``attention_dkv``): a run is a key block's query blocks,
    ascending, once for each of the ``group`` query heads that share the
    key/value head.
    ``by="head"`` (``attention_bwd``): the same pairs with the heads taken
    outermost; a run is one query head's walk of the whole band, key block
    by key block, each key block's query blocks ascending."""
    n = T // block
    i, j = np.indices((n, n))
    lo, hi = (i - j) * block - (block - 1), (i - j) * block + (block - 1)
    some = _visible(np.clip(0, lo, hi), 0, window)
    column = [[(b, a) for b in np.flatnonzero(some[:, a])] for a in range(n)]
    if by == "query":
        runs = [[(a, b, 0) for b in np.flatnonzero(some[a])]
                for a in range(n)]
    elif by == "key":
        runs = [[(qi, ki, g) for g in range(group) for qi, ki in column[a]]
                for a in range(n)]
    else:
        runs = [[(qi, ki, g) for pairs in column for qi, ki in pairs]
                for g in range(group)]
    entries = [(qi, ki, g, (FIRST if e == 0 else 0)
                | (LAST if e == len(run) - 1 else 0))
               for run in runs for e, (qi, ki, g) in enumerate(run)]
    q, k, head, flags = np.asarray(entries, np.int32).T
    longest = int(some.sum(1 if by == "query" else 0).max())  # of one head
    heads = 1 if by == "query" else group
    return Schedule(q, k, head, flags,
                    heads * (n * longest - int(some.sum())))


# ------------------------------------------------------------ blocked twin
def _twin_block(T):
    return TWIN_BLOCK if T % TWIN_BLOCK == 0 else T


def _twin_ranges(T, window):
    bq = _twin_block(T)
    for lo in range(0, T, bq):
        k_lo = max(lo - window + 1, 0) if window else 0
        yield lo, lo + bq, k_lo, lo + bq


def _twin_scores(qb, kb, q_lo, k_lo, window, scale):
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    qpos = q_lo + jnp.arange(qb.shape[1])[:, None]
    kpos = k_lo + jnp.arange(kb.shape[1])[None, :]
    return jnp.where(_visible(qpos, kpos, window), s, NEG)


def _heads(x, kv_heads):
    return x.reshape(x.shape[0], x.shape[1], kv_heads, -1)


def attention_jnp(q, k, v, num_heads, num_kv_heads, window):
    """(out [b, T, heads*dv], lse [b, heads, T]) — query blocks of
    ``TWIN_BLOCK`` rows, each against the static slice of keys its band
    reaches, softmax in float32, probabilities in the values' type for the
    second product (as the kernel has them). The values' heads may be of
    another size (``dv``) than the queries' and keys' (``d``, which scales
    the scores)."""
    b, T, _ = q.shape
    g = num_heads // num_kv_heads
    d = q.shape[-1] // num_heads
    scale = d ** -0.5
    qh = _heads(q, num_kv_heads).reshape(b, T, num_kv_heads, g, d)
    kh, vh = _heads(k, num_kv_heads), _heads(v, num_kv_heads)
    outs, lses = [], []
    for q_lo, q_hi, k_lo, k_hi in _twin_ranges(T, window):
        s = _twin_scores(qh[:, q_lo:q_hi], kh[:, k_lo:k_hi], q_lo, k_lo,
                         window, scale)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype),
                       vh[:, k_lo:k_hi], preferred_element_type=jnp.float32)
        o = o / jnp.moveaxis(l, 3, 1)                    # [b, q, h, g, 1]
        outs.append(o.reshape(b, q_hi - q_lo, -1).astype(q.dtype))
        lses.append((m + jnp.log(l))[..., 0].reshape(b, num_heads, -1))
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=2)


def attention_jnp_bwd(q, k, v, out, lse, dout, num_heads, num_kv_heads,
                      window):
    """(dq, dk, dv) from the saved log-sum-exp, block by block."""
    b, T, _ = q.shape
    g = num_heads // num_kv_heads
    d = q.shape[-1] // num_heads
    scale = d ** -0.5
    qh, oh, doh = (x.reshape(b, T, num_kv_heads, g, -1)
                   for x in (q, out, dout))
    kh, vh = _heads(k, num_kv_heads), _heads(v, num_kv_heads)
    delta = jnp.sum(oh.astype(jnp.float32) * doh.astype(jnp.float32), -1)
    lse = lse.reshape(b, num_kv_heads, g, T)
    dq = []
    dk = jnp.zeros(kh.shape, jnp.float32)
    dv = jnp.zeros(vh.shape, jnp.float32)
    for q_lo, q_hi, k_lo, k_hi in _twin_ranges(T, window):
        qb, dob = qh[:, q_lo:q_hi], doh[:, q_lo:q_hi]
        kb, vb = kh[:, k_lo:k_hi], vh[:, k_lo:k_hi]
        s = _twin_scores(qb, kb, q_lo, k_lo, window, scale)
        p = jnp.exp(s - lse[..., q_lo:q_hi, None])
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", dob, vb,
                        preferred_element_type=jnp.float32)
        dl = jnp.moveaxis(delta[:, q_lo:q_hi], 1, 3)[..., None]
        ds = (p * (dp - dl) * scale).astype(q.dtype)
        dq.append(jnp.einsum("bhgqk,bkhd->bqhgd", ds, kb,
                             preferred_element_type=jnp.float32))
        dk = dk.at[:, k_lo:k_hi].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", ds, qb, preferred_element_type=jnp.float32))
        dv = dv.at[:, k_lo:k_hi].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", p.astype(dout.dtype), dob,
            preferred_element_type=jnp.float32))
    dq = jnp.concatenate(dq, axis=1).reshape(q.shape).astype(q.dtype)
    return (dq, dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype))


# ---------------------------------------------------------------- kernels
def kernel_block(T):
    for blk in KERNEL_BLOCKS:
        if T % blk == 0:
            return blk
    return None


def attention_supported(q, num_heads, num_kv_heads, window, v=None):
    """The kernels take whole 128-lane heads (the values' ``v``, where
    given, may be of another size than the queries' and keys'), a length
    that 128 divides and a band whose schedule fits scalar memory (the
    longest is ``attention_dkv``'s, a group's heads through every pair);
    anything else is the twin's."""
    T, d, blk, group = _geometry(q, num_heads, num_kv_heads)
    dv = d if v is None else v.shape[-1] // num_kv_heads
    return (d % LANES == 0 and dv % LANES == 0 and blk is not None
            and q.dtype in (jnp.bfloat16, jnp.float32)
            and len(band_schedule(T, blk, window).q) * group <= MAX_ENTRIES)


def _scores(q, k, qi, ki, blk, window, scale):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = qi * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(_visible(qpos, kpos, window), s, NEG)


def _wide(stat, cols):
    """A lane-replicated ``[rows, 128]`` row statistic against a ``[rows,
    cols]`` block: whole copies side by side. The same numbers as
    ``stat[:, :1]`` broadcast, which crosses lanes for every vreg of the
    block and was two fifths of the forward kernel on a v5e (PERF.md,
    PR 29)."""
    return stat if cols == LANES else jnp.tile(stat, (1, cols // LANES))


def _entry(q_tab, k_tab, flags_tab):
    """(query block, key block, flags) of this grid step's entry."""
    e = pl.program_id(2)
    return q_tab[e], k_tab[e], flags_tab[e]


def _fwd_kernel(q_tab, k_tab, flags_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, blk, window, scale):
    qi, ki, flags = _entry(q_tab, k_tab, flags_tab)

    @pl.when(flags & FIRST != 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    v = v_ref[...]
    s = _scores(q_ref[...], k_ref[...], qi, ki, blk, window, scale)
    m_prev = m_scr[...]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # a row wholly masked in this block adds exp(0) here; the diagonal
    # block comes last, holds a real score for every row, and its
    # alpha = exp(NEG - m) wipes that
    p = jnp.exp(s - _wide(m_next, s.shape[1]))
    alpha = jnp.exp(m_prev - m_next)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * _wide(alpha, v.shape[1]) + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_next

    @pl.when(flags & LAST != 0)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...]
                      / _wide(l, acc_scr.shape[1])).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _dq_kernel(q_tab, k_tab, flags_tab, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *, blk, window, scale):
    qi, ki, flags = _entry(q_tab, k_tab, flags_tab)

    @pl.when(flags & FIRST != 0)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    k = k_ref[...]
    s = _scores(q_ref[...], k, qi, ki, blk, window, scale)
    p = jnp.exp(s - _wide(lse_ref[...], s.shape[1]))
    dp = jax.lax.dot_general(do_ref[...], v_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _wide(delta_ref[...], s.shape[1]))
    dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                           preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_tab, k_tab, head_tab, flags_tab, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, blk, window, scale):
    del head_tab                            # the index maps' alone
    qi, ki, flags = _entry(q_tab, k_tab, flags_tab)

    @pl.when(flags & FIRST != 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    q, do = q_ref[...], do_ref[...]
    s = _scores(q, k_ref[...], qi, ki, blk, window, scale)
    p = jnp.exp(s - _wide(lse_ref[...], s.shape[1]))
    dv_scr[...] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _wide(delta_ref[...], s.shape[1]))
    dk_scr[...] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _rows(i, blk):
    return pl.ds(pl.multiple_of(i * blk, blk), blk)


def _by_rows(T, blk, fn):
    """``fn(rows)`` for each block of rows of a whole-sequence ref."""
    def step(r, carry):
        fn(_rows(r, blk))
        return carry
    jax.lax.fori_loop(0, T // blk, step, 0)


def _zero(blk, *scratch):
    def clear(rows):
        for scr in scratch:
            scr[rows, :] = jnp.zeros((blk, scr.shape[1]), jnp.float32)
    _by_rows(scratch[0].shape[0], blk, clear)


def _flush(blk, scr, out, scale=None):
    """``out = scr * scale`` in ``out``'s type, as the split kernels end."""
    def put(rows):
        x = scr[rows, :]
        out[rows, :] = (x if scale is None else x * scale).astype(out.dtype)
    _by_rows(scr.shape[0], blk, put)


def _bwd_kernel(q_tab, k_tab, head_tab, flags_tab, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_scr,
                dk_scr, dv_scr, *, blk, window, scale):
    """One walk of the band for all three gradients. The accumulators
    cover the whole sequence: ``dq``'s the current query head's, opened
    and flushed by its run's flags; ``dk``'s and ``dv``'s the key/value
    head's, opened by the first grid step and flushed by the last."""
    del head_tab                            # the index maps' alone
    qi, ki, flags = _entry(q_tab, k_tab, flags_tab)
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _():
        _zero(blk, dk_scr, dv_scr)

    @pl.when(flags & FIRST != 0)
    def _():
        _zero(blk, dq_scr)

    q, k, do = q_ref[...], k_ref[...], do_ref[...]
    s = _scores(q, k, qi, ki, blk, window, scale)
    p = jnp.exp(s - _wide(lse_ref[...], s.shape[1]))
    kr = _rows(ki, blk)
    dv_scr[kr, :] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - _wide(delta_ref[...], s.shape[1]))).astype(q.dtype)
    dk_scr[kr, :] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dq_scr[_rows(qi, blk), :] += jnp.dot(ds, k,
                                         preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _():
        _flush(blk, dq_scr, dq_ref, scale)

    @pl.when(e == pl.num_programs(2) - 1)
    def _():
        _flush(blk, dk_scr, dk_ref, scale)
        _flush(blk, dv_scr, dv_ref)


def _call(kernel, name, heads, tables, operands, in_specs, out_specs,
          out_shape, scratch_shapes, vmem_limit_bytes=None):
    """One kernel over the grid (batch, ``heads``, the schedule's entries),
    the schedule's ``tables`` prefetched into scalar memory, where the
    index maps and the body read them."""
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(operands[0].shape[0], heads, len(tables[0])),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=on_cpu(),
    )(*(jnp.asarray(t) for t in tables), *operands)


def _geometry(q, num_heads, num_kv_heads):
    T = q.shape[1]
    return (T, q.shape[-1] // num_heads, kernel_block(T),
            num_heads // num_kv_heads)


def _query_order(T, blk, widths, window, group):
    """What ``attention_fwd`` and ``attention_dq`` share: the schedule's
    tables and, for each head size of ``widths``, the block specs of a
    query head's own blocks and of its key/value head's blocks, then that
    of its rows' statistics, at grid step (batch, query head, entry)."""
    sched = band_schedule(T, blk, window)
    own = [pl.BlockSpec((None, blk, d),
                        lambda b, h, e, qt, kt, fl: (b, qt[e], h))
           for d in widths]
    kv = [pl.BlockSpec((None, blk, d),
                       lambda b, h, e, qt, kt, fl: (b, kt[e], h // group))
          for d in widths]
    rows = pl.BlockSpec((None, None, blk, LANES),
                        lambda b, h, e, qt, kt, fl: (b, h, qt[e], 0))
    return (sched.q, sched.k, sched.flags), own, kv, rows


def attention_pallas(q, k, v, num_heads, num_kv_heads, window, scale=None):
    """(out [b, T, heads*dv], lse [b, heads, T, 128]) by the forward
    kernel; ``scale`` defaults to the query heads' size ^-0.5 (a caller
    that padded them gives the true one)."""
    T, d, blk, group = _geometry(q, num_heads, num_kv_heads)
    dv = v.shape[-1] // num_kv_heads
    tables, (own, own_v), (kv, kv_v), rows = _query_order(
        T, blk, (d, dv), window, group)
    b = q.shape[0]
    return _call(
        functools.partial(_fwd_kernel, blk=blk, window=window,
                          scale=scale or d ** -0.5),
        "attention_fwd", num_heads, tables, (q, k, v),
        in_specs=[own, kv, kv_v], out_specs=[own_v, rows],
        out_shape=[jax.ShapeDtypeStruct((b, T, num_heads * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, num_heads, T, LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, LANES), jnp.float32),
                        pltpu.VMEM((blk, LANES), jnp.float32),
                        pltpu.VMEM((blk, dv), jnp.float32)])


# The fused backward holds whole-sequence accumulators and output blocks in
# VMEM, so its bytes grow with the length: it is the form wherever its count
# fits the budget, and the split pair (a [blk, d] accumulator each) above
# it. A v5e TensorCore has 128 MiB of VMEM; the budget is three quarters of
# it, so that the limit's headroom and what Mosaic keeps for itself stay
# inside the chip.
VMEM_BYTES = 128 << 20
BWD_VMEM_BUDGET = VMEM_BYTES * 3 // 4
BWD_VMEM_HEADROOM = 4 << 20


def bwd_vmem_bytes(T, d, dv, blk, dtype):
    """VMEM bytes of ``attention_bwd`` from its shapes: the float32
    accumulators and the output blocks of ``dq``, ``dk`` (``d`` wide) and
    ``dv`` over the whole sequence, each output block twice (the pipeline
    keeps two); the streamed blocks twice (``q``, ``k``, ``v``, ``d out``
    and the two float32 lane-replicated row statistics); two float32
    ``[blk, blk]`` values. Mosaic's own allocation reads 1.0-1.5 MiB less
    at the cells' shapes (27.0 of 28 MiB at 8192 tokens and heads of 128)."""
    item = jnp.dtype(dtype).itemsize
    whole = T * (2 * d + dv) * (4 + 2 * item)
    streamed = 2 * blk * ((2 * d + 2 * dv) * item + 2 * LANES * 4)
    return whole + streamed + 2 * blk * blk * 4


def backward_form(q, v, num_heads, num_kv_heads):
    """"fused" where ``attention_bwd``'s count fits ``BWD_VMEM_BUDGET``,
    else "split"."""
    T, d, blk, _ = _geometry(q, num_heads, num_kv_heads)
    dv = v.shape[-1] // num_kv_heads
    fits = bwd_vmem_bytes(T, d, dv, blk, q.dtype) <= BWD_VMEM_BUDGET
    return "fused" if fits else "split"


def attention_pallas_bwd(q, k, v, out, lse, dout, num_heads, num_kv_heads,
                         window, scale=None):
    """(dq, dk, dv) by the form ``backward_form`` picks from the shapes."""
    bwd = (attention_bwd_fused
           if backward_form(q, v, num_heads, num_kv_heads) == "fused"
           else attention_bwd_split)
    return bwd(q, k, v, out, lse, dout, num_heads, num_kv_heads, window,
               scale)


def _bwd_operands(q, k, v, out, lse, dout, num_heads):
    """(q, k, v, d out, lse, delta): delta is each row's ``out . d out``,
    lane-replicated as the log-sum-exp is."""
    b, T, _ = q.shape
    delta = jnp.sum((out.astype(jnp.float32) * dout.astype(jnp.float32))
                    .reshape(b, T, num_heads, -1), axis=-1)
    delta = jnp.broadcast_to(jnp.swapaxes(delta, 1, 2)[..., None],
                             (b, num_heads, T, LANES))
    return q, k, v, dout, lse, delta


def _key_order_specs(blk, widths, group):
    """At grid step (batch, key/value head, entry), where the entry names
    the query head of the group beside its blocks: for each head size of
    ``widths`` the block specs of the entry's query head's blocks and of
    the key/value head's blocks, then that of its rows' statistics."""
    own = [pl.BlockSpec(
        (None, blk, w),
        lambda b, h, e, qt, kt, gt, fl: (b, qt[e], h * group + gt[e]))
        for w in widths]
    kv = [pl.BlockSpec((None, blk, w),
                       lambda b, h, e, qt, kt, gt, fl: (b, kt[e], h))
          for w in widths]
    rows = pl.BlockSpec(
        (None, None, blk, LANES),
        lambda b, h, e, qt, kt, gt, fl: (b, h * group + gt[e], qt[e], 0))
    return own, kv, rows


def attention_bwd_fused(q, k, v, out, lse, dout, num_heads, num_kv_heads,
                        window, scale=None):
    """(dq, dk, dv) by ``attention_bwd``: one walk of the band a key/value
    head, its query heads outermost, five products a pair."""
    T, d, blk, group = _geometry(q, num_heads, num_kv_heads)
    dv = v.shape[-1] // num_kv_heads
    sched = band_schedule(T, blk, window, by="head", group=group)
    (own, own_v), (kv, kv_v), rows = _key_order_specs(blk, (d, dv), group)
    # whole-sequence output blocks: dq's follows the query head, so it is
    # written back as each head's walk ends; dk's and dv's once
    whole_q = pl.BlockSpec((None, T, d), lambda b, h, e, qt, kt, gt, fl: (
        b, 0, h * group + gt[e]))
    whole_k, whole_v = (pl.BlockSpec(
        (None, T, w), lambda b, h, e, qt, kt, gt, fl: (b, 0, h))
        for w in (d, dv))
    return _call(
        functools.partial(_bwd_kernel, blk=blk, window=window,
                          scale=scale or d ** -0.5),
        "attention_bwd", num_kv_heads,
        (sched.q, sched.k, sched.head, sched.flags),
        _bwd_operands(q, k, v, out, lse, dout, num_heads),
        in_specs=[own, kv, kv_v, own_v, rows, rows],
        out_specs=[whole_q, whole_k, whole_v],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((T, w), jnp.float32) for w in (d, d, dv)],
        vmem_limit_bytes=bwd_vmem_bytes(T, d, dv, blk, q.dtype)
        + BWD_VMEM_HEADROOM)


def attention_bwd_split(q, k, v, out, lse, dout, num_heads, num_kv_heads,
                        window, scale=None):
    """(dq, dk, dv) by the long-sequence form: one kernel over query blocks
    for ``dq``, one over key blocks for ``dk``/``dv`` that sums a group's
    query heads in VMEM."""
    T, d, blk, group = _geometry(q, num_heads, num_kv_heads)
    dv = v.shape[-1] // num_kv_heads
    scale = scale or d ** -0.5
    operands = _bwd_operands(q, k, v, out, lse, dout, num_heads)
    tables, (own, own_v), (kv, kv_v), rows = _query_order(
        T, blk, (d, dv), window, group)
    dq = _call(
        functools.partial(_dq_kernel, blk=blk, window=window, scale=scale),
        "attention_dq", num_heads, tables, operands,
        in_specs=[own, kv, kv_v, own_v, rows, rows], out_specs=own,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)])

    sched = band_schedule(T, blk, window, by="key", group=group)
    (own, own_v), (kv, kv_v), rows = _key_order_specs(blk, (d, dv), group)
    dk, dv_out = _call(
        functools.partial(_dkv_kernel, blk=blk, window=window, scale=scale),
        "attention_dkv", num_kv_heads,
        (sched.q, sched.k, sched.head, sched.flags), operands,
        in_specs=[own, kv, kv_v, own_v, rows, rows], out_specs=[kv, kv_v],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, dv), jnp.float32)])
    return dq, dk, dv_out
