"""Banded causal attention with grouped key/value heads: the Pallas family
``attention`` and its blocked jnp twin.

Query head ``j`` reads key/value head ``j // (num_heads // num_kv_heads)``
(blocked groups, the source family's ``repeat_kv``). Position ``i`` sees
``j`` with ``0 <= i - j`` and, where ``window > 0``, ``i - j < window``.
Neither path holds a ``[T, T]`` tensor: queries go in blocks, a block
meets only the key blocks its band touches (a window layer's work is
``T x window``, a full layer's ``T^2 / 2``), and the backward pass rebuilds
each block's probabilities from the forward's saved log-sum-exp.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_cpu

NEG = -1e30             # masked score: exp(NEG - m) is 0.0 for any real m
LANES = 128
TWIN_BLOCK = 512
KERNEL_BLOCKS = (512, 256, 128)


# ------------------------------------------------------------------ the band
def band_first(q_lo, window, block):
    """First key block a query block starting at row ``q_lo`` sees."""
    if not window:
        return 0 * q_lo
    return jnp.maximum(q_lo - window + 1, 0) // block


def _visible(qpos, kpos, window):
    mask = kpos <= qpos
    if window:
        mask = mask & (qpos - kpos < window)
    return mask


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
    """(out [b, T, heads*d], lse [b, heads, T]) — query blocks of
    ``TWIN_BLOCK`` rows, each against the static slice of keys its band
    reaches, softmax in float32, probabilities in the values' type for the
    second product (as the kernel has them)."""
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
    shape5 = (b, T, num_kv_heads, g, d)
    qh, oh, doh = (x.reshape(shape5) for x in (q, out, dout))
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


def attention_supported(q, num_heads):
    """The kernels take whole 128-lane heads and a length that 128
    divides; anything else is the twin's."""
    d = q.shape[-1] // num_heads
    return (d % LANES == 0 and kernel_block(q.shape[1]) is not None
            and q.dtype in (jnp.bfloat16, jnp.float32))


def _last_query(kj, blk, window, n):
    """Last query block that sees key block ``kj``."""
    if not window:
        return n - 1 + 0 * kj
    return jnp.minimum((kj * blk + blk + window - 2) // blk, n - 1)


def _span(T, blk, window):
    """Static grid extents: the number of blocks, the most key blocks any
    query block sees, and the most query blocks any key block is seen
    by."""
    n = T // blk
    if not window:
        return n, n, n
    keys = max(i - max(i * blk - window + 1, 0) // blk + 1 for i in range(n))
    queries = max(min((j * blk + blk + window - 2) // blk, n - 1) - j + 1
                  for j in range(n))
    return n, keys, queries


def _scores(q, k, qi, ki, blk, window, scale):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = qi * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(_visible(qpos, kpos, window), s, NEG)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, blk, window, scale):
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = band_first(qi * blk, window, blk) + j

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ki <= qi)
    def _():
        v = v_ref[...]
        s = _scores(q_ref[...], k_ref[...], qi, ki, blk, window, scale)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row wholly masked in this block adds exp(0) here; the diagonal
        # block comes last, holds a real score for every row, and its
        # alpha = exp(NEG - m) wipes that
        p = jnp.exp(s - m_next[:, :1])
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_next

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, blk, window, scale):
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = band_first(qi * blk, window, blk) + j

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(ki <= qi)
    def _():
        k = k_ref[...]
        s = _scores(q_ref[...], k, qi, ki, blk, window, scale)
        p = jnp.exp(s - lse_ref[...][:, :1])
        dp = jax.lax.dot_general(do_ref[...], v_ref[...],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[...][:, :1])
        dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, blk, window, scale, n):
    ki, g, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    qi = ki + j

    @pl.when((g == 0) & (j == 0))
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(qi <= _last_query(ki, blk, window, n))
    def _():
        q, do = q_ref[...], do_ref[...]
        s = _scores(q, k_ref[...], qi, ki, blk, window, scale)
        p = jnp.exp(s - lse_ref[...][:, :1])
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[...], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[...][:, :1])
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((g == pl.num_programs(3) - 1) & (j == pl.num_programs(4) - 1))
    def _():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _geometry(q, num_heads, num_kv_heads, window):
    b, T, _ = q.shape
    d = q.shape[-1] // num_heads
    blk = kernel_block(T)
    n, keys, queries = _span(T, blk, window)
    return b, T, d, blk, n, keys, queries, num_heads // num_kv_heads


def _band_maps(blk, window, group):
    """Index maps of the kernels whose grid is (batch, head, query block,
    step in the band): the key block is clamped to the diagonal, so a step
    past the band fetches nothing new and its body is skipped."""
    def key_block(i, j):
        return jnp.minimum(band_first(i * blk, window, blk) + j, i)

    return {
        "q": lambda b, h, i, j: (b, i, h),
        "kv": lambda b, h, i, j: (b, key_block(i, j), h // group),
        "row": lambda b, h, i, j: (b, h, i, 0),
    }


def attention_pallas(q, k, v, num_heads, num_kv_heads, window):
    """(out [b, T, heads*d], lse [b, heads, T, 128]) by the forward
    kernel."""
    b, T, d, blk, n, keys, _, group = _geometry(q, num_heads, num_kv_heads,
                                                window)
    maps = _band_maps(blk, window, group)
    kernel = functools.partial(_fwd_kernel, blk=blk, window=window,
                               scale=d ** -0.5)
    return pl.pallas_call(
        kernel, name="attention_fwd",
        grid=(b, num_heads, n, keys),
        in_specs=[pl.BlockSpec((None, blk, d), maps["q"]),
                  pl.BlockSpec((None, blk, d), maps["kv"]),
                  pl.BlockSpec((None, blk, d), maps["kv"])],
        out_specs=[pl.BlockSpec((None, blk, d), maps["q"]),
                   pl.BlockSpec((None, None, blk, LANES), maps["row"])],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, num_heads, T, LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, LANES), jnp.float32),
                        pltpu.VMEM((blk, LANES), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        compiler_params=_params(("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=on_cpu(),
    )(q, k, v)


def attention_pallas_bwd(q, k, v, out, lse, dout, num_heads, num_kv_heads,
                         window):
    """(dq, dk, dv): one kernel over query blocks for ``dq``, one over key
    blocks for ``dk``/``dv`` that sums a group's query heads in VMEM."""
    b, T, d, blk, n, keys, queries, group = _geometry(
        q, num_heads, num_kv_heads, window)
    scale = d ** -0.5
    delta = jnp.sum((out.astype(jnp.float32) * dout.astype(jnp.float32))
                    .reshape(b, T, num_heads, d), axis=-1)
    delta = jnp.broadcast_to(jnp.swapaxes(delta, 1, 2)[..., None],
                             (b, num_heads, T, LANES))
    maps = _band_maps(blk, window, group)
    rows = pl.BlockSpec((None, None, blk, LANES), maps["row"])
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, blk=blk, window=window, scale=scale),
        name="attention_dq",
        grid=(b, num_heads, n, keys),
        in_specs=[pl.BlockSpec((None, blk, d), maps["q"]),
                  pl.BlockSpec((None, blk, d), maps["kv"]),
                  pl.BlockSpec((None, blk, d), maps["kv"]),
                  pl.BlockSpec((None, blk, d), maps["q"]), rows, rows],
        out_specs=pl.BlockSpec((None, blk, d), maps["q"]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        compiler_params=_params(("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=on_cpu(),
    )(q, k, v, dout, lse, delta)

    def query_block(i, j):
        return jnp.minimum(i + j, _last_query(i, blk, window, n))

    q_map = lambda b, h, i, g, j: (b, query_block(i, j), h * group + g)
    kv_map = lambda b, h, i, g, j: (b, i, h)
    row_map = lambda b, h, i, g, j: (b, h * group + g, query_block(i, j), 0)
    rows = pl.BlockSpec((None, None, blk, LANES), row_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, blk=blk, window=window, scale=scale,
                          n=n),
        name="attention_dkv",
        grid=(b, num_kv_heads, n, group, queries),
        in_specs=[pl.BlockSpec((None, blk, d), q_map),
                  pl.BlockSpec((None, blk, d), kv_map),
                  pl.BlockSpec((None, blk, d), kv_map),
                  pl.BlockSpec((None, blk, d), q_map), rows, rows],
        out_specs=[pl.BlockSpec((None, blk, d), kv_map),
                   pl.BlockSpec((None, blk, d), kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        compiler_params=_params(("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=on_cpu(),
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv
