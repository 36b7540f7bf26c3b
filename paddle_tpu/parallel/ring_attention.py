"""Ring attention: sequence/context parallelism over the device mesh.

The reference predates sequence parallelism — its long-sequence story is
ragged efficiency (LoD, SURVEY.md §5); scaling sequence LENGTH across chips
is the TPU-native extension this framework adds as first-class: shard the
sequence axis over a mesh axis ("sp"), keep each device's Q block resident,
and rotate K/V blocks around the ring with ``lax.ppermute`` while
accumulating attention in an online (flash-style) numerically stable
softmax. Communication rides ICI neighbor links (the ppermute ring), so
per-step traffic is one K/V block per hop — the standard ring-attention
recipe (shard_map + collective-permute) rather than an all-gather of the
full sequence.

API: ``ring_attention(q, k, v, mesh, axis="sp", causal=False,
batch_axis=None)`` with [batch, seq, heads, head_dim] inputs sharded on
seq; ``batch_axis`` composes dp×sp (batch rows sharded over a
data-parallel mesh axis while the ring runs over sp). Numerics match full
softmax attention (pinned by tests on the 8-virtual-device mesh and the
dryrun's composed dp×sp training-step equality).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def _block_attention(q, k, v, m_prev, l_prev, acc_prev, mask=None):
    """One K/V block's contribution under online softmax.

    q [b, sq, h, d], k/v [b, sk, h, d]; m/l [b, h, sq] running max and
    normalizer; acc [b, sq, h, d] running weighted values.
    """
    scale = q.shape[-1] ** -0.5
    # [b, h, sq, sk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    m_block = jnp.max(scores, axis=-1)                    # [b, h, sq]
    m_new = jnp.maximum(m_prev, m_block)
    # guard: fully-masked blocks produce -inf maxima; exp(-inf - -inf) traps
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - safe_m[..., None])               # [b, h, sq, sk]
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    correction = jnp.exp(jnp.where(jnp.isneginf(m_prev), -jnp.inf,
                                   m_prev - safe_m))
    correction = jnp.where(jnp.isneginf(m_prev), 0.0, correction)
    l_new = correction * l_prev + jnp.sum(p, axis=-1)
    acc_new = (acc_prev * correction.transpose(0, 2, 1)[..., None]
               + jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return m_new, l_new, acc_new


@functools.lru_cache(maxsize=64)
def _build_ring_fn(mesh, axis, causal, batch_axis=None):
    """Compiled ring step, cached per (mesh, axis, causal, batch_axis) so a
    training loop calling ring_attention every step hits the jit cache
    instead of retracing (jit keys on the function object). ``batch_axis``
    composes sequence parallelism with data parallelism: batch rows shard
    over that mesh axis while the ring runs per-dp-slice over ``axis``."""
    sp = mesh.shape[axis]
    spec = P(batch_axis, axis, None, None)

    def local(qb, kb, vb):
        rank = lax.axis_index(axis)
        b, sq, h, d = qb.shape
        blk = sq  # per-device block length
        m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, sq), jnp.float32)
        acc0 = jnp.zeros(qb.shape, jnp.float32)
        perm = [(i, (i + 1) % sp) for i in range(sp)]  # ring: pass right

        def body(i, carry):
            kb_i, vb_i, m, l, acc = carry
            # the K/V block currently held arrived from rank - i
            src = (rank - i) % sp

            def attend(carry3):
                m, l, acc = carry3
                mask = None
                if causal:
                    q_pos = rank * blk + jnp.arange(sq)[:, None]    # [sq, 1]
                    k_pos = src * blk + jnp.arange(kb_i.shape[1])[None]
                    mask = (q_pos >= k_pos)[None, None]             # 1,1,sq,sk
                return _block_attention(qb.astype(jnp.float32),
                                        kb_i.astype(jnp.float32),
                                        vb_i.astype(jnp.float32),
                                        m, l, acc, mask)

            if causal:
                # blocks entirely in the future (src > rank) contribute
                # nothing: skip their einsums — halves causal FLOPs
                m, l, acc = lax.cond(src > rank,
                                     lambda c: c, attend, (m, l, acc))
            else:
                m, l, acc = attend((m, l, acc))
            kb_i = lax.ppermute(kb_i, axis, perm)
            vb_i = lax.ppermute(vb_i, axis, perm)
            return kb_i, vb_i, m, l, acc

        _, _, m, l, acc = lax.fori_loop(0, sp, body, (kb, vb, m0, l0, acc0))
        l = jnp.maximum(l, 1e-20)
        out = acc / l.transpose(0, 2, 1)[..., None]
        return out.astype(qb.dtype)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return jax.jit(fn), NamedSharding(mesh, spec)


def ring_attention(q, k, v, mesh, axis="sp", causal=False,
                   batch_axis=None):
    """Multi-head attention with the SEQUENCE axis sharded over
    ``mesh[axis]``. Inputs [batch, seq, heads, head_dim]; seq must divide
    the axis size. ``batch_axis`` additionally shards batch rows over a
    data-parallel mesh axis (dp×sp composition). Returns the attention
    output with the same sharding."""
    sp = mesh.shape[axis]
    seq = q.shape[1]
    assert seq % sp == 0, (seq, sp)
    if batch_axis is not None:
        assert q.shape[0] % mesh.shape[batch_axis] == 0, \
            (q.shape[0], mesh.shape[batch_axis])
    fn, sharding = _build_ring_fn(mesh, axis, bool(causal), batch_axis)
    q = jax.device_put(q, sharding)
    k = jax.device_put(k, sharding)
    v = jax.device_put(v, sharding)
    return fn(q, k, v)


def full_attention(q, k, v, causal=False):
    """Single-device reference: plain softmax attention (for tests)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
