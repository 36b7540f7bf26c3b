"""LoD (Level-of-Detail) ragged-sequence support.

The reference's signature data structure is the LoDTensor: a dense tensor of
concatenated variable-length sequences plus nested offset tables
(/root/reference/paddle/fluid/framework/lod_tensor.h:55-107). Every sequence op
propagates those offsets, and RNNs run directly on the ragged layout via
sequence2batch reordering (/root/reference/paddle/fluid/operators/math/
sequence2batch.h) and ragged<->padded converters
(operators/math/sequence_padding.h:64-71).

TPU-native re-design: XLA wants static shapes, so on device a level-1 LoD tensor
is a ``LoDArray``: padded dense data of shape [batch, max_len, ...] plus an
int32 ``lens`` vector of true lengths. ``lens`` lives on device (it is data, so
changing lengths never recompiles); max_len is static (bucketed padding at the
feed boundary keeps recompiles bounded). Sequence ops mask with
``mask = iota(max_len) < lens[:, None]`` instead of walking offsets — that is
the ragged->padded packing the reference performs in sequence_padding.h promoted
to the XLA boundary, exactly as SURVEY.md §5 prescribes.

Host-side conversion helpers keep API parity with the reference's
``create_lod_tensor`` (python/paddle/fluid/lod_tensor.py) recursive-seq-lens
interface.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .profiler import record_event
from ..obs.metrics import REGISTRY as _METRICS

_M_PACK = _METRICS.counter(
    "paddle_tpu_lod_pack_elements",
    "sequence positions through core.lod.pack_sequences: real = the "
    "sequences' own lengths summed; padded = batch x padded length, what "
    "the device is given to work on (real / padded is the packing's fill)",
    labels=("kind",))
_M_PACK_REAL = _M_PACK.labels(kind="real")
_M_PACK_PADDED = _M_PACK.labels(kind="padded")


@jax.tree_util.register_pytree_node_class
class LoDArray:
    """Padded device representation of a LoD tensor.

    data: [batch, max_len, *feature] padded with zeros past each row's length
    lens: [batch] int32 true sequence lengths (the INNERMOST LoD level)
    outer_lens: optional outer LoD levels grouping the ``batch`` rows — the
        nested-offsets capability of the reference LoD
        (framework/lod_tensor.h:55, arbitrarily nested ``LoD =
        vector<Vector<size_t>>``). Either

        * a single [n_outer] int32 array — one extra level
          (sum(outer_lens) == batch), e.g. beam-search output grouping
          batch*beam sentence rows by source sentence; or
        * a tuple of arrays OUTERMOST FIRST for deeper nesting: each level's
          lens sum to the number of entries of the level below it, and the
          innermost tuple entry sums to ``batch``.
    """

    __slots__ = ("data", "lens", "_outer")

    def __init__(self, data, lens, outer_lens=None):
        self.data = data
        self.lens = lens
        self.outer_lens = outer_lens

    @property
    def outer_lens(self):
        """None (level-1), the single outer array (level-2, the dominant
        case — callers index it directly), or the outermost-first tuple of
        arrays (level-3+)."""
        if not self._outer:
            return None
        if len(self._outer) == 1:
            return self._outer[0]
        return self._outer

    @outer_lens.setter
    def outer_lens(self, value):
        if value is None:
            self._outer = ()
        elif isinstance(value, (tuple, list)):
            self._outer = tuple(value)
        else:
            self._outer = (value,)

    @property
    def outer_levels(self):
        """All outer levels as a tuple, outermost first (empty for level-1)."""
        return self._outer

    # pytree protocol: traces through jit/grad/scan transparently; aux is the
    # outer-level count (bool back-compat: False==0 / True==1 pickles match)
    def tree_flatten(self):
        return (self.data, self.lens) + self._outer, len(self._outer)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, lens = children[0], children[1]
        n = int(aux)
        return cls(data, lens, tuple(children[2:2 + n]) if n else None)

    @property
    def batch(self):
        return self.data.shape[0]

    @property
    def max_len(self):
        return self.data.shape[1]

    @property
    def lod_level(self):
        return 1 + len(self._outer)

    def mask(self, dtype=jnp.float32):
        """[batch, max_len] 1/0 validity mask."""
        return (jnp.arange(self.data.shape[1])[None, :]
                < self.lens[:, None]).astype(dtype)

    def row_to_outer(self, level=-1):
        """[n_below] int32: for each entry of the level below, the index of
        its parent group in outer level ``level`` (default: the innermost
        outer level, mapping data rows to their group)."""
        lens = self._outer[level]
        starts = jnp.cumsum(lens)
        n_below = self.data.shape[0] if level in (-1, len(self._outer) - 1) \
            else self._outer[level + 1].shape[0]
        return jnp.searchsorted(starts, jnp.arange(n_below),
                                side="right").astype(jnp.int32)

    def __repr__(self):
        extra = f", outer_lens={self.outer_lens}" if self._outer else ""
        return (f"LoDArray(data={getattr(self.data, 'shape', None)}, "
                f"lens={self.lens}{extra})")


def pack_sequences(seqs, dtype=None, max_len=None, pad_multiple=1):
    """List of [len_i, *feature] numpy arrays -> host LoDArray (padded + lens).

    ``pad_multiple`` buckets max_len up to a multiple to bound the number of
    distinct compiled shapes (the bucketed-padding policy from SURVEY.md §5).
    """
    with record_event("lod.pack", kind="reader"):
        lens = np.array([len(s) for s in seqs], dtype=np.int32)
        ml = int(max_len if max_len is not None
                 else (lens.max() if len(lens) else 0))
        if pad_multiple > 1:
            ml = ((ml + pad_multiple - 1) // pad_multiple) * pad_multiple
        ml = max(ml, 1)
        first = np.asarray(seqs[0])
        feat = first.shape[1:]
        dt = dtype or first.dtype
        out = np.zeros((len(seqs), ml) + tuple(feat), dtype=dt)
        for i, s in enumerate(seqs):
            s = np.asarray(s, dtype=dt)
            out[i, : len(s)] = s
        _M_PACK_REAL.inc(int(lens.sum()))
        _M_PACK_PADDED.inc(len(seqs) * ml)
        return LoDArray(out, lens)


def lod_from_lens(lens) -> list:
    """lengths -> reference-style level-1 offset table [[0, l0, l0+l1, ...]]."""
    offs = np.concatenate([[0], np.cumsum(np.asarray(lens))]).astype(np.int64)
    return [offs.tolist()]


def lens_from_lod(lod) -> np.ndarray:
    offs = np.asarray(lod[0] if isinstance(lod[0], (list, tuple, np.ndarray)) else lod)
    return np.diff(offs).astype(np.int32)


def flat_to_lodarray(flat, lod, pad_multiple=1):
    """Reference feed form (concatenated [sum_len, *feat] array, offset lod)
    -> padded LoDArray. Handles arbitrarily nested LoD — level-1
    ([[offsets]]), level-2 ([[outer offsets], [token offsets]]), level-N
    (framework/lod_tensor.h:55 ``LoD = vector<Vector<size_t>>``, outermost
    first). This is the feed-boundary packer."""
    lod = list(lod)
    inner = lod[-1]
    lens = lens_from_lod([inner])
    flat = np.asarray(flat)
    seqs, start = [], 0
    for ln in lens:
        seqs.append(flat[start:start + int(ln)])
        start += int(ln)
    arr = pack_sequences(seqs, dtype=flat.dtype, pad_multiple=pad_multiple)
    if len(lod) > 1:
        arr.outer_lens = tuple(lens_from_lod([lvl]) for lvl in lod[:-1])
    return arr


def lodarray_to_flat(arr: LoDArray):
    """Padded LoDArray -> (concatenated numpy array, offset lod): the fetch-
    boundary unpacker, restoring the reference's LoDTensor wire form (with
    every nesting level for multi-level LoD)."""
    data = np.asarray(arr.data)
    lens = np.asarray(arr.lens)
    parts = [data[i, : int(lens[i])] for i in range(len(lens))]
    flat = np.concatenate(parts, axis=0) if parts else np.zeros((0,) + data.shape[2:],
                                                               data.dtype)
    lod = lod_from_lens(lens)
    for lvl in reversed(arr.outer_levels):
        lod = lod_from_lens(np.asarray(lvl)) + lod
    return flat, lod


def sequence_mask(lens, max_len, dtype=jnp.float32):
    return (jnp.arange(max_len)[None, :] < lens[:, None]).astype(dtype)
