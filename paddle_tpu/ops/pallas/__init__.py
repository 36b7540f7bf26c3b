"""The Pallas kernel tier: a small library of fused TPU primitives.

Design template: *Tensor Processing Primitives* (PAPERS.md) — the op layer
targets a SMALL set of fused kernels (conv+bn+relu epilogues, one-kernel
optimizer steps, rowwise embedding updates, whole-recurrence RNN/CTC)
instead of growing one-off kernels per call site. Every kernel here has a
jnp twin with pinned numerics (tests run the kernels in interpret mode on
CPU), and every dispatch site routes through :func:`use_pallas` so tier
selection, per-kernel fallback, and profiler attribution live in ONE place.

Tier selection (the ``kernel_tier`` flag):

* ``auto`` (default) — Pallas on TPU for the families in
  :data:`AUTO_PALLAS` (admitted by an on-chip observation, see there), jnp
  everywhere else (CPU suites never pay interpret-mode kernels unless they
  opt in).
* ``pallas`` — Pallas for every family (interpret mode on CPU: this is
  what the parity tests run). On a TPU a kernel that Mosaic cannot compile
  raises its compile error: nothing between :func:`use_pallas` and
  ``pallas_call`` catches it (``ctc``, ``embedding_sgd`` and
  ``paged_attention`` do not lower: PR 21).
* ``jnp`` — the plain jax.numpy lowerings, bitwise-identical to the
  pre-tier behavior.

Fallback contract: when the tier resolves to Pallas but a dispatch site
reports the shape/config unsupported (``supported=False``), the call
SILENTLY routes to the jnp twin and bumps a per-kernel counter
(:func:`fallback_counts`) — an unsupported shape is a routing decision,
never an error. Profiler spans (``pallas/<kernel>`` vs ``jnp/<kernel>``,
kind="kernel") land in chrome traces so the two paths are distinguishable
per op.
"""

from __future__ import annotations

from contextlib import contextmanager

from ...core.flags import get_flag
from ...core.profiler import record_event
from ...obs.metrics import REGISTRY as _METRICS

# Families that default to Pallas under kernel_tier=auto on a TPU. A family
# is admitted only by an on-chip observation at the shapes the repo runs:
# tools/kernel_probe.py on the chip (it lowered natively, matched its jnp
# twin, and was not slower in a same-process A/B) AND a benchmark cell whose
# step it does not slow. The next family's evidence must come from the same
# two places. One entry a family, taken on a TPU v5 lite, jax 0.9.0 (PR 21
# where no other PR is named; full lines in CHANGES.md / PERF.md):
#   lstm          IN   bitwise equal to the scan twin; recurrence 0.458 vs
#                      0.558 ms (1.22x, PR 21). With the backward a kernel
#                      too (lstm_bwd, PR 27) the benchmark's LSTM cells
#                      read 41.0 ms a step at 256 x 512 (62.3 before) and
#                      +45% samples/s over ragged lengths; the reverse
#                      kernel is 1.9-2.7x its scan at L = 64..512
#   attention     IN   (PR 28) banded grouped-query causal attention, three
#                      kernels that skip key blocks outside the band. At
#                      8192 x 32/4 heads of 128, bf16: forward 3.73 ms
#                      (window 1024) / 9.74 (full) against the blocked twin's
#                      2.84 / 20.87, backward 7.41 / 18.87 against 11.65 /
#                      33.70; at the step (three window layers, one full)
#                      191.6 ms against 214.3 on the twin, which also holds
#                      each block's scores in HBM (19.3 GB peak for 11.6)
#   grouped_matmul IN  (PR 28) a tile of rows meets one expert's resident
#                      weights. 8192 rows over 8 experts of 2304 x 896:
#                      0.80-1.20 ms a product against 1.28-1.59 for
#                      XLA:TPU's own ragged-dot kernel (512 x 256 x 128
#                      tiles), equal to the bit; at the step 191.6 ms
#                      against 215.2
#   moe_combine   IN   (PR 32) routed_experts' rows -> tokens, forward and
#                      backward: a gather and a sum over blocks of tokens
#                      that reads only the rows in use. A family of its own
#                      and not a fourth grouped_matmul kernel: its shape
#                      predicate is about tokens and scalar memory, not an
#                      expert's weights, so either falls back without the
#                      other, and the counters say apart that it engaged.
#                      18432 rows of 2304 float32 (8.5 k in use) to 8192
#                      tokens: 0.37 ms on the device against 2.69 for the
#                      scatter-add with its mask and weights (0.51 / 2.84 by
#                      the probe's host clock), equal to the bit; at the
#                      step 142.7 ms against 164.6, six of six pairs
#   delta_rule    IN   (PR 35) gated_delta_rule's chunked core, forward
#                      and backward: a chunk's terms live in VMEM, the
#                      [128, 128] state (or its gradient) is carried in
#                      scratch, the gradients are hand-derived. At 4096
#                      tokens x 32 heads of 128, chunks of 64, bfloat16
#                      q / k / v, the Kimi-Linear configuration's decays:
#                      forward 3.82 ms against the chunked jnp scan's 9.00
#                      (2.36x), backward 7.01 against 40.70 (5.80x), apart
#                      by one bfloat16 rounding of the outputs (4.5e-3 /
#                      7.8e-3 of the largest); at the step (four layers)
#                      172.3 ms against 336.8, six of six pairs
#   causal_conv1d IN   (PR 37) the mixers' short convolution with its SiLU,
#                      forward and backward: the whole time axis of 256
#                      channels in VMEM, shifted copies as loads at a sublane
#                      offset from a float32 scratch column, hand-derived
#                      gradients, each array across HBM once. At 4096 tokens,
#                      4 taps, bfloat16: 4096 channels without a bias
#                      forward 0.122 ms a call in a chain (0.215 by the
#                      probe's host clock) against the compiled jnp op's
#                      0.138 (0.248), backward 0.205 (0.492) against
#                      0.961 (1.202); 6144 channels with a bias 0.231 /
#                      0.367 against 0.247 / 2.542; one bfloat16 rounding
#                      apart, filter and bias gradients 1.2e-5. At the step
#                      the Kimi-Linear cell (12 + 12 calls) reads 159.8 ms
#                      against 172.5, four of four pairs, 4.4 ms of it
#                      `mul_grad` fusions that no longer carry the old
#                      backward's float32 passes; the Nemotron cell (3 + 3)
#                      112.6 against 114.9, three of three: its own calls
#                      fall from 5.56 to 1.10 ms a step, but `ssd_scan` and
#                      `gated_rms_norm` pay 4.3 ms for operands that now
#                      arrive row-major (XLA had them time-minor)
#   ssd_scan      IN   (PR 39) the Mamba-2 state-space core, forward and
#                      backward: a grid step is a group's heads of one
#                      chunk, `C B^T` once a group, the pairwise decay, the
#                      read and the contribution as values in VMEM, the
#                      group's [R * P, N] states (or their gradient) carried
#                      in scratch, hand-derived gradients; operands stay
#                      row-major. At 4096 tokens, 64 heads of 64 in 8 groups
#                      of state 128, chunks of 128, bfloat16, the Nemotron
#                      configuration's decays, `_prepare` on both sides:
#                      forward 0.454 ms on the device (0.668 by the probe's
#                      host clock) against the chunked jnp program's 1.720
#                      (1.946), backward 0.973 (1.271) against 3.293
#                      (3.590); outputs one bfloat16 rounding apart, every
#                      gradient nearer the float32 core than the twin's. At
#                      the step (three layers) the Nemotron cell reads
#                      105.3 ms against 112.8, six of six pairs: the 2.5 ms
#                      the jnp core paid to change its operands' layout
#                      went with it
#   conv_bn       out  lowers, but 0.2-0.65x of XLA's conv+BN fusions at
#                      6 of 7 ResNet-50 shapes; fused flagship step 318.6
#                      vs 102.5 ms unfused
#   optimizer     out  arena momentum step 68.4 vs 26.0 ms (0.38x): the
#                      per-step concat/split costs more than it saves
#   ctc           out  does not lower: (1, S) block of a [b, S] array
#   embedding_sgd out  does not lower: (1, D) block of a [R, D] array
#   paged_attention out does not lower under this jax
#   gru           out  recurrence 1.61x its scan, but no step measured: no
#                      cell runs a GRU
AUTO_PALLAS = frozenset({"lstm", "attention", "grouped_matmul",
                         "moe_combine", "delta_rule", "causal_conv1d",
                         "ssd_scan"})

# pallas->jnp silent-fallback counter, in the obs.metrics registry
# (fallback_counts() derives its historical dict from this family)
_M_FALLBACKS = _METRICS.counter(
    "paddle_tpu_pallas_fallbacks",
    "unsupported shapes routed pallas->jnp silently, per kernel family",
    labels=("kernel",))

# Pallas dispatches by how they lowered: "native" (Mosaic, on a TPU) or
# "interpret" (the CPU interpreter) — what chip_smoke.py reads to prove
# that no kernel on the chip ran interpreted
_M_DISPATCHES = _METRICS.counter(
    "paddle_tpu_pallas_dispatches",
    "Pallas kernel dispatches (counted at trace time, once per retrace), "
    "per kernel family and lowering mode (native|interpret)",
    labels=("kernel", "mode"))


def on_cpu():
    """Shared interpret-mode predicate: every kernel module passes
    ``interpret=on_cpu()`` to pallas_call so CPU (tests, smoke benches)
    runs the same kernel bodies through the interpreter."""
    import jax
    return jax.default_backend() == "cpu"


def _tier():
    t = get_flag("kernel_tier")
    if t not in ("auto", "pallas", "jnp"):
        raise ValueError(
            f"kernel_tier must be auto|pallas|jnp, got {t!r}")
    return t


def _on_tpu():
    import jax
    return jax.default_backend() == "tpu"


def resolve_tier():
    """The tier the ``kernel_tier`` flag resolves to: 'pallas' or 'jnp'
    ('auto' = pallas on TPU, jnp elsewhere — per-kernel AUTO_PALLAS
    membership is applied in :func:`use_pallas`, not here)."""
    t = _tier()
    if t == "auto":
        return "pallas" if _on_tpu() else "jnp"
    return t


def use_pallas(kernel, supported=True):
    """Should this dispatch take the Pallas path? THE routing rule of the
    kernel tier: every dispatch site asks it and nothing else.

    ``kernel`` names the kernel family ("lstm", "gru", "ctc", "conv_bn",
    "optimizer", "embedding_sgd", "paged_attention", "attention",
    "grouped_matmul", "moe_combine", "delta_rule", "causal_conv1d",
    "ssd_scan");
    ``supported`` is the call site's
    shape/config predicate. Unsupported shapes under a Pallas tier fall
    back to the jnp twin with a counter bump (never an error).
    """
    t = _tier()
    want = t == "pallas" or (t == "auto" and kernel in AUTO_PALLAS
                             and _on_tpu())
    if want and not supported:
        record_fallback(kernel)
        return False
    return want


def record_fallback(kernel):
    _M_FALLBACKS.labels(kernel=kernel).inc()
    # flight recorder: a silent tier downgrade is exactly the kind of
    # decision an incident bundle must surface (a fleet quietly running
    # jnp twins explains a perf regression)
    from ...obs.recorder import record as _flight_record
    _flight_record("pallas_fallback", component="ops.pallas",
                   kernel=kernel)


def fallback_counts():
    """{kernel: times an unsupported shape routed pallas->jnp} — derived
    from the ``paddle_tpu_pallas_fallbacks`` registry counter; kernels
    with zero fallbacks are omitted (the historical dict shape)."""
    out = {}
    for key, child in _M_FALLBACKS.children().items():
        n = int(child.value)
        if n:
            out[key[0]] = n
    return out


def reset_fallback_counts():
    """TEST hygiene: zero the fallback counters (scrape consumers treat
    counters as monotonic — do not call outside tests)."""
    _M_FALLBACKS.reset()


def dispatch_counts():
    """{kernel: {"native": n, "interpret": m}} — Pallas dispatches since
    process start by lowering mode, derived from the
    ``paddle_tpu_pallas_dispatches`` registry counter; families that
    never dispatched a Pallas kernel are omitted."""
    out = {}
    for (kernel, mode), child in _M_DISPATCHES.children().items():
        n = int(child.value)
        if n:
            out.setdefault(kernel, {"native": 0, "interpret": 0})[mode] = n
    return out


@contextmanager
def kernel_span(tier, kernel):
    """Profiler span around one kernel dispatch: chrome traces show
    ``pallas/<kernel>`` vs ``jnp/<kernel>`` (kind="kernel") so tier time is
    attributable per op. Host spans: real time in eager mode, trace-time
    under jit (the repo's standard record_event semantics). Every
    non-jnp span is also one counted Pallas dispatch
    (:func:`dispatch_counts`)."""
    if tier != "jnp":
        _M_DISPATCHES.labels(
            kernel=kernel,
            mode="interpret" if on_cpu() else "native").inc()
    with record_event(f"{tier}/{kernel}", kind="kernel"):
        yield


# kernel modules (conv_bn, optimizer, embedding, rnn, ctc, ...) are imported
# lazily by their dispatch sites: the tier layer itself must stay cheap to
# import (it is pulled in at ops-package import time)

__all__ = [
    "AUTO_PALLAS", "resolve_tier", "use_pallas", "record_fallback",
    "fallback_counts", "reset_fallback_counts", "dispatch_counts",
    "kernel_span",
]
