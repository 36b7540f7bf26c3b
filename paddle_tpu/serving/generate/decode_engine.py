"""GenerationEngine: stateful autoregressive decode over a saved program.

The :class:`~..engine.InferenceEngine` sibling for generative bundles. A
generative saved program is a decoder-only LM over a token window —
feeds ``tokens`` (``[batch, seq, 1]`` int64, plus optional ``positions``),
one logits fetch ``[batch, seq, vocab]`` — whose attention sites are
``causal_self_attention`` ops (fluid.layers.causal_self_attention). The
engine SPLITS that one program into the two serving phases:

* **prefill** — the program cloned with every attention site rewritten to
  ``prefill_attention``: causal attention over the (bucket-padded) prompt
  window that also scatters each position's K/V into the paged arena
  (kvcache.py). One executable per prompt-length bucket, compiled at
  :meth:`warmup`.
* **chunked prefill** — a third clone rewritten to
  ``chunked_prefill_attention``: a prompt CHUNK attending over arena
  context that is already there (a cached shared prefix, previous
  chunks). Built and warmed only when the prefix cache
  (``serving_prefix_cache_blocks``) or chunking
  (``serving_prefill_chunk``) is enabled, so disabled engines compile
  exactly what they did before. A request whose prompt prefix is cached
  attaches to the cached blocks and prefills only its uncached tail; a
  long cold prompt (with chunking on) admits immediately and prefills
  one bounded chunk per :meth:`step` boundary, so in-flight decode
  streams keep producing tokens while it loads.
* **decode** — the clone rewritten to ``paged_attention``: a fixed-shape
  ``[max_seqs, 1]`` step over the arena. Ragged in-flight sequences share
  this ONE executable through their block tables and context lengths;
  idle slots ride along masked (sentinel slot, context length 0). The hot
  path never retraces — ``stats()`` carries per-phase compile/hit
  counters and the same ``hot_recompiles`` alarm the feed-forward engine
  has.

Sampling is host-side and PER-SEQUENCE — greedy argmax, top-k (own
``numpy.RandomState`` seeded per request), or beam search riding the
dense ``beam_search`` op (ops/control_flow_ops.py) with copy-on-write
block-table forks for hypothesis reordering. Because the phase ops are
row-independent and sampling state is per-sequence, a sequence's token
stream is BITWISE identical whether it decodes alone or joins a running
continuous batch — the parity contract the scheduler and tests pin.
"""

from __future__ import annotations

import threading

import numpy as np

from ...core.flags import get_flag
from ...core.profiler import record_event
from ...core.scope import Scope
from ...obs import perf as _perf
from ...obs.metrics import REGISTRY as _METRICS, json_safe, next_instance
from ...obs.recorder import record as _flight_record
from .. import execcache as _execcache
from ..engine import commit_scope_arrays, parse_buckets
from . import kvstore as _kvstore
from .kvcache import CacheExhausted, PagedKVCache

_M_COMPILES = _METRICS.counter(
    "paddle_tpu_genengine_compiles",
    "GenerationEngine executable compiles, per instance/phase/bucket",
    labels=("instance", "phase", "bucket"))
_M_HITS = _METRICS.counter(
    "paddle_tpu_genengine_hits",
    "GenerationEngine trace-cache hits, per instance/phase/bucket",
    labels=("instance", "phase", "bucket"))
_M_HOT = _METRICS.counter(
    "paddle_tpu_genengine_hot_recompiles",
    "generation compiles observed AFTER warmup (the no-recompile alarm)",
    labels=("instance",))
# per-request serving quantities: TTFT (submit -> first ACTUAL token —
# stamped by the scheduler, which owns the submit clock; a request
# aborted before its first token DISCARDS its probe) and TPOT (mean
# time per output token after the first, recorded once at stream end
# for requests that emitted >= 2 tokens)
_M_TTFT = _METRICS.histogram(
    "paddle_tpu_genengine_ttft_seconds",
    "time to first token per generation request (submit -> first actual "
    "token), per engine instance", labels=("instance",),
    span_name="serving/ttft", span_kind="stage")
_M_TPOT = _METRICS.histogram(
    "paddle_tpu_genengine_tpot_seconds",
    "mean time per output token after the first, recorded once per "
    "finished stream that emitted >= 2 tokens, per engine instance",
    labels=("instance",), span_name="serving/tpot", span_kind="stage")

ATTENTION_OP = "causal_self_attention"
_SLOTS = "__kv_slots__"
_TABLES = "__kv_block_tables__"
_CTXLENS = "__kv_context_lens__"
_CHUNKSTART = "__kv_chunk_start__"


class NoFreeSlots(RuntimeError):
    """All ``max_seqs`` decode slots are occupied: the admission-control
    twin of :class:`CacheExhausted` for the slot dimension. The scheduler
    keeps the request queued until a sequence finishes."""


def _kv_name(kind, layer):
    return f"__kv_{kind}_{layer}__"


def normalize_sampling(sampling):
    """Validate/default a sampling spec (a plain dict so it crosses the
    RPC wire untouched): ``mode`` greedy | topk | beam, with ``top_k``/
    ``temperature``/``seed`` for topk and ``beam_size`` for beam;
    ``eos_id`` (None = run to max_new_tokens) applies to all modes."""
    s = dict(sampling or {})
    mode = s.pop("mode", "greedy")
    out = {"mode": mode,
           "eos_id": s.pop("eos_id", None),
           "top_k": int(s.pop("top_k", 8)),
           "temperature": float(s.pop("temperature", 1.0)),
           "seed": int(s.pop("seed", 0)),
           "beam_size": int(s.pop("beam_size", 4))}
    if s:
        raise ValueError(f"unknown sampling fields {sorted(s)}")
    if mode not in ("greedy", "topk", "beam"):
        raise ValueError(f"sampling mode must be greedy|topk|beam, "
                         f"got {mode!r}")
    if mode == "topk" and out["top_k"] <= 0:
        raise ValueError("top_k must be positive")
    if mode == "topk" and out["temperature"] <= 0:
        raise ValueError("temperature must be positive")
    if mode == "beam" and out["beam_size"] < 2:
        raise ValueError("beam_size must be >= 2")
    if out["eos_id"] is not None:
        out["eos_id"] = int(out["eos_id"])
    return out


def _log_softmax(x):
    x = x - x.max()
    return x - np.log(np.exp(x).sum())


class _Sequence:
    """One decode slot's state (a beam hypothesis is one of these too)."""

    __slots__ = ("seq_id", "slot", "next_token", "emitted", "max_new",
                 "params", "rng", "group", "finished", "user_data",
                 "prompt", "pending", "prefilling")

    def __init__(self, seq_id, slot, params, max_new):
        self.seq_id = seq_id
        self.slot = slot
        self.params = params
        self.max_new = max_new
        self.next_token = 0
        self.emitted = 0
        self.rng = np.random.RandomState(params["seed"] & 0x7FFFFFFF)
        self.group = None          # set for beam hypotheses
        self.finished = False
        self.user_data = None      # scheduler's stream handle
        self.prompt = None         # full prompt (prefix registration)
        self.pending = None        # prompt tail still to chunk-prefill
        self.prefilling = False    # occupies a slot but must not decode


class _BeamGroup:
    """A beam request: ``beam_size`` sequences advancing in lockstep."""

    __slots__ = ("seqs", "pre_ids", "pre_scores", "hist_ids",
                 "hist_parents", "steps", "max_new", "end_id", "finished",
                 "user_data", "prompt", "pending", "prefilling")

    def __init__(self, seqs, max_new, end_id):
        self.seqs = seqs
        self.max_new = max_new
        # -1 never matches a real token: "no EOS" runs to max_new
        self.end_id = -1 if end_id is None else int(end_id)
        self.pre_ids = None
        self.pre_scores = None
        self.hist_ids = []
        self.hist_parents = []
        self.steps = 0
        self.finished = False
        self.user_data = None
        self.prompt = None
        self.pending = None        # lead hypothesis's unprefilled tail
        self.prefilling = False


class GenerationEngine:
    """``GenerationEngine(model_dir)`` loads a generative bundle into a
    private scope and splits it; ``max_seqs``/``block_size``/``num_blocks``
    default from the ``serving_max_seqs`` / ``serving_kv_block_size`` /
    ``serving_kv_num_blocks`` flags; ``max_len`` bounds prompt+generation
    per sequence (it sizes the block-table width); ``prefill_buckets``
    are the prompt-length pads (default: powers of two up to ``max_len``).

    Thread safety: like InferenceEngine, dispatches serialize on a lock;
    the ContinuousBatcher drives the engine from one worker thread."""

    def __init__(self, model_dir=None, program=None, feed_names=None,
                 fetch_vars=None, executor=None, scope=None, max_seqs=None,
                 block_size=None, num_blocks=None, max_len=128,
                 prefill_buckets=None, prefix_cache_blocks=None,
                 prefill_chunk=None, exec_cache=None, kv_store=None,
                 donate_arena=True):
        import paddle_tpu.fluid as fluid

        self._scope = scope or Scope()
        self._exe = executor or fluid.Executor()
        if model_dir is not None:
            program, feed_names, fetch_vars = fluid.io.load_inference_model(
                model_dir, self._exe, scope=self._scope)
        if program is None or feed_names is None or fetch_vars is None:
            raise ValueError(
                "GenerationEngine needs model_dir= or all of program=/"
                "feed_names=/fetch_vars=")
        # persistent compiled-executable cache: each (phase, bucket)
        # executable loads from a fingerprint-matched artifact at warmup
        # instead of compiling (serving/execcache.py). The engine config
        # (max_seqs, max_len, arena geometry, chunking) needs no explicit
        # key — it is fully determined by the warmup feed shapes the
        # fingerprint already covers.
        self._bundle_hash = _execcache.bundle_content_hash(model_dir) \
            if model_dir else None
        self._exec_cache = _execcache.resolve_cache(model_dir, exec_cache) \
            if self._bundle_hash is not None else None
        self._warm_execs = {}          # (phase, bucket) -> WarmExecutable
        self._warm_loaded = set()      # keys whose executable was LOADED
        # numpy state's first dispatch would land a second jit cache
        # entry per executable once the run writes jax arrays back —
        # commit up front (see engine.commit_scope_arrays)
        commit_scope_arrays(self._scope)
        self._feed_names = list(feed_names)
        unknown = [n for n in self._feed_names
                   if n not in ("tokens", "positions")]
        if "tokens" not in self._feed_names or unknown:
            raise ValueError(
                "a generative bundle feeds 'tokens' (and optionally "
                f"'positions'); this one feeds {self._feed_names}")
        fetch_names = [v if isinstance(v, str) else v.name
                       for v in fetch_vars]
        if len(fetch_names) != 1:
            raise ValueError(
                f"a generative bundle fetches exactly its logits, "
                f"got {fetch_names}")
        self._logits_name = fetch_names[0]

        self.max_seqs = int(max_seqs if max_seqs is not None
                            else get_flag("serving_max_seqs"))
        self.max_len = int(max_len)
        if self.max_seqs <= 0 or self.max_len <= 0:
            raise ValueError("max_seqs and max_len must be positive")

        layers, heads, head_dim = self._attention_config(program)
        self.num_layers = layers
        self.cache = PagedKVCache(layers, heads, head_dim,
                                  num_blocks=num_blocks,
                                  block_size=block_size,
                                  prefix_cache_blocks=prefix_cache_blocks)
        # persistent KV-prefix spill tier (serving/generate/kvstore.py):
        # a published <version>/kv/ dir (read-only, manifest-pinned) or
        # the serving_kv_spill_dir flag's local tier. Keyed by the same
        # bundle content hash the exec cache uses plus the arena
        # geometry — no bundle bytes, no spill tier.
        self._kv_store = None
        if self._bundle_hash is not None and kv_store is not False:
            kv_fp = _kvstore.kv_fingerprint(
                self._bundle_hash, layers, heads, head_dim,
                self.cache.block_size, self.cache.k[0].dtype)
            self._kv_store = _kvstore.resolve_store(model_dir, kv_store,
                                                    kv_fp)
        self.cache.attach_spill(self._kv_store)
        # decode-arena donation: the phase executables alias the arena
        # feed buffers into the arena fetches (donate_argnums on a
        # dedicated jit argument), so the functional arena update stays
        # on device instead of allocating a fresh arena every dispatch.
        # Token streams are bitwise identical either way (donation is
        # aliasing, never arithmetic); donate_arena=False pins the
        # undonated twin for parity tests.
        self.donate_arena = bool(donate_arena)
        self._donate_feeds = tuple(sorted(self._arena_fetch_names())) \
            if self.donate_arena else ()
        self.prefill_chunk = int(prefill_chunk if prefill_chunk is not None
                                 else get_flag("serving_prefill_chunk"))
        self._table_width = self.cache.blocks_for(self.max_len)
        self._prefill_program = self._rewrite(program, "prefill_attention")
        self._decode_program = self._rewrite(program, "paged_attention")
        # the chunked-prefill executable family exists only when a
        # partial prefill can happen (cached-prefix tails, chunked
        # admission) — disabled engines compile exactly what they always
        # did, and warmup cost doesn't grow for them
        self._partial_enabled = (self.cache.prefix_cache_blocks > 0
                                 or self.prefill_chunk > 0)
        self._chunk_program = (
            self._rewrite(program, "chunked_prefill_attention")
            if self._partial_enabled else None)
        if prefill_buckets is None:
            b, buckets = 8, []
            while b < self.max_len:
                buckets.append(b)
                b *= 2
            buckets.append(b)
            prefill_buckets = buckets
        self.prefill_buckets = parse_buckets(prefill_buckets)

        self._slots = [None] * self.max_seqs
        self._groups = []
        self._prefill_queue = []   # FIFO of handles mid-chunked-prefill
        self._next_seq_id = 0
        self._lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._seen = set()
        # per-(phase, bucket) compile/hit counters live in the
        # obs.metrics registry under this engine's instance label;
        # stats() derives the historical phases dict from them
        self.obs_instance = next_instance("genengine")
        self._phase = {"prefill": {}, "chunk": {}, "decode": {}}
        self._m_hot = _M_HOT.labels(instance=self.obs_instance)
        # per-request TTFT/TPOT windows: the scheduler (which owns the
        # submit clock) records into these; stats() snapshots them
        self.ttft = _M_TTFT.labels(instance=self.obs_instance)
        self.tpot = _M_TPOT.labels(instance=self.obs_instance)
        self._warmed = False
        from ...ops.pallas import resolve_tier
        self._kernel_tier = resolve_tier()

    # ------------------------------------------------------------------
    # program split
    # ------------------------------------------------------------------
    # ops that carry a state from token to token which the engine does not
    # keep: a decode step would start every token from a zero state
    _STATE_OPS = {
        "ssd_scan": "a state-space layer's recurrent state",
        "gated_delta_rule": "a linear-attention layer's recurrent state",
        "causal_conv1d": "a short convolution's last taps",
    }

    @classmethod
    def _refuse_unserved(cls, block):
        """Ops whose decoding needs what the engine does not keep: refused
        by name, before a rewritten program computes something else."""
        for op in block.ops:
            if op.type in cls._STATE_OPS:
                raise ValueError(
                    f"{op.type}: {cls._STATE_OPS[op.type]} is carried from "
                    "token to token; GenerationEngine keeps a paged KV "
                    "arena and no recurrent-state cache, so a decode step "
                    "would start every token from nothing; programs with "
                    "state layers cannot be served yet")
            if op.type == "routed_experts" \
                    and op.attr("expert_form", "gated_silu") != "gated_silu":
                raise ValueError(
                    f"routed_experts with expert_form="
                    f"{op.attr('expert_form')!r}: the un-gated expert form "
                    "comes with single-mixer state-space hybrids, whose "
                    "layers need a recurrent-state cache that "
                    "GenerationEngine does not have; no phase program has "
                    "decoded it and it cannot be served yet")

    def _attention_config(self, program):
        block = program.global_block()
        self._refuse_unserved(block)
        sites = [op for op in block.ops if op.type == ATTENTION_OP]
        if not sites:
            raise ValueError(
                "program has no causal_self_attention sites: not a "
                "generative bundle (use InferenceEngine for feed-forward "
                "models)")
        configs = set()
        for op in sites:
            heads = int(op.attr("num_heads"))
            # the phase ops and the cache allocator know equal heads and
            # full causal attention only: refuse the rest by name, before
            # a rewritten program computes something else
            kv_heads = int(op.attr("num_kv_heads", 0) or heads)
            if kv_heads != heads:
                raise ValueError(
                    f"causal_self_attention with num_kv_heads={kv_heads} "
                    f"!= num_heads={heads}: the paged KV arena is laid out "
                    "for equal query and key/value heads; grouped-query "
                    "programs cannot be served by GenerationEngine yet")
            if int(op.attr("window", 0) or 0):
                raise ValueError(
                    f"causal_self_attention with window="
                    f"{int(op.attr('window'))}: the cache allocator keeps "
                    "every block of a sequence and the phase ops attend "
                    "over all of them; sliding-window programs cannot be "
                    "served by GenerationEngine yet")
            kvar = block.var(op.input("K")[0])
            hidden = int(kvar.shape[-1])
            configs.add((heads, hidden // heads))
        if len(configs) != 1:
            raise ValueError(
                f"attention sites disagree on (heads, head_dim): "
                f"{sorted(configs)}")
        heads, head_dim = configs.pop()
        return len(sites), heads, head_dim

    def _rewrite(self, program, phase_op):
        """Clone the program and rewrite every attention site into the
        phase op, wiring the per-layer arena vars in and out under the
        SAME names (the optimizer-op in-place convention) so the arena
        update stays on device. Arena/slot vars are DECLARED in the clone
        (dtype-annotated, ``is_data`` — they are fed every dispatch), so
        the rewritten program is self-describing and verifiable."""
        from ...fluid.framework import Operator

        p = program.clone(for_test=True)
        block = p.global_block()

        def _declare(name, dtype):
            if not block.has_var(name):
                block.create_var(name=name, dtype=dtype, is_data=True)

        _declare(_SLOTS, "int32")
        if phase_op == "paged_attention":
            _declare(_TABLES, "int32")
            _declare(_CTXLENS, "int32")
        elif phase_op == "chunked_prefill_attention":
            _declare(_TABLES, "int32")
            _declare(_CHUNKSTART, "int32")
        layer = 0
        for i, op in enumerate(block.ops):
            if op.type != ATTENTION_OP:
                continue
            inputs = dict(op.inputs)
            outputs = dict(op.outputs)
            outputs.pop("LogSumExp", None)    # the training op's residual
            for kind in ("k", "v"):
                _declare(_kv_name(kind, layer), "float32")
            inputs["KCache"] = [_kv_name("k", layer)]
            inputs["VCache"] = [_kv_name("v", layer)]
            inputs["SlotMapping"] = [_SLOTS]
            outputs["KCacheOut"] = [_kv_name("k", layer)]
            outputs["VCacheOut"] = [_kv_name("v", layer)]
            if phase_op == "paged_attention":
                inputs["BlockTables"] = [_TABLES]
                inputs["ContextLens"] = [_CTXLENS]
            elif phase_op == "chunked_prefill_attention":
                inputs["BlockTables"] = [_TABLES]
                inputs["ChunkStart"] = [_CHUNKSTART]
            block.ops[i] = Operator(block, phase_op, inputs, outputs,
                                    dict(op.attrs))
            layer += 1
        # verify_passes: the per-phase clone-rewrite is a transform pass
        # like any other — a mis-wired arena var fails HERE naming the
        # phase, not as an undefined name inside the compiled step
        from ...fluid.analysis import verify_pass_output
        verify_pass_output(
            p, f"GenerationEngine._rewrite({phase_op})",
            feed_names=list(self._feed_names))
        return p

    # ------------------------------------------------------------------
    # dispatch plumbing
    # ------------------------------------------------------------------
    def _arena_feed(self):
        feed = {}
        for l in range(self.num_layers):
            feed[_kv_name("k", l)] = self.cache.k[l]
            feed[_kv_name("v", l)] = self.cache.v[l]
        return feed

    def _arena_fetch_names(self):
        return [_kv_name(k, l) for l in range(self.num_layers)
                for k in ("k", "v")]

    def _gen_fetch(self):
        return [self._logits_name] + self._arena_fetch_names()

    def _warm_phase(self, program, feed, phase, bucket):
        """Register one (phase, bucket) warm executable from the
        persistent cache — or, writable caches only, AOT-compile and
        persist it. Silent on every failure: the phase just compiles
        through the normal jit path at its warmup dispatch."""
        if self._exec_cache is None or (phase, bucket) in self._warm_execs:
            return
        entry = _execcache.acquire(
            self._exec_cache, self._bundle_hash, f"gen_{phase}_b{bucket}",
            program, feed, self._gen_fetch(), self._exe, self._scope,
            identity={"instance": self.obs_instance, "phase": phase,
                      "bucket": bucket},
            donate_feeds=self._donate_feeds)
        if entry is not None:
            self._warm_execs[(phase, bucket)] = entry
            if entry.source == "cache":
                self._warm_loaded.add((phase, bucket))

    def _phase_children(self, phase, bucket):
        per = self._phase[phase].get(bucket)
        if per is None:
            per = self._phase[phase][bucket] = (
                _M_COMPILES.labels(instance=self.obs_instance,
                                   phase=phase, bucket=str(bucket)),
                _M_HITS.labels(instance=self.obs_instance,
                               phase=phase, bucket=str(bucket)))
        return per

    def _dispatch(self, program, feed, phase, bucket):
        fetch = self._gen_fetch()
        key = (phase, bucket)
        warm = self._warm_execs.get(key)
        # accounting BEFORE dispatch (mark-then-dispatch): concurrent
        # first dispatches of one executable count ONE compile; a
        # cache-LOADED first dispatch counts as a hit (nothing
        # compiles — warm warmup() reports 0)
        with self._stats_lock:
            per = self._phase_children(phase, bucket)
            if key in self._seen:
                per[1].inc()
            else:
                self._seen.add(key)
                if warm is not None and key in self._warm_loaded:
                    per[1].inc()
                else:
                    per[0].inc()
                    if self._warmed:
                        self._m_hot.inc()
        outs = None
        if warm is not None:
            # warm path: the persisted executable dispatched directly
            # (same trace, same glue — bitwise the jit path's outputs);
            # a deserialized-but-unrunnable artifact falls through to
            # the jit path with a reject bump, never an engine error
            try:
                with record_event(f"serving/gen_{phase}_b{bucket}",
                                  kind="stage"):
                    outs = warm.run(self._exe, program, feed, self._scope,
                                    return_numpy=False,
                                    donate_feeds=self._donate_feeds)
            except Exception as e:
                self._warm_execs.pop(key, None)
                loaded = key in self._warm_loaded
                self._warm_loaded.discard(key)
                self._exec_cache.note_reject(f"gen_{phase}_b{bucket}",
                                             "run_failed", error=e)
                if loaded:
                    with self._stats_lock:
                        # the jit fallback below really compiles but the
                        # pre-dispatch accounting booked a hit: record
                        # the real compile + hot alarm (compiles never
                        # undercount; the stray hit on this one-off
                        # corruption event is accepted)
                        per[0].inc()
                        if self._warmed:
                            self._m_hot.inc()
        if outs is None:
            # compile-site label for obs.perf: a build under this
            # dispatch (warmup compiles one executable per phase clone x
            # bucket) is attributed with its phase/bucket identity
            site = "genengine_warmup" if not self._warmed \
                else f"genengine_{phase}"
            detail = dict(instance=self.obs_instance, phase=phase,
                          bucket=bucket)
            if self._exec_cache is not None:
                detail["cache_hit"] = False
            with _perf.compile_site(site, **detail):
                with record_event(f"serving/gen_{phase}_b{bucket}",
                                  kind="stage"):
                    outs = self._exe.run(program, feed=feed,
                                         fetch_list=fetch,
                                         scope=self._scope,
                                         return_numpy=False,
                                         donate_feeds=self._donate_feeds)
        for l in range(self.num_layers):
            self.cache.k[l] = outs[1 + 2 * l]
            self.cache.v[l] = outs[2 + 2 * l]
        return np.asarray(outs[0], np.float32)

    def _prefill_bucket(self, n):
        import bisect
        i = bisect.bisect_left(self.prefill_buckets, n)
        if i == len(self.prefill_buckets):
            raise ValueError(
                f"prompt of {n} tokens exceeds the largest prefill "
                f"bucket {self.prefill_buckets[-1]}")
        return self.prefill_buckets[i]

    def _run_prefill(self, seq, prompt):
        bucket = self._prefill_bucket(len(prompt))
        toks = np.zeros((1, bucket, 1), np.int64)
        toks[0, :len(prompt), 0] = prompt
        slots = np.full((1, bucket), self.cache.sentinel_slot, np.int32)
        slots[0, :len(prompt)] = self.cache.append_slots(
            seq.seq_id, len(prompt))
        feed = self._arena_feed()
        feed["tokens"] = toks
        feed[_SLOTS] = slots
        if "positions" in self._feed_names:
            feed["positions"] = np.arange(bucket, dtype=np.int64) \
                .reshape(1, bucket, 1)
        logits = self._dispatch(self._prefill_program, feed, "prefill",
                                bucket)
        return logits[0, len(prompt) - 1]          # [vocab]

    def _chunk_limit(self):
        # tails longer than this defer to the chunked pump; with
        # chunking off nothing defers (a tail never exceeds max_len)
        return self.prefill_chunk if self.prefill_chunk > 0 else self.max_len

    def _run_chunk(self, seq, chunk, start):
        """One partial-prefill dispatch: ``chunk`` prompt tokens whose
        context starts at absolute position ``start`` (everything before
        them — cached prefix, earlier chunks — is already in the arena).
        Returns the chunk's last real position's logits."""
        bucket = self._prefill_bucket(len(chunk))
        toks = np.zeros((1, bucket, 1), np.int64)
        toks[0, :len(chunk), 0] = chunk
        slots = np.full((1, bucket), self.cache.sentinel_slot, np.int32)
        slots[0, :len(chunk)] = self.cache.append_slots(
            seq.seq_id, len(chunk))
        feed = self._arena_feed()
        feed["tokens"] = toks
        feed[_SLOTS] = slots
        feed[_TABLES] = self.cache.block_table(
            seq.seq_id, self._table_width).reshape(1, -1)
        feed[_CHUNKSTART] = np.asarray([start], np.int32)
        if "positions" in self._feed_names:
            feed["positions"] = (start + np.arange(bucket, dtype=np.int64)) \
                .reshape(1, bucket, 1)
        logits = self._dispatch(self._chunk_program, feed, "chunk", bucket)
        return logits[0, len(chunk) - 1]           # [vocab]

    def _run_tail(self, seq, prompt, cached):
        """Single-dispatch prefill of the uncached tail: a cold prompt
        keeps the original full-window prefill path (bitwise the
        pre-cache behavior); a cached prefix prefills only the tail
        through the chunked executable."""
        if cached == 0:
            return self._run_prefill(seq, prompt)
        return self._run_chunk(seq, prompt[cached:], cached)

    def _run_decode(self):
        S, P = self.max_seqs, self._table_width
        toks = np.zeros((S, 1, 1), np.int64)
        pos = np.zeros((S, 1, 1), np.int64)
        tables = np.zeros((S, P), np.int32)
        ctx = np.zeros(S, np.int32)
        slots = np.full(S, self.cache.sentinel_slot, np.int32)
        for s in self._slots:
            if s is None or s.finished or s.prefilling:
                continue
            j = s.slot
            toks[j, 0, 0] = s.next_token
            pos[j, 0, 0] = self.cache.context_len(s.seq_id)
            slots[j] = self.cache.append_slots(s.seq_id, 1)[0]
            tables[j] = self.cache.block_table(s.seq_id, P)
            ctx[j] = self.cache.context_len(s.seq_id)
        feed = self._arena_feed()
        feed["tokens"] = toks
        feed[_SLOTS] = slots
        feed[_TABLES] = tables
        feed[_CTXLENS] = ctx
        if "positions" in self._feed_names:
            feed["positions"] = pos
        logits = self._dispatch(self._decode_program, feed, "decode",
                                self.max_seqs)
        return logits[:, 0]                        # [max_seqs, vocab]

    # ------------------------------------------------------------------
    def warmup(self, sample_feed=None):
        """Compile the decode executable and every prefill bucket with
        inert feeds (sentinel slots: nothing is written to the arena).
        Returns the number of executables compiled."""
        del sample_feed                            # engine derives its own
        with self._lock:
            before = self._compiles()
            from ...ops.pallas import resolve_tier
            self._kernel_tier = resolve_tier()
            with record_event("serving/gen_warmup", kind="stage"):
                if self._exec_cache is not None:
                    # inert decode feed, shaped exactly like the
                    # _run_decode below builds it with every slot idle —
                    # the fingerprint must key the aval set the hot path
                    # dispatches
                    S, P = self.max_seqs, self._table_width
                    dfeed = self._arena_feed()
                    dfeed["tokens"] = np.zeros((S, 1, 1), np.int64)
                    dfeed[_SLOTS] = np.full(S, self.cache.sentinel_slot,
                                            np.int32)
                    dfeed[_TABLES] = np.zeros((S, P), np.int32)
                    dfeed[_CTXLENS] = np.zeros(S, np.int32)
                    if "positions" in self._feed_names:
                        dfeed["positions"] = np.zeros((S, 1, 1), np.int64)
                    self._warm_phase(self._decode_program, dfeed,
                                     "decode", self.max_seqs)
                self._run_decode()
                for b in self.prefill_buckets:
                    toks = np.zeros((1, b, 1), np.int64)
                    slots = np.full((1, b), self.cache.sentinel_slot,
                                    np.int32)
                    feed = self._arena_feed()
                    feed["tokens"] = toks
                    feed[_SLOTS] = slots
                    if "positions" in self._feed_names:
                        feed["positions"] = np.arange(b, dtype=np.int64) \
                            .reshape(1, b, 1)
                    self._warm_phase(self._prefill_program, feed,
                                     "prefill", b)
                    self._dispatch(self._prefill_program, feed, "prefill",
                                   b)
                    if self._partial_enabled:
                        # warm the chunked-prefill twin of every bucket
                        # with an inert feed (sentinel slots write
                        # nothing) so a cached-tail or chunked prefill
                        # never compiles on the hot path
                        feed = self._arena_feed()
                        feed["tokens"] = toks
                        feed[_SLOTS] = slots
                        feed[_TABLES] = np.zeros((1, self._table_width),
                                                 np.int32)
                        feed[_CHUNKSTART] = np.zeros(1, np.int32)
                        if "positions" in self._feed_names:
                            feed["positions"] = np.arange(
                                b, dtype=np.int64).reshape(1, b, 1)
                        self._warm_phase(self._chunk_program, feed,
                                         "chunk", b)
                        self._dispatch(self._chunk_program, feed,
                                       "chunk", b)
            self._warmed = True
            return self._compiles() - before

    def _compiles(self):
        with self._stats_lock:
            return int(sum(c.value for per in self._phase.values()
                           for c, _h in per.values()))

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------
    def _sample(self, seq, logits):
        p = seq.params
        if p["mode"] == "greedy":
            return int(np.argmax(logits))
        k = min(p["top_k"], logits.shape[0])
        # deterministic top-k: stable sort on (-logit, index)
        idx = np.lexsort((np.arange(logits.shape[0]), -logits))[:k]
        logp = _log_softmax(logits[idx].astype(np.float64)
                            / p["temperature"])
        probs = np.exp(logp)
        probs /= probs.sum()
        r = seq.rng.random_sample()
        return int(idx[np.searchsorted(np.cumsum(probs), r,
                                       side="right").clip(0, k - 1)])

    # ------------------------------------------------------------------
    # sequence lifecycle
    # ------------------------------------------------------------------
    @property
    def active_sequences(self):
        return sum(1 for s in self._slots if s is not None)

    def _free_slots(self):
        return [i for i, s in enumerate(self._slots) if s is None]

    def _new_seq(self, slot, params, max_new):
        seq = _Sequence(self._next_seq_id, slot, params, max_new)
        self._next_seq_id += 1
        return seq

    def start(self, prompt, max_new_tokens, sampling=None):
        """Admit + prefill one request. Returns ``(handle, first_tokens,
        finished)`` — the first token(s) stream immediately (time to
        first token = admission + prefill + one sample). Raises
        :class:`NoFreeSlots` / :class:`CacheExhausted` typed (and admits
        nothing) when the request cannot join the running batch."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("prompt must have at least one token")
        max_new = int(max_new_tokens)
        if max_new <= 0:
            raise ValueError("max_new_tokens must be positive")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds the engine's max_len {self.max_len}")
        params = normalize_sampling(sampling)
        # NEVER-satisfiable requests must raise ValueError (a bad request
        # the scheduler pops and fails), not NoFreeSlots/CacheExhausted
        # (transient capacity the strict-FIFO scheduler would wait on
        # forever, wedging the queue behind the head)
        beam = params["beam_size"] if params["mode"] == "beam" else 1
        if beam > self.max_seqs:
            raise ValueError(
                f"beam_size {beam} exceeds the engine's {self.max_seqs} "
                f"decode slots: this request can never be admitted")
        headroom = 1 if params["mode"] == "beam" else 0
        need = beam * (self.cache.blocks_for(len(prompt) + max_new)
                       + headroom)
        if need > self.cache.num_blocks:
            raise ValueError(
                f"request needs {need} KV blocks worst-case but the arena "
                f"only has {self.cache.num_blocks}: it can never be "
                f"admitted (raise serving_kv_num_blocks or lower "
                f"max_new_tokens)")
        with self._lock:
            if params["mode"] == "beam":
                return self._start_beam(prompt, max_new, params)
            free = self._free_slots()
            if not free:
                raise NoFreeSlots(
                    f"all {self.max_seqs} decode slots are busy")
            slot = free[0]
            seq = self._new_seq(slot, params, max_new)
            seq.prompt = prompt
            self.cache.admit(seq.seq_id, len(prompt) + max_new)
            cached = self.cache.attach_prefix(seq.seq_id, prompt) \
                if self.cache.prefix_cache_blocks > 0 else 0
            _flight_record(
                "gen_admit", component=self.obs_instance,
                seq=seq.seq_id, prompt_tokens=len(prompt),
                cached_tokens=cached, max_new=max_new,
                mode=params["mode"],
                chunked=len(prompt) - cached > self._chunk_limit())
            if len(prompt) - cached > self._chunk_limit():
                # long uncached tail under chunking: admit NOW, prefill
                # one bounded chunk per step boundary (the in-flight
                # decode batch keeps stepping in between)
                seq.pending = list(prompt[cached:])
                seq.prefilling = True
                self._slots[slot] = seq
                self._prefill_queue.append(seq)
                return seq, [], False
            try:
                logits = self._run_tail(seq, prompt, cached)
            except Exception:
                self.cache.release(seq.seq_id)
                raise
            self._slots[slot] = seq
            self.cache.register_prefix(seq.seq_id, prompt)
            tok = self._sample(seq, logits)
            toks, finished = self._advance(seq, tok)
            if finished:
                self._retire(seq)
            return seq, toks, finished

    def _advance(self, seq, tok):
        """Apply one sampled token to a greedy/topk sequence; returns
        (tokens_to_emit, finished). EOS is consumed, not emitted."""
        if seq.params["eos_id"] is not None and tok == seq.params["eos_id"]:
            seq.finished = True
            return [], True
        seq.emitted += 1
        seq.next_token = tok
        if seq.emitted >= seq.max_new:
            seq.finished = True
            return [tok], True
        return [tok], False

    def _start_beam(self, prompt, max_new, params):
        B = params["beam_size"]
        free = self._free_slots()
        if len(free) < B:
            raise NoFreeSlots(
                f"beam request needs {B} slots, {len(free)} free of "
                f"{self.max_seqs}")
        seqs, admitted = [], []
        try:
            for slot in free[:B]:
                seq = self._new_seq(slot, params, max_new)
                self.cache.admit(seq.seq_id, len(prompt) + max_new,
                                 cow_headroom=1)
                admitted.append(seq)
                seqs.append(seq)
        except CacheExhausted:
            for s in admitted:
                self.cache.release(s.seq_id)
            raise
        group = _BeamGroup(seqs, max_new, params["eos_id"])
        group.prompt = prompt
        cached = self.cache.attach_prefix(seqs[0].seq_id, prompt) \
            if self.cache.prefix_cache_blocks > 0 else 0
        _flight_record(
            "gen_admit", component=self.obs_instance,
            seq=seqs[0].seq_id, prompt_tokens=len(prompt),
            cached_tokens=cached, max_new=max_new, mode="beam",
            beam_size=B,
            chunked=len(prompt) - cached > self._chunk_limit())
        if len(prompt) - cached > self._chunk_limit():
            # chunked beam prefill: the lead hypothesis loads the prompt
            # chunk-by-chunk; siblings fork COW once it completes
            group.pending = list(prompt[cached:])
            group.prefilling = True
            for s in seqs:
                s.group = group
                s.prefilling = True
                self._slots[s.slot] = s
            self._prefill_queue.append(group)
            return group, [], False
        try:
            logits = self._run_tail(seqs[0], prompt, cached)
        except Exception:
            for s in admitted:
                self.cache.release(s.seq_id)
            raise
        return self._finish_beam_prefill(group, logits)

    def _finish_beam_prefill(self, group, logits):
        """Completion of a beam request's (possibly chunked) prefill:
        register the prefix, fork the sibling hypotheses COW off the
        prefilled lead, and seed the beam from the prompt logits. A beam
        stream emits only on completion (the winning hypothesis is
        unknown until the search ends)."""
        seqs = group.seqs
        B = len(seqs)
        self.cache.register_prefix(seqs[0].seq_id, group.prompt)
        for s in seqs[1:]:
            self.cache.fork(seqs[0].seq_id, s.seq_id)
        logp = _log_softmax(logits.astype(np.float64)).astype(np.float32)
        order = np.lexsort((np.arange(logp.shape[0]), -logp))[:B]
        group.pre_ids = order.astype(np.int64)
        group.pre_scores = logp[order]
        group.hist_ids.append(group.pre_ids.copy())
        group.hist_parents.append(np.arange(B))
        group.steps = 1
        group.prefilling = False
        for s, t in zip(seqs, group.pre_ids):
            s.group = group
            s.prefilling = False
            s.next_token = int(t)
            self._slots[s.slot] = s
        self._groups.append(group)
        if group.steps >= group.max_new or bool(
                np.all(group.pre_ids == group.end_id)):
            toks = self._finish_beam(group)
            return group, toks, True
        return group, [], False

    # ------------------------------------------------------------------
    def step(self):
        """One continuous-batching step: advance the FIFO-head chunked
        prefill by ONE bounded chunk (if any is pending), then one
        fixed-shape decode dispatch over every active slot, then
        per-sequence sampling / one dense ``beam_search`` op call per
        beam group. Returns a list of ``(handle, new_tokens, finished)``
        events (handles are the objects :meth:`start` returned).
        Finished sequences leave the batch immediately — their slots and
        blocks are free before the next step."""
        with self._lock:
            events = []
            if self._prefill_queue:
                events.extend(self._pump_prefill_locked())
            if not any(s is not None and not s.finished
                       and not s.prefilling for s in self._slots):
                return events
            logits = self._run_decode()
            for s in list(self._slots):
                if s is None or s.group is not None or s.prefilling:
                    continue
                tok = self._sample(s, logits[s.slot])
                toks, finished = self._advance(s, tok)
                if finished:
                    self._retire(s)
                if toks or finished:
                    events.append((s, toks, finished))
            for g in list(self._groups):
                events.extend(self._beam_step(g, logits))
            return events

    def _pump_prefill_locked(self):
        """Advance the oldest pending chunked prefill by one chunk; on
        the LAST chunk the request's first sample happens and it joins
        the decode batch — the completion event(s) are returned."""
        handle = self._prefill_queue[0]
        lead = handle.seqs[0] if isinstance(handle, _BeamGroup) else handle
        chunk = handle.pending[:self.prefill_chunk]
        del handle.pending[:len(chunk)]
        start = self.cache.context_len(lead.seq_id)
        _flight_record("gen_prefill_chunk", component=self.obs_instance,
                       seq=lead.seq_id, chunk_tokens=len(chunk),
                       start=start, remaining=len(handle.pending))
        logits = self._run_chunk(lead, chunk, start)
        if handle.pending:
            return []
        self._prefill_queue.pop(0)
        if isinstance(handle, _BeamGroup):
            h, toks, finished = self._finish_beam_prefill(handle, logits)
            return [(h, toks, finished)]
        handle.prefilling = False
        self.cache.register_prefix(handle.seq_id, handle.prompt)
        tok = self._sample(handle, logits)
        toks, finished = self._advance(handle, tok)
        if finished:
            self._retire(handle)
        return [(handle, toks, finished)]

    def _beam_step(self, group, logits):
        B = len(group.seqs)
        logp = np.stack([
            _log_softmax(logits[s.slot].astype(np.float64))
            for s in group.seqs]).astype(np.float32)      # [B, vocab]
        vocab = logp.shape[1]
        k = min(B, vocab)
        cand_idx = np.argsort(-logp, axis=1, kind="stable")[:, :k]
        cand_scores = np.take_along_axis(logp, cand_idx, axis=1)
        sel_ids, sel_scores, parents = self._beam_search_op(
            group.pre_ids.reshape(1, B),
            group.pre_scores.reshape(1, B),
            cand_idx.reshape(1, B, k).astype(np.int64),
            cand_scores.reshape(1, B, k),
            B, group.end_id)
        group.pre_ids = sel_ids.reshape(B).astype(np.int64)
        group.pre_scores = sel_scores.reshape(B)
        parents = parents.reshape(B)
        group.hist_ids.append(group.pre_ids.copy())
        group.hist_parents.append(parents.copy())
        group.steps += 1
        # fork hypothesis state: slot j continues from its parent's
        # context (copy-on-write block sharing), then feeds its token
        self.cache.reorder({
            s.seq_id: group.seqs[int(parents[j])].seq_id
            for j, s in enumerate(group.seqs)})
        for j, s in enumerate(group.seqs):
            s.next_token = int(group.pre_ids[j])
        if group.steps >= group.max_new or bool(
                np.all(group.pre_ids == group.end_id)):
            toks = self._finish_beam(group)
            return [(group, toks, True)]
        # heartbeat: the group advanced but emits only on completion
        return [(group, [], False)]

    _beam_programs = {}

    def _beam_search_op(self, pre_ids, pre_scores, ids, scores, beam,
                        end_id):
        """One step of the dense ``beam_search`` op, run through a tiny
        eager program (reusing the op exactly as the book decoders do)."""
        import paddle_tpu.fluid as fluid

        key = (beam, end_id)
        prog = self._beam_programs.get(key)
        if prog is None:
            prog = fluid.Program()
            b = prog.global_block()
            for n, dt in (("pre_ids", "int64"), ("pre_scores", "float32"),
                          ("ids", "int64"), ("scores", "float32")):
                b.create_var(name=n, dtype=dt, is_data=True)
            b.append_op(
                "beam_search",
                inputs={"pre_ids": ["pre_ids"], "pre_scores": ["pre_scores"],
                        "ids": ["ids"], "scores": ["scores"]},
                outputs={"selected_ids": ["selected_ids"],
                         "selected_scores": ["selected_scores"],
                         "parent_idx": ["parent_idx"]},
                attrs={"beam_size": beam, "end_id": end_id})
            self._beam_programs[key] = prog
        exe = fluid.Executor(mode="eager")
        out = exe.run(prog,
                      feed={"pre_ids": pre_ids, "pre_scores": pre_scores,
                            "ids": ids, "scores": scores},
                      fetch_list=["selected_ids", "selected_scores",
                                  "parent_idx"],
                      scope=Scope())
        return out[0], out[1], out[2]

    def _finish_beam(self, group):
        """Backtrack the best hypothesis and retire the group. Returns
        its tokens (EOS-trimmed) — a beam stream's single emission."""
        j = int(np.argmax(group.pre_scores))
        toks = []
        for t in range(len(group.hist_ids) - 1, -1, -1):
            toks.append(int(group.hist_ids[t][j]))
            j = int(group.hist_parents[t][j])
        toks.reverse()
        if group.end_id in toks:
            toks = toks[:toks.index(group.end_id)]
        group.finished = True
        for s in group.seqs:
            s.finished = True
            self._retire(s)
        self._groups.remove(group)
        return toks

    def _retire(self, seq):
        if self._slots[seq.slot] is seq:
            self._slots[seq.slot] = None
        self.cache.release(seq.seq_id)

    def abort(self, handle):
        """Cancel an in-flight request (client disconnected): frees its
        slot(s) and blocks immediately (mid-chunked-prefill requests
        leave the prefill queue too)."""
        with self._lock:
            if not handle.finished:
                lead = handle.seqs[0] if isinstance(handle, _BeamGroup) \
                    else handle
                _flight_record(
                    "gen_abort", component=self.obs_instance,
                    seq=lead.seq_id, prefilling=bool(handle.prefilling))
            if handle in self._prefill_queue:
                self._prefill_queue.remove(handle)
            if isinstance(handle, _BeamGroup):
                if not handle.finished:
                    handle.finished = True
                    for s in handle.seqs:
                        if not s.finished:
                            s.finished = True
                            self._retire(s)
                    if handle in self._groups:
                        self._groups.remove(handle)
            elif not handle.finished:
                handle.finished = True
                self._retire(handle)

    # ------------------------------------------------------------------
    @property
    def warmed(self):
        """Whether warmup() ran — the cheap liveness bit health() reads
        without paying stats()'s device-memory sample."""
        return self._warmed

    @property
    def hot_recompiles(self):
        """Compiles observed after warmup — derived from this engine's
        registry counter."""
        return int(self._m_hot.value)

    def _memory_section(self):
        """KV-arena accounting reconciliation: the arena's full byte
        footprint (pre-allocated — live regardless of occupancy), the
        share its in-use blocks address, the scope's parameter bytes,
        and the device's live total, so an operator can see what of
        ``paddle_tpu_device_bytes_live`` the serving state explains."""
        arena_bytes = sum(int(a.nbytes)
                          for arrs in (self.cache.k, self.cache.v)
                          for a in arrs)
        cs = self.cache.stats()
        in_use_frac = cs["blocks_in_use"] / max(cs["num_blocks"], 1)
        param_bytes = 0
        for name in self._scope.local_names():
            v = self._scope.find_var(name)
            nb = getattr(v, "nbytes", None)
            if nb is not None:
                param_bytes += int(nb)
        mem = _perf.sample_device_memory()
        accounted = arena_bytes + param_bytes
        return {"arena_bytes": arena_bytes,
                "arena_bytes_in_use": int(arena_bytes * in_use_frac),
                "param_bytes": param_bytes,
                "device_bytes_live": mem["total"],
                "unaccounted_bytes": max(0, mem["total"] - accounted)}

    def stats(self):
        with self._stats_lock:
            phases = {ph: {b: {"compiles": int(c.value),
                               "hits": int(h.value)}
                           for b, (c, h) in per.items()}
                      for ph, per in self._phase.items()}
        return json_safe({
            "phases": phases,
            "compiles": sum(s["compiles"] for per in phases.values()
                            for s in per.values()),
            "hits": sum(s["hits"] for per in phases.values()
                        for s in per.values()),
            "hot_recompiles": self.hot_recompiles,
            "warmed": self._warmed,
            "active_sequences": self.active_sequences,
            "prefilling": len(self._prefill_queue),
            "max_seqs": self.max_seqs,
            "blocks_in_use": self.cache.stats()["blocks_in_use"],
            "cache": self.cache.stats(),
            "prefill_chunk": self.prefill_chunk,
            "kernel_tier": self._kernel_tier,
            "exec_cache": self._exec_cache.stats()
            if self._exec_cache is not None else None,
            "kv_store": self._kv_store.stats()
            if self._kv_store is not None else None,
            "donate_arena": self.donate_arena,
            "warm_loaded": len(self._warm_loaded),
            "ttft": self.ttft.snapshot(),
            "tpot": self.tpot.snapshot(),
            "memory": self._memory_section(),
        })


__all__ = ["GenerationEngine", "NoFreeSlots", "normalize_sampling"]
