"""InferenceEngine: shape-bucketed execution of a saved inference program.

On TPU the serving problem is dominated by avoiding XLA recompiles: the
jitted step retraces for every new feed SHAPE, and a model server sees a
different batch size on nearly every request. The engine pads each
incoming batch up to a small fixed set of power-of-two batch buckets (the
``serving_batch_buckets`` flag), so the executable for each bucket
compiles once at :meth:`warmup` and the hot path only ever replays
compiled traces — the same static-shape discipline the training side's
``reader.bucket_by_length`` applies to ragged sequence lengths.

The engine reuses the Executor's ``_ProgramAnalysis`` cache (PR 1): the
steady-state dispatch does no block walks, and the per-program jit cache
holds exactly one trace per bucket. Per-bucket compile/hit counters (and a
``hot_recompiles`` alarm — a compile observed AFTER warmup) are surfaced
through :meth:`stats` so a server can prove the no-recompile contract.

Warm starts (serving/execcache.py): when the bundle carries persisted
compiled-executable artifacts (a registry version's ``warm/`` dir, or
the ``serving_exec_cache_dir`` local cache), :meth:`warmup` LOADS each
bucket's executable whose full-identity fingerprint matches instead of
compiling it, and dispatches it directly on the hot path — the jit path
stays as the miss/corruption fallback with bitwise-identical outputs.

Feeds are dense host arrays keyed by feed name (the serving wire form —
LoD/ragged inputs belong to the batch-shaping layer above, which must pad
them to static shapes before they reach a server anyway). Padding rows
replicate the batch's last row — numerically inert for any per-row model
and never a NaN source — and every fetch is trimmed back to the true row
count before it leaves the engine.
"""

from __future__ import annotations

import bisect
import threading

import numpy as np

from ..core.flags import get_flag
from ..core.profiler import record_event
from ..core.scope import Scope
from ..core.types import np_dtype
from ..obs import perf as _perf
from ..obs.metrics import REGISTRY as _METRICS, json_safe, next_instance
from . import execcache as _execcache

# obs plane: the engine's compile/hit/hot-recompile counters live in the
# process-wide metrics registry (stable names, scraped by the built-in
# ``metrics`` RPC); each engine instance owns its own labeled children and
# stats() derives the historical dict shape from them
_M_COMPILES = _METRICS.counter(
    "paddle_tpu_engine_compiles",
    "InferenceEngine executable compiles, per engine instance and bucket",
    labels=("instance", "bucket"))
_M_HITS = _METRICS.counter(
    "paddle_tpu_engine_hits",
    "InferenceEngine trace-cache hits, per engine instance and bucket",
    labels=("instance", "bucket"))
_M_HOT = _METRICS.counter(
    "paddle_tpu_engine_hot_recompiles",
    "compiles observed AFTER warmup (the no-recompile alarm)",
    labels=("instance",))


def parse_buckets(spec=None):
    """'1,2,4,8' -> sorted unique positive ints (flag default when None).

    Unsorted and duplicate entries are normalized (sorted, deduped);
    empty specs, non-integer entries and non-positive entries raise ONE
    typed ValueError naming the offending spec — never a raw int() parse
    error from deep inside, and never a silently-accepted bucket list
    whose order the bisect-based ``bucket_for`` would then misread."""
    if spec is None:
        spec = get_flag("serving_batch_buckets")
    try:
        if isinstance(spec, str):
            vals = [int(s) for s in spec.split(",") if s.strip()]
        else:
            vals = [int(b) for b in spec]
    except (TypeError, ValueError) as e:
        raise ValueError(f"serving batch buckets must be positive ints, "
                         f"got {spec!r} ({e})") from e
    if not vals or any(b <= 0 for b in vals):
        raise ValueError(f"serving batch buckets must be positive ints, "
                         f"got {spec!r}")
    return sorted(set(vals))


def commit_scope_arrays(scope):
    """Convert a scope's plain numpy arrays to jax arrays IN PLACE —
    exactly the conversion the jit boundary applies at every dispatch
    anyway (same dtype rules), done once up front. Without this, the
    FIRST dispatch of each engine traces against numpy state avals and
    the next dispatch of the same executable (now fed the jax arrays
    the first run wrote back) lands a SECOND jit cache entry — a whole
    hidden recompile per engine that the engine's own signature-based
    compile counters never saw (found by obs.perf compile telemetry:
    the zero-steady-state-compile pin caught it)."""
    import jax.numpy as jnp
    for name in scope.local_names():
        v = scope.find_var(name)
        if isinstance(v, np.ndarray):
            scope.set(name, jnp.asarray(v))


def _pad_rows(a, bucket):
    """Pad a [n, ...] array up to [bucket, ...] by replicating its last
    row (outputs for the padding rows are discarded by the caller)."""
    a = np.asarray(a)
    pad = bucket - a.shape[0]
    if pad <= 0:
        return a
    return np.concatenate(
        [a, np.broadcast_to(a[-1:], (pad,) + a.shape[1:])], axis=0)


class InferenceEngine:
    """Bucket-padded executor for one saved inference model.

    Either point it at a ``save_inference_model`` directory::

        engine = InferenceEngine(model_dir)

    or hand it an already-loaded bundle (``program``, ``feed_names``,
    ``fetch_vars``). A ``model_dir`` engine loads persistables into its
    OWN private scope, so many engines (many models) coexist in one
    process without colliding in the global scope.

    Thread safety: :meth:`infer` serializes dispatches with a lock — the
    scope (rng key, params) is shared mutable state, and a server's
    concurrency comes from batching, not from racing executors.
    """

    def __init__(self, model_dir=None, program=None, feed_names=None,
                 fetch_vars=None, executor=None, scope=None, buckets=None,
                 exec_cache=None):
        import paddle_tpu.fluid as fluid

        self._scope = scope or Scope()
        self._exe = executor or fluid.Executor()
        if model_dir is not None:
            program, feed_names, fetch_vars = fluid.io.load_inference_model(
                model_dir, self._exe, scope=self._scope)
        if program is None or feed_names is None or fetch_vars is None:
            raise ValueError(
                "InferenceEngine needs model_dir= or all of program=/"
                "feed_names=/fetch_vars=")
        commit_scope_arrays(self._scope)
        # persistent compiled-executable cache (serving/execcache.py):
        # warmup LOADS each bucket's executable where an artifact with a
        # matching full-identity fingerprint exists, and compiles+saves
        # the rest (writable caches only). None = compile always, the
        # pre-cache behavior.
        self._exec_cache = _execcache.resolve_cache(model_dir, exec_cache)
        self._bundle_hash = _execcache.bundle_content_hash(model_dir) \
            if self._exec_cache is not None and model_dir else None
        if self._bundle_hash is None:
            self._exec_cache = None
        self._warm_execs = {}          # dispatch sig -> WarmExecutable
        self._warm_loaded = set()      # sigs whose executable was LOADED
        self._program = program
        self._feed_names = list(feed_names)
        self._fetch_names = [v if isinstance(v, str) else v.name
                             for v in fetch_vars]
        self.buckets = parse_buckets(buckets)
        # _lock serializes DISPATCH only; counters live under their own
        # lock so stats()/health() stay cheap while a dispatch (or a
        # multi-second warmup compile) is running
        self._lock = threading.Lock()
        self._stats_lock = threading.Lock()
        # (bucket, per-feed dtype/trailing-shape signature) dispatched so
        # far: a new signature is a compile, a seen one is a trace-cache
        # hit — exactly the jit cache's keying (shape+dtype avals)
        self._seen = set()
        # counters live in the obs.metrics registry under this engine's
        # instance label; stats() derives the per-bucket dict from them
        self.obs_instance = next_instance("engine")
        self._m_compiles = {b: _M_COMPILES.labels(instance=self.obs_instance,
                                                  bucket=str(b))
                            for b in self.buckets}
        self._m_hits = {b: _M_HITS.labels(instance=self.obs_instance,
                                          bucket=str(b))
                        for b in self.buckets}
        self._m_hot = _M_HOT.labels(instance=self.obs_instance)
        self._warmed = False
        # which kernel tier this engine's executables compile with
        # (ops/pallas tier resolution; re-sampled at warmup so a tier flip
        # before warmup is reflected — after warmup it names what the
        # compiled buckets actually used)
        from ..ops.pallas import resolve_tier
        self._kernel_tier = resolve_tier()

    # ------------------------------------------------------------------
    @property
    def program(self):
        return self._program

    @property
    def feed_names(self):
        return list(self._feed_names)

    @property
    def fetch_names(self):
        return list(self._fetch_names)

    @property
    def max_batch(self):
        return self.buckets[-1]

    def bucket_for(self, n):
        """Smallest bucket >= n (the largest bucket for oversized n —
        :meth:`infer` chunks those)."""
        i = bisect.bisect_left(self.buckets, n)
        return self.buckets[min(i, len(self.buckets) - 1)]

    # ------------------------------------------------------------------
    def _template_feed(self):
        """One-row zero feed synthesized from the program's feed-var
        metadata (shape [-1, d1, ...] + dtype), for metadata-only warmup."""
        block = self._program.global_block()
        feed = {}
        for name in self._feed_names:
            v = block.var(name)
            if v.lod_level and v.lod_level > 0:
                raise ValueError(
                    f"feed var {name!r} is LoD (ragged); pass warmup() an "
                    "explicit sample_feed of padded dense arrays")
            dims = list(v.shape or [])
            if dims and dims[0] == -1:
                dims = dims[1:]
            if any(d is None or int(d) < 0 for d in dims):
                raise ValueError(
                    f"feed var {name!r} has unknown dims {v.shape}; pass "
                    "warmup() an explicit sample_feed")
            dt = np_dtype(v.dtype) if v.dtype is not None else np.float32
            feed[name] = np.zeros([1] + [int(d) for d in dims], dt)
        return feed

    def _normalize_dtypes(self, arrs):
        """Cast feeds to their declared var dtypes — the same coercion
        Executor._prepare_feed applies before jit. Doing it HERE keeps the
        engine's compile/hit signature aligned with the avals jit actually
        sees (a client feeding float64 — numpy's default — neither skews
        the counters nor changes numerics for its batch-mates)."""
        block = self._program.global_block()
        for name, a in arrs.items():
            if block.has_var(name):
                want = block.var(name).dtype
                if want is not None and a.dtype != np_dtype(want):
                    arrs[name] = a.astype(np_dtype(want))
        return arrs

    def warmup(self, sample_feed=None):
        """Compile every bucket's executable up front: pad a one-row
        template (from ``sample_feed`` or the program's feed-var metadata)
        to each bucket and dispatch it. After this returns, a correctly-
        shaped request can never trigger a hot-path compile; any compile
        observed later increments ``hot_recompiles``. Returns the number
        of executables compiled."""
        if sample_feed is None:
            feed = self._template_feed()
        else:
            feed = self._normalize_dtypes(
                {k: np.asarray(v)[:1] for k, v in sample_feed.items()})
        before = sum(c.value for c in self._m_compiles.values())
        from ..ops.pallas import resolve_tier
        self._kernel_tier = resolve_tier()
        with record_event("serving/warmup", kind="stage"):
            for b in self.buckets:
                if self._exec_cache is not None:
                    self._warm_bucket(feed, b)
                self._dispatch(feed, 1, b)
        self._warmed = True
        return int(sum(c.value for c in self._m_compiles.values()) - before)

    def _sig(self, padded, bucket, fetch_names):
        # fetch names stay IN ORDER: the executor's jit cache keys on the
        # ordered fetch tuple, so a reordered fetch_list is a distinct
        # executable and must count as a compile here too
        return (bucket, tuple(fetch_names),
                tuple(sorted((k, a.dtype.str, a.shape[1:])
                             for k, a in padded.items())))

    def _warm_bucket(self, feed, bucket):
        """Register one bucket's warm executable: LOAD the artifact whose
        fingerprint matches this exact dispatch (bundle bytes, padded
        feed avals, jit-key flags, toolchain, backend), or — writable
        caches only — AOT-compile exactly as the jit path would and
        persist it for the next process. Every failure is silent: the
        bucket just compiles through the normal jit path."""
        padded = {k: _pad_rows(np.asarray(a), bucket)
                  for k, a in feed.items()}
        sig = self._sig(padded, bucket, self._fetch_names)
        if sig in self._warm_execs:
            return
        entry = _execcache.acquire(
            self._exec_cache, self._bundle_hash, f"infer_b{bucket}",
            self._program, padded, self._fetch_names, self._exe,
            self._scope,
            identity={"instance": self.obs_instance, "bucket": bucket})
        if entry is not None:
            self._warm_execs[sig] = entry
            if entry.source == "cache":
                self._warm_loaded.add(sig)

    # ------------------------------------------------------------------
    def infer(self, feed, fetch_list=None):
        """Run one batch; returns the fetch arrays trimmed to the true row
        count. Batches larger than the biggest bucket are chunked through
        it and the per-chunk results concatenated."""
        fetch_names = self._fetch_names if fetch_list is None else \
            [v if isinstance(v, str) else v.name for v in fetch_list]
        missing = [n for n in self._feed_names if n not in feed]
        if missing:
            raise ValueError(f"infer feed is missing vars {missing}; "
                             f"the model feeds {self._feed_names}")
        arrs = self._normalize_dtypes(
            {n: np.asarray(feed[n]) for n in self._feed_names})
        ns = {a.shape[0] if a.ndim else 0 for a in arrs.values()}
        if len(ns) != 1:
            raise ValueError(
                f"inconsistent batch sizes across feeds: "
                f"{ {n: a.shape for n, a in arrs.items()} }")
        n = ns.pop()
        if n == 0:
            raise ValueError("cannot infer an empty batch")
        if n <= self.max_batch:
            return self._dispatch(arrs, n, self.bucket_for(n),
                                  fetch_names)
        parts = []
        for lo in range(0, n, self.max_batch):
            chunk = {k: a[lo:lo + self.max_batch] for k, a in arrs.items()}
            cn = min(self.max_batch, n - lo)
            parts.append(self._dispatch(chunk, cn, self.bucket_for(cn),
                                        fetch_names))
        # _dispatch guarantees per-row outputs, so chunk concat is exact
        return [np.concatenate([p[i] for p in parts], axis=0)
                for i in range(len(fetch_names))]

    def _dispatch(self, arrs, n, bucket, fetch_names=None):
        fetch_names = fetch_names or self._fetch_names
        padded = {k: _pad_rows(a, bucket) for k, a in arrs.items()}
        sig = self._sig(padded, bucket, fetch_names)
        warm = self._warm_execs.get(sig)
        # accounting BEFORE dispatch (mark-then-dispatch, the pre-cache
        # order): two concurrent first dispatches of one sig must count
        # ONE compile — the second sees the sig claimed and counts a
        # hit, exactly like the jit cache it mirrors. A cache-LOADED
        # first dispatch counts as a hit: nothing compiles, so warmup()
        # reports 0 compiles for a fully warm engine.
        with self._stats_lock:
            if sig in self._seen:
                self._m_hits[bucket].inc()
            else:
                self._seen.add(sig)
                if warm is not None and sig in self._warm_loaded:
                    self._m_hits[bucket].inc()
                else:
                    self._m_compiles[bucket].inc()
                    if self._warmed:
                        self._m_hot.inc()
        with self._lock:
            outs = None
            if warm is not None:
                # warm path: the deserialized (or publish-time-compiled)
                # executable dispatched directly — same trace, same glue
                # as the jit path, bitwise-identical outputs, zero
                # compile risk. A failure here (an artifact that
                # deserialized but will not run) falls through to the
                # jit path with a reject bump — never an engine error.
                try:
                    with record_event(f"serving/infer_b{bucket}",
                                      kind="stage"):
                        outs = warm.run(self._exe, self._program, padded,
                                        self._scope)
                except Exception as e:
                    self._warm_execs.pop(sig, None)
                    loaded = sig in self._warm_loaded
                    self._warm_loaded.discard(sig)
                    self._exec_cache.note_reject(f"infer_b{bucket}",
                                                 "run_failed", error=e)
                    if loaded:
                        with self._stats_lock:
                            # the fallback below REALLY compiles but the
                            # pre-dispatch accounting booked a cache
                            # hit: record the real compile and fire the
                            # hot alarm — an operator watching the ==0
                            # contract must see a mid-request XLA
                            # compile (the stray hit on this one-off
                            # corruption event is accepted; compiles
                            # and hot_recompiles never undercount)
                            self._m_compiles[bucket].inc()
                            if self._warmed:
                                self._m_hot.inc()
            if outs is None:
                # compile-site label for obs.perf: a build detected
                # inside this dispatch (each bucket's first padded
                # shape) is attributed to the engine with its bucket
                # identity; after warmup any compile here is the
                # hot-recompile alarm's twin
                site = "engine_warmup" if not self._warmed \
                    else "engine_infer"
                detail = dict(instance=self.obs_instance, bucket=bucket)
                if self._exec_cache is not None:
                    detail["cache_hit"] = False
                with _perf.compile_site(site, **detail):
                    with record_event(f"serving/infer_b{bucket}",
                                      kind="stage"):
                        outs = self._exe.run(self._program, feed=padded,
                                             fetch_list=list(fetch_names),
                                             scope=self._scope)
        trimmed = []
        for name, o in zip(fetch_names, outs):
            if isinstance(o, np.ndarray) and o.ndim >= 1 \
                    and o.shape[0] == bucket:
                trimmed.append(o[:n])
                continue
            # a fetch without a leading batch dim was computed OVER the
            # padding rows (and, batched, over other callers' coalesced
            # rows) — its value is silently wrong, so reject the model
            # configuration loudly instead of serving corrupt answers
            shape = getattr(o, "shape", None)
            raise ValueError(
                f"fetch {name!r} is not per-row (shape {shape}, bucket "
                f"{bucket}): serving requires every fetch to carry a "
                "leading batch dimension — batch-reduced outputs (means, "
                "aggregate metrics) cannot be padded or split per caller")
        return trimmed

    # ------------------------------------------------------------------
    @property
    def warmed(self):
        """Whether warmup() ran — the cheap liveness bit health() reads
        (stats() includes a device-memory sample since the perf plane;
        a health poll must not pay that walk twice)."""
        return self._warmed

    @property
    def hot_recompiles(self):
        """Compiles observed after warmup — derived from this engine's
        registry counter (the dict shape callers read is unchanged)."""
        return int(self._m_hot.value)

    def release(self):
        """Drop this engine's device-memory footprint: the warm
        executables and the private scope's parameter arrays. The
        multi-model ModelServer's LRU evictor calls this when a cold
        model leaves the host so its arena goes back to the device pool
        with the last reference. The engine is DONE serving afterwards —
        call only after its final in-flight dispatch finished."""
        with self._lock:
            self._warm_execs.clear()
            self._warm_loaded.clear()
            self._scope = Scope()
            self._warmed = False

    def _memory_section(self):
        """Accounting reconciliation: bytes this engine can explain
        (its scope's parameter arrays) next to the device's live total,
        so an operator can see how much of
        ``paddle_tpu_device_bytes_live`` THIS engine's weights are —
        and how much is bucket executables / other tenants."""
        param_bytes = 0
        for name in self._scope.local_names():
            v = self._scope.find_var(name)
            nb = getattr(v, "nbytes", None)
            if nb is not None:
                param_bytes += int(nb)
        mem = _perf.sample_device_memory()
        return {"param_bytes": param_bytes,
                "device_bytes_live": mem["total"],
                "unaccounted_bytes": max(0, mem["total"] - param_bytes)}

    def stats(self):
        # the historical dict shape, DERIVED from this instance's
        # obs.metrics children (the registry is the source of truth; the
        # built-in ``metrics`` RPC reports the same numbers)
        per_bucket = {b: {"compiles": int(self._m_compiles[b].value),
                          "hits": int(self._m_hits[b].value)}
                      for b in self.buckets}
        return json_safe({
            "buckets": list(self.buckets),
            "per_bucket": per_bucket,
            "compiles": sum(s["compiles"] for s in per_bucket.values()),
            "hits": sum(s["hits"] for s in per_bucket.values()),
            "hot_recompiles": self.hot_recompiles,
            "warmed": self._warmed,
            "kernel_tier": self._kernel_tier,
            "exec_cache": self._exec_cache.stats()
            if self._exec_cache is not None else None,
            "warm_loaded": len(self._warm_loaded),
            "memory": self._memory_section(),
        })


__all__ = ["InferenceEngine", "parse_buckets"]
