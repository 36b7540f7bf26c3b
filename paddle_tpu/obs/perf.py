"""Performance introspection plane: compile telemetry, device-memory
watermarks, and HLO cost attribution — the fourth obs pillar.

PRs 10 and 12 built the *operational* planes (metrics/traces, then
SLO/flight-recorder/incidents); this module carries the signals
profile-driven kernel work needs:

* **Compile telemetry** — every compiled-executable build (Executor jit
  (re)traces, engine warmup buckets, the generation engine's
  prefill/chunk/decode clones, ``run_steps`` scans) lands a
  ``paddle_tpu_compile_seconds`` observation labeled by *site*, a
  :class:`CompileRecord` in the bounded per-process :data:`COMPILE_LOG`
  (wall time, bucket/program identity, and the wall time split by
  *stage*: trace / lower / xla_compile / cache_load / other, from JAX's
  own monitoring events — see ``_on_duration``), and a
  ``compile`` flight-recorder event carrying the active trace id, so a
  rollout that pays warmup compiles is visible in the incident bundle.
  The existing ``paddle_tpu_executor_retraces`` counter says *that*
  something retraced; this layer says *which* executable, *what it
  cost* and *where the cost went*. Detection rides the jit trace-cache
  size (one C++ probe per
  dispatch, ~0.02 us), so per-bucket internal retraces of one compiled
  fn are each attributed. The ``obs_compile_log`` flag (capacity; 0
  disables) is deliberately NOT in the executor's ``_JIT_KEY_FLAGS`` —
  flipping the layer on/off never retraces.
* **Device-memory watermarks** — :func:`sample_device_memory` sets
  ``paddle_tpu_device_bytes_live{device}`` (and ``_peak`` where the
  backend reports it) from ``jax.local_devices()[*].memory_stats()``,
  falling back to a ``jax.live_arrays()`` byte tally on backends
  without allocator stats (CPU). :class:`MemorySampler` re-samples on
  the existing background-monitor cadence (``obs_slo_interval_s``);
  ``ModelServer.health()`` samples per scrape — so the gauge is
  SLO-able through the PR-12 rule engine with zero new machinery.
* **Cost attribution** — :func:`attribute` AOT-lowers one dispatch of
  any program / engine / registry bundle exactly as the Executor would
  compile it, and merges the optimized HLO's static per-instruction
  operand+result bytes (:func:`hlo_shape_bytes`, extracted from
  ``tools/hlo_report.py`` and unit-tested) with the backend's
  ``cost_analysis()`` totals into a top-N table; ``tools/hlo_report.py``
  is argument parsing over it. Device TIME is a trace's to give:
  ``fluid.profiler.device_tracer(dir)`` around a loop records the device's
  operations, each under its Fluid op's ``phase/op_type`` scope, beside the
  program's own spans (``core/profiler.record_event``).
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import deque
from contextlib import contextmanager

from ..core.flags import get_flag
from .metrics import REGISTRY as _METRICS, json_safe

# the obs_compile_log flag is DEFINEd in core/flags.py with every other
# flag (check_flags_doc.py regex-scans that one file)

_M_COMPILE_SECONDS = _METRICS.histogram(
    "paddle_tpu_compile_seconds",
    "wall seconds per compiled-executable build (trace + XLA compile + "
    "the dispatch that triggered it), labeled by compile site",
    labels=("site",), span_name="perf/compile", span_kind="stage")
_M_STAGE_SECONDS = _METRICS.counter(
    "paddle_tpu_compile_stage_seconds",
    "seconds spent building executables, by compile site and stage "
    "(trace / lower / xla_compile / cache_load / other); site=eager holds "
    "the builds no executor-owned function asked for",
    labels=("site", "stage"))
_M_BUILDS = _METRICS.counter(
    "paddle_tpu_compile_builds",
    "executables the backend handed back, by compile site and source "
    "(compiled by XLA / cache: loaded from the persistent compile cache)",
    labels=("site", "source"))
_M_BYTES_LIVE = _METRICS.gauge(
    "paddle_tpu_device_bytes_live",
    "live device memory bytes per local device — backend memory_stats "
    "bytes_in_use when available, else a jax.live_arrays() byte tally",
    labels=("device",))
_M_BYTES_PEAK = _METRICS.gauge(
    "paddle_tpu_device_bytes_peak",
    "peak device memory bytes per local device (backends that report "
    "memory_stats peak_bytes_in_use only — absent on CPU)",
    labels=("device",))

# ---------------------------------------------------------------------------
# fork safety (mirrors obs.recorder: O(1) hook, lazy ring reset)
# ---------------------------------------------------------------------------

_FORK_EPOCH = 0


def _bump_fork_epoch():
    global _FORK_EPOCH
    _FORK_EPOCH += 1


os.register_at_fork(after_in_child=_bump_fork_epoch)


# ---------------------------------------------------------------------------
# compile telemetry
# ---------------------------------------------------------------------------

def enabled():
    """Whether the compile-telemetry layer records anything (the
    ``obs_compile_log`` capacity flag is > 0)."""
    return int(get_flag("obs_compile_log")) > 0


STAGES = ("trace", "lower", "xla_compile", "cache_load", "other")


class CompileRecord:
    """One compiled-executable build: where it happened (``site``), what
    it cost (``seconds`` wall: trace + XLA compile + the dispatch that
    triggered it), which executable (``identity`` — bucket / phase /
    feed shapes / program version, op and fetch counts, site-dependent;
    engines with a persistent executable cache stamp a ``cache_hit``
    detail field: False marks the compile a warm replica would have
    skipped), and where the seconds went (``stages``: one entry per
    :data:`STAGES`, summing to ``seconds``; ``cache_read`` is the part of
    ``cache_load`` spent reading the persistent cache's entry)."""

    __slots__ = ("site", "seconds", "t", "identity", "stages", "cache_read",
                 "trace", "seq")

    def __init__(self, site, seconds, identity=None, stages=None,
                 cache_read=0.0, trace=None):
        self.site = str(site)
        self.seconds = float(seconds)
        self.t = time.time()
        self.identity = json_safe(identity or {})
        self.stages = split_seconds(self.seconds, stages or ())
        self.cache_read = float(cache_read)
        self.trace = trace
        self.seq = 0

    def as_dict(self):
        return json_safe({
            "site": self.site, "seconds": self.seconds, "t": self.t,
            "identity": self.identity, "stages": self.stages,
            "cache_read": self.cache_read, "trace": self.trace,
            "seq": self.seq,
        })

    def __repr__(self):
        return (f"CompileRecord({self.site!r}, {self.seconds:.3f}s, "
                f"identity={self.identity})")


def split_seconds(seconds, measured):
    """``{stage: seconds}`` over :data:`STAGES` for a build of ``seconds``
    wall whose first four stages were ``measured`` (JAX's events; missing
    ones are 0): ``other`` is what they leave, never negative. JAX clocks
    its events with ``time.time()`` and a build is clocked here with
    ``perf_counter()``; should the four pass the wall (a stepped clock)
    they are scaled to it, so the five always sum to ``seconds``."""
    four = ([max(float(x), 0.0) for x in measured] + [0.0] * 4)[:4]
    total = sum(four)
    if total > seconds:
        four = [x * seconds / total for x in four]
        total = seconds
    out = dict(zip(STAGES, four))
    out["other"] = max(seconds - total, 0.0)
    return out


class CompileLog:
    """Bounded per-process ring of :class:`CompileRecord`. Capacity
    defaults from the ``obs_compile_log`` flag (read lazily at first
    record); fork-started children lazily reset — they never report the
    parent's compiles nor deadlock on an inherited lock."""

    def __init__(self, capacity=None):
        self._capacity = capacity
        self._lock = threading.Lock()
        self._records = None
        self._seq = 0
        self._total_seconds = 0.0
        self._epoch = _FORK_EPOCH

    def _check_fork(self):
        if self._epoch != _FORK_EPOCH:
            self._lock = threading.Lock()
            self._records = None
            self._seq = 0
            self._total_seconds = 0.0
            self._epoch = _FORK_EPOCH

    def _ring_locked(self):
        if self._records is None:
            cap = self._capacity
            if cap is None:
                cap = int(get_flag("obs_compile_log"))
            self._records = deque(maxlen=max(1, int(cap)))
        return self._records

    def add(self, record):
        self._check_fork()
        with self._lock:
            self._seq += 1
            record.seq = self._seq
            self._total_seconds += record.seconds
            self._ring_locked().append(record)
        return record

    def records(self, site=None):
        """Records oldest-first (the ring's window), optionally filtered
        to one site."""
        self._check_fork()
        with self._lock:
            recs = list(self._ring_locked())
        if site is not None:
            recs = [r for r in recs if r.site == site]
        return recs

    def stats(self):
        """``{count, total_seconds, by_site}`` — count/total cover the
        process lifetime (not just the ring window); ``by_site`` holds the
        window's count, seconds and seconds by stage."""
        self._check_fork()
        with self._lock:
            recs = list(self._ring_locked())
            count, total = self._seq, self._total_seconds
        by_site = {}
        for r in recs:
            s = by_site.setdefault(r.site, {"count": 0, "seconds": 0.0,
                                            "stages": dict.fromkeys(
                                                STAGES, 0.0)})
            s["count"] += 1
            s["seconds"] += r.seconds
            for stage, x in r.stages.items():
                s["stages"][stage] += x
        return json_safe({"count": count,
                          "total_seconds": total,
                          "by_site": by_site})

    def clear(self):
        """TEST hygiene: drop every record and reset counters."""
        self._check_fork()
        with self._lock:
            if self._records is not None:
                self._records.clear()
            self._seq = 0
            self._total_seconds = 0.0


COMPILE_LOG = CompileLog()

# compile-site labeling: engines (and any other owner of a compiled
# executable) wrap their dispatch in compile_site(...) so a build
# detected inside Executor dispatch is attributed to the REAL site
# (engine_warmup / genengine_decode / ...) with its bucket/phase
# identity, not just "jit_step"
_SITE = threading.local()


@contextmanager
def compile_site(site, **detail):
    """Label any compile detected inside the block with ``site`` (a
    bounded code-site enum — it becomes a metric label value) and attach
    ``detail`` to its CompileRecord identity."""
    prev = getattr(_SITE, "value", None)
    _SITE.value = (str(site), detail)
    try:
        yield
    finally:
        _SITE.value = prev


def current_site(default="jit_step"):
    """(site, detail) the next detected compile should be attributed to."""
    v = getattr(_SITE, "value", None)
    if v is None:
        return default, {}
    return v


# ---------------------------------------------------------------------------
# compile stages: where a build's seconds went
# ---------------------------------------------------------------------------
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"
_EV_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# a span's event -> index into STAGES (a backend compile that was served
# from the cache moves on to ``cache_load``)
_SPAN_STAGE = {_EV_TRACE: 0, _EV_LOWER: 1, _EV_BACKEND: 2}
_SOURCES = ("compiled", "cache")
EAGER = "eager"
# the ``eager`` site's children, made once: its seconds are credited from
# inside the listener
_EAGER_SECONDS = [_M_STAGE_SECONDS.labels(site=EAGER, stage=s)
                  for s in STAGES[:4]]
_EAGER_BUILDS = [_M_BUILDS.labels(site=EAGER, source=s) for s in _SOURCES]


class _Building:
    """One thread's builds in progress. ``owners``: how many owned builds
    (an ``_InstrumentedFn`` call, ``with building():``) are under way on the
    thread; while it is 0 the listener credits what arrives to the site
    ``eager``. ``seconds`` / ``builds`` / ``cache_read``: what arrived
    since the owner last took it. ``open``: spans entered and not yet
    left, each ``[event, its children's seconds, a cache hit inside]``."""

    __slots__ = ("owners", "seconds", "builds", "cache_read", "open")

    def __init__(self):
        self.owners = 0
        self.open = []
        self.drop()

    def drop(self):
        """Forget what arrived (a build that raised built nothing)."""
        self.seconds = [0.0, 0.0, 0.0, 0.0]
        self.builds = [0, 0]
        self.cache_read = 0.0

    def take(self):
        """(stage seconds, builds by source, cache_read) since the last
        take, handed over to the caller."""
        out = self.seconds, self.builds, self.cache_read
        self.drop()
        return out

    def __enter__(self):
        self.owners += 1
        return self

    def __exit__(self, exc_type, exc, tb):
        self.owners -= 1
        if exc_type is not None:
            self.drop()
        return False


class _PerThread(threading.local):
    def __init__(self):
        self.building = _Building()


_THREAD = _PerThread()


def building():
    """The calling thread's :class:`_Building`. ``with building():`` marks
    an owned build: stage seconds that arrive inside belong to the
    :func:`note_compile` that follows, not to ``eager``."""
    return _THREAD.building


def _on_span_enter(event, _start_time, **_kw):
    if event in _SPAN_STAGE and enabled():
        _THREAD.building.open.append([event, 0.0, False])


def _on_event(event, **_kw):
    if event == _EV_CACHE_HIT and enabled():
        open_ = _THREAD.building.open
        if open_:
            open_[-1][2] = True


def _on_duration(event, seconds, **_kw):
    """The listener for JAX's duration events: credits a span's own seconds
    to its stage, on this thread's owned build or on ``eager``.

    JAX 0.9.0 times the parts of a build itself and publishes them on
    jax.monitoring, the bus core/compile_cache.CacheStats already counts
    hits and misses from. As read from the installed jax/_src:

    * pjit.py ``_create_pjit_jaxpr`` wraps ``trace_to_jaxpr`` in
      ``dispatch.log_elapsed_time(..., event=JAXPR_TRACE_EVENT)``. For an
      executor-owned function that is ``_run_ops`` over every Fluid op of
      the program, the op lowerings' Python, the kernel tier's routing and
      lazy kernel imports. Every jitted ``jnp`` function called for the
      first time INSIDE that trace opens a span of the same event inside
      the outer one.
    * interpreters/pxla.py ``lower_sharding_computation`` wraps
      ``mlir.lower_jaxpr_to_module`` in JAXPR_TO_MLIR_MODULE_EVENT (a
      lowering rule that traces a jitted function opens a trace span
      inside it).
    * pxla.py ``_cached_compilation`` wraps ``compiler.
      compile_or_get_cached`` WHOLE in BACKEND_COMPILE_EVENT: hashing the
      module for its cache key, then either the cache's read and
      deserialisation (compiler.py records the event ``cache_hits`` and
      the duration ``cache_retrieval_time_sec``, the read alone, inside
      the span) or XLA's compile (Mosaic's for a Pallas kernel) and the
      write into the cache (``cache_misses`` where it is written).
    * ``log_elapsed_time.__enter__`` announces each span with
      ``record_scalar(event, start_time)``; ``__exit__`` records the
      duration. A duration holds everything nested in it.

    So a span's OWN seconds are its duration less its children's, and the
    own seconds of all spans of a build sum to no more than its wall time.
    The listeners keep, per thread, the stack of open spans and four
    floats; they run only while something is being built, never in a steady
    step. ``backend_compile_duration`` counts as ``cache_load`` where a
    ``cache_hits`` event arrived inside it and as ``xla_compile`` otherwise.
    """
    stage = _SPAN_STAGE.get(event)
    if stage is None:
        if event == _EV_CACHE_READ and enabled():
            b = _THREAD.building
            if b.owners:
                b.cache_read += seconds
        return
    if not enabled():
        return
    b = _THREAD.building
    open_ = b.open
    children, hit = 0.0, False
    while open_:                # spans close innermost first
        ev, inside, hit_inside = open_.pop()
        if ev == event:
            children, hit = inside, hit_inside
            break
    if open_:
        open_[-1][1] += seconds
    own = max(seconds - children, 0.0)
    built = event == _EV_BACKEND        # an executable was handed back
    if built and hit:
        stage += 1                      # xla_compile -> cache_load
    if b.owners:
        b.seconds[stage] += own
        b.builds[hit] += built
    else:
        _EAGER_SECONDS[stage].inc(own)
        if built:
            _EAGER_BUILDS[hit].inc()


def _install_listeners():
    """Once a process (this module's import): JAX's monitoring bus offers
    no way to ask whether a listener is on it."""
    from jax import monitoring
    monitoring.register_scalar_listener(_on_span_enter)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


_install_listeners()


def note_compile(site, seconds, identity=None):
    """Land one compiled-executable build in the telemetry layer:
    histogram observation (labeled by site), the stage seconds and builds
    that arrived on this thread since the last build (they are this
    build's: see :class:`_Building`) into the two counter families,
    CompileRecord in :data:`COMPILE_LOG`, and a ``compile``
    flight-recorder event (which
    carries the active distributed trace id — a reload RPC's warmup
    compiles join the rollout's trace). No-op when the layer is off."""
    if not enabled():
        return None
    measured, builds, cache_read = _THREAD.building.take()
    rec = CompileRecord(site, seconds, identity=identity, stages=measured,
                        cache_read=cache_read)
    from .recorder import record as _flight_record
    _M_COMPILE_SECONDS.labels(site=rec.site).observe(rec.seconds)
    for stage, x in rec.stages.items():
        if x:
            _M_STAGE_SECONDS.labels(site=rec.site, stage=stage).inc(x)
    for source, n in zip(_SOURCES, builds):
        if n:
            _M_BUILDS.labels(site=rec.site, source=source).inc(n)
    ev = _flight_record("compile", component=rec.site,
                        seconds=round(rec.seconds, 4),
                        stages={k: round(v, 4)
                                for k, v in rec.stages.items()},
                        **{k: v for k, v in rec.identity.items()
                           if k in ("bucket", "phase", "instance",
                                    "program_version", "cache_hit")})
    rec.trace = ev.get("trace")
    COMPILE_LOG.add(rec)
    return rec


# ---------------------------------------------------------------------------
# device-memory watermarks
# ---------------------------------------------------------------------------

def sample_device_memory():
    """One memory sample: per-device live bytes into
    ``paddle_tpu_device_bytes_live{device}`` (and ``_peak`` where the
    backend reports it). Source per device: allocator ``memory_stats()``
    when available (TPU/GPU), else the device's share of a
    ``jax.live_arrays()`` byte tally (CPU — no allocator stats).
    Returns ``{"devices": {label: bytes}, "peaks": {...}, "sources":
    {label: "memory_stats"|"live_arrays"}, "total": int}``."""
    import jax

    devices, peaks, sources = {}, {}, {}
    tally_labels = []
    for d in jax.local_devices():
        label = f"{d.platform}:{d.id}"
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms and ms.get("bytes_in_use") is not None:
            devices[label] = int(ms["bytes_in_use"])
            sources[label] = "memory_stats"
            if ms.get("peak_bytes_in_use") is not None:
                peaks[label] = int(ms["peak_bytes_in_use"])
        else:
            tally_labels.append(label)
    if tally_labels:
        tally = {label: 0 for label in tally_labels}
        for a in jax.live_arrays():
            try:
                ds = list(a.devices())
                nbytes = int(a.nbytes)
            except Exception:
                continue
            for d in ds:
                label = f"{d.platform}:{d.id}"
                if label in tally:
                    # a sharded array's bytes split across its devices
                    tally[label] += nbytes // max(len(ds), 1)
        for label, b in tally.items():
            devices[label] = b
            sources[label] = "live_arrays"
    for label, b in devices.items():
        _M_BYTES_LIVE.labels(device=label).set(b)
    for label, b in peaks.items():
        _M_BYTES_PEAK.labels(device=label).set(b)
    return {"devices": devices, "peaks": peaks, "sources": sources,
            "total": sum(devices.values())}


def memory_section():
    """The JSON-safe dict ``health()``/``stats()`` surfaces embed — one
    fresh sample (so a health scrape always carries a current gauge)."""
    s = sample_device_memory()
    return json_safe({
        "device_bytes_live": s["devices"],
        "device_bytes_peak": s["peaks"],
        "sources": s["sources"],
        "total_bytes_live": s["total"],
    })


class MemorySampler:
    """Background device-memory sampler: re-samples every ``interval_s``
    (default: the ``obs_slo_interval_s`` flag — the same cadence the
    background SLO monitor evaluates on), keeping the
    ``paddle_tpu_device_bytes_live`` gauge fresh for SLO rules and
    scrapes without a caller in the loop.

    Self-bounding: the CPU fallback walks ``jax.live_arrays()`` under
    the GIL, whose cost grows with the process's live-array count
    (milliseconds in a busy server) — so after each sample the wait
    stretches to at least ``cost_factor`` times the observed sample
    duration. A sampler can then never steal more than
    ~1/cost_factor of a core no matter how expensive sampling gets;
    it degrades to a sparser cadence instead (``effective_interval_s``
    in :meth:`stats` reports the stretch)."""

    def __init__(self, interval_s=None, cost_factor=50.0):
        self.interval_s = float(get_flag("obs_slo_interval_s")
                                if interval_s is None else interval_s)
        self.cost_factor = float(cost_factor)
        self._stop = threading.Event()
        self._thread = None
        self._samples = 0
        self._last_error = None
        self._effective_interval_s = self.interval_s

    def start(self):
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("MemorySampler already running")
        self._stop.clear()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="perf-memory-sampler")
        self._thread.start()
        return self

    def _watch(self):
        while not self._stop.wait(self._effective_interval_s):
            try:
                t0 = time.perf_counter()
                sample_device_memory()
                dt = time.perf_counter() - t0
                self._samples += 1
                self._effective_interval_s = max(self.interval_s,
                                                 dt * self.cost_factor)
            except Exception as e:     # the sampler must never die
                self._last_error = f"{type(e).__name__}: {e}"

    def sample_now(self):
        """One synchronous sample on the calling thread — counts like a
        background sample and primes the cost-bounded cadence (callers
        that are about to enter a measured/latency-sensitive phase take
        one up front so the background thread already knows the cost)."""
        t0 = time.perf_counter()
        out = sample_device_memory()
        dt = time.perf_counter() - t0
        self._samples += 1
        self._effective_interval_s = max(self.interval_s,
                                         dt * self.cost_factor)
        return out

    def stop(self, timeout=10.0):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            return not self._thread.is_alive()
        return True

    def running(self):
        return self._thread is not None and self._thread.is_alive()

    @property
    def samples(self):
        return self._samples

    def stats(self):
        return json_safe({"running": self.running(),
                          "interval_s": self.interval_s,
                          "effective_interval_s": self._effective_interval_s,
                          "samples": self._samples,
                          "last_error": self._last_error})


# ---------------------------------------------------------------------------
# static HLO traffic estimation (the hlo_report.py estimator, extracted)
# ---------------------------------------------------------------------------

_HLO_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_HLO_SHAPE_RE = re.compile(
    r"(c128|c64|f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
    r"\[([0-9,]*)\]")


def hlo_shape_bytes(shape_str):
    """Total bytes of every HLO shape in ``shape_str`` — a plain array
    shape (``bf16[256,56,56,64]{3,2,1,0}``), a SCALAR (``f32[]`` — zero
    dims is one element), or a tuple, arbitrarily nested
    (``(f32[2]{0}, (s32[], pred[3]))`` sums every member). Layout/tiling
    suffixes and unknown dtypes contribute nothing."""
    total = 0
    for m in _HLO_SHAPE_RE.finditer(shape_str):
        dt, dims = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _HLO_DTYPE_BYTES[dt]
    return total


def hlo_entry_rows(hlo_text, skip_kinds=("parameter", "constant",
                                         "get-tuple-element", "tuple",
                                         "bitcast")):
    """Static per-instruction traffic estimate over the ENTRY computation
    of an optimized-HLO dump: for every top-level instruction, its
    result bytes plus the operand shapes named on its line. Returns
    ``(rows, kind_totals)`` where rows are
    ``(total_bytes, result_bytes, kind, name, line_snippet)`` sorted
    largest-first."""
    entry, in_entry = [], False
    for ln in hlo_text.splitlines():
        if ln.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry:
            if ln.startswith("}"):
                break
            entry.append(ln.strip())
    rows = []
    kind_totals = {}
    for ln in entry:
        # "ROOT %x = ..." lines count too (the original estimator
        # silently skipped the root instruction)
        m = re.match(r"(?:ROOT )?(%?[\w.\-]+) = (.+?) (\w+)\(", ln)
        if not m:
            continue
        name, shape_str, kind = m.groups()
        if kind in skip_kinds:
            continue
        result_b = hlo_shape_bytes(shape_str)
        operand_b = hlo_shape_bytes(ln[m.end():])
        total = result_b + operand_b
        rows.append((total, result_b, kind, name, ln[:160]))
        kind_totals[kind] = kind_totals.get(kind, 0) + total
    rows.sort(reverse=True)
    return rows, kind_totals


# ---------------------------------------------------------------------------
# cost attribution (AOT lower + cost_analysis + static HLO merge)
# ---------------------------------------------------------------------------

def template_feed(program, feed_names, batch=1):
    """Zero feed synthesized from the program's feed-var metadata
    (shape ``[-1, d1, ...]`` + dtype) at ``batch`` rows — the analysis
    twin of the serving engine's warmup template."""
    import numpy as np
    from ..core.types import np_dtype

    block = program.global_block()
    feed = {}
    for name in feed_names:
        v = block.var(name)
        dims = list(v.shape or [])
        if dims and dims[0] == -1:
            dims = dims[1:]
        if any(d is None or int(d) < 0 for d in dims):
            raise ValueError(
                f"feed var {name!r} has unknown dims {v.shape}; pass an "
                "explicit feed")
        dt = np_dtype(v.dtype) if v.dtype is not None else np.float32
        feed[name] = np.zeros([int(batch)] + [int(d) for d in dims], dt)
    return feed


def _program_dispatch(program, feed, fetch_list, executor, scope,
                      donate_feeds):
    """(executor, compiled-step wrapper, its arguments) of one dispatch of
    ``program``, resolved exactly as ``Executor.run`` resolves them."""
    import jax
    from ..core.executor import (Executor, _RNG_KEY, _collect_free_inputs,
                                 _written_names)
    from ..core.scope import global_scope

    exe = executor or Executor(mode="jit")
    # default scope = the global scope, exactly Executor.run's default
    # (a fresh empty scope would miss the program's trained parameters)
    scope = scope if scope is not None else global_scope()
    fetch_names = tuple(f if isinstance(f, str) else f.name
                        for f in fetch_list)
    feed = dict(feed)
    donated = {n: feed.pop(n) for n in donate_feeds
               if n in feed} if donate_feeds else {}
    if scope.find_var(_RNG_KEY) is None:
        scope.set(_RNG_KEY, jax.random.PRNGKey(program.random_seed or 0))
    block = program.global_block()
    free = _collect_free_inputs(program, 0)
    state_in = tuple(n for n in free
                     if n not in feed and n not in donated
                     and scope.has_var(n))
    written = _written_names(program, 0)
    state_out = tuple(n for n in written
                      if (block.has_var(n) and block.var(n).persistable)
                      or scope.has_var(n))
    fn = exe._compiled(program, tuple(sorted(feed)), fetch_names,
                       state_in, state_out, tuple(sorted(donated)))
    state = {n: scope.find_var(n) for n in state_in}
    state[_RNG_KEY] = scope.find_var(_RNG_KEY)
    return exe, fn, (state, feed) + ((donated,) if donated else ())


def lower_program(program, feed, fetch_list, executor=None, scope=None,
                  donate_feeds=()):
    """AOT-lower one dispatch of ``program`` exactly as ``Executor.run``
    would compile it (same state/feed surface resolution, same jit
    wrapper) and compile it for the attached backend. ``donate_feeds``
    names feeds that ride the donated third jit argument (the engine's
    KV-arena donation) — the lowered signature must match how the engine
    dispatches. Returns ``(lowered, compiled)``."""
    from ..core.amp import amp_guard

    exe, fn, args = _program_dispatch(program, feed, fetch_list, executor,
                                      scope, donate_feeds)
    with amp_guard(exe.amp):
        lowered = fn.lower(*args)
    return lowered, lowered.compile()


def program_jaxpr(program, feed, fetch_list, executor=None, scope=None):
    """The jaxpr of the same dispatch: what the step holds before XLA sees
    it (which primitives, how many of each), for structural tests."""
    import jax
    from ..core.amp import amp_guard

    exe, fn, args = _program_dispatch(program, feed, fetch_list, executor,
                                      scope, ())
    with amp_guard(exe.amp):
        return jax.make_jaxpr(fn.traceable)(*args)


def cost_totals(compiled):
    """``cost_analysis()`` of an AOT-compiled executable flattened to
    ``{flops, bytes_accessed, detail}`` (detail keeps every per-category
    ``bytes accessed*`` entry above 1e8 bytes); empty values when the
    backend provides nothing."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        ca = {}
    detail = {k: v for k, v in ca.items()
              if "bytes accessed" in k and k != "bytes accessed"
              and v > 1e8}
    return json_safe({"flops": ca.get("flops"),
                      "bytes_accessed": ca.get("bytes accessed"),
                      "detail": detail})


def attribute(target, feed=None, fetch_list=None, batch=1, top=40,
              executor=None, scope=None, dump_hlo=None, per_op=False):
    """Per-op cost attribution for one dispatch: AOT-lower ``target``,
    merge the backend's ``cost_analysis()`` totals with the optimized
    HLO's static per-instruction operand+result bytes, and return a
    top-N table.

    ``target`` is a ``Program`` (with ``feed`` + ``fetch_list``), a
    bundle directory (``save_inference_model`` export or a registry
    version dir — loaded into a private scope, feeds synthesized at
    ``batch`` rows), or an ``InferenceEngine`` (its program/scope).
    Returns ``{"cost": {flops, bytes_accessed, detail}, "kind_totals",
    "rows": [{bytes, result_bytes, kind, name, hlo}], "instructions",
    "compile_seconds"}``; ``dump_hlo=`` writes the optimized HLO text.

    ``per_op=True`` adds a ``"per_op"`` key — EVERY entry instruction
    (not just the rendered top-N) as structured ``{op, kind, flops,
    bytes, shape}`` dicts, the measured total FLOPs apportioned over the
    compute instructions (dot/convolution/fusion/custom-call) by their
    static byte share, ``flops: None`` when the backend gave no cost
    analysis — so consumers (the placement planner) never re-parse the
    rendered table. The default return is bitwise unchanged."""
    from ..serving.engine import InferenceEngine

    if isinstance(target, str):
        import paddle_tpu.fluid as fluid
        from ..core.scope import Scope
        scope = scope if scope is not None else Scope()
        exe = executor or fluid.Executor()
        program, feed_names, fetch_vars = fluid.io.load_inference_model(
            target, exe, scope=scope)
        feed = feed if feed is not None \
            else template_feed(program, feed_names, batch=batch)
        fetch_list = fetch_vars if fetch_list is None else fetch_list
        executor = exe
    elif isinstance(target, InferenceEngine):
        program = target.program
        scope = target._scope if scope is None else scope
        executor = target._exe if executor is None else executor
        feed = feed if feed is not None \
            else template_feed(program, target.feed_names, batch=batch)
        fetch_list = target.fetch_names if fetch_list is None else fetch_list
    else:
        program = target
        if feed is None or fetch_list is None:
            raise ValueError(
                "attribute(program, ...) needs feed= and fetch_list= "
                "(bundle dirs and engines synthesize their own)")

    t0 = time.perf_counter()
    with building():
        _lowered, compiled = lower_program(program, feed, fetch_list,
                                           executor=executor, scope=scope)
    compile_seconds = time.perf_counter() - t0
    cost = cost_totals(compiled)
    hlo = compiled.as_text()
    if dump_hlo:
        with open(dump_hlo, "w") as f:
            f.write(hlo)
    rows, kind_totals = hlo_entry_rows(hlo)
    note_compile("attribute", compile_seconds,
                 identity={"fetch": [f if isinstance(f, str) else f.name
                                     for f in fetch_list][:4]})
    out = {
        "cost": cost,
        "kind_totals": dict(sorted(kind_totals.items(),
                                   key=lambda kv: -kv[1])),
        "rows": [{"bytes": t, "result_bytes": rb, "kind": k,
                  "name": n, "hlo": snip}
                 for t, rb, k, n, snip in rows[:int(top)]],
        "instructions": len(rows),
        "compile_seconds": compile_seconds,
    }
    if per_op:
        out["per_op"] = per_op_rows(rows, cost.get("flops"))
    return json_safe(out)


# HLO instruction kinds that carry the computation's FLOPs — the
# apportioning targets for per_op_rows
_COMPUTE_KINDS = ("dot", "convolution", "fusion", "custom-call")


def per_op_rows(rows, total_flops=None):
    """``hlo_entry_rows`` rows as structured per-op dicts
    ``{op, kind, flops, bytes, shape}``: the result shape re-parsed from
    each row's HLO snippet, ``total_flops`` (the backend cost_analysis
    total) apportioned over the compute-kind instructions by their
    static byte share — ``flops: None`` everywhere when no total is
    available (a backend without cost analysis)."""
    compute_bytes = sum(t for t, _rb, k, _n, _s in rows
                        if k in _COMPUTE_KINDS)
    out = []
    for total, _result_b, kind, name, snip in rows:
        m = re.search(r"=\s*\(?([a-z0-9]+)\[([\d,]*)\]", snip)
        shape = None
        if m:
            shape = [int(d) for d in m.group(2).split(",") if d]
        flops = None
        if total_flops and compute_bytes and kind in _COMPUTE_KINDS:
            flops = float(total_flops) * total / compute_bytes
        out.append({"op": name, "kind": kind, "flops": flops,
                    "bytes": total, "shape": shape})
    return out


__all__ = [
    "COMPILE_LOG", "CompileLog", "CompileRecord", "MemorySampler",
    "STAGES", "attribute", "building", "compile_site", "cost_totals",
    "current_site", "enabled", "hlo_entry_rows",
    "hlo_shape_bytes", "lower_program", "memory_section", "note_compile",
    "per_op_rows", "program_jaxpr", "sample_device_memory", "template_feed",
]
