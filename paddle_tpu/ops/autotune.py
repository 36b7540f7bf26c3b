"""Kernel autotuner plane: measured per-shape variant selection.

The ``kernel_tier`` routing layer (ops/pallas/__init__.py) decides
pallas-vs-jnp per kernel FAMILY from the hand-edited ``AUTO_PALLAS``
frozenset — a guess encoded in source. This module makes that decision
DATA: every tunable kernel registers its named variants here (``jnp``,
``pallas``, and the conv_bn-only ``pallas_db`` double-buffered /
``pallas_bf16`` reduced-precision variants), a :class:`Tuner` times the
variants that support a concrete shape key — interleaved best-of-N
windows, the bench.py discipline — and the winners land in a persistent
:class:`TuneTable`. Dispatch sites consult the attached table through
:func:`dispatch_variant` under ``kernel_tier=auto`` BEFORE falling back
to the static ``AUTO_PALLAS`` routing, so a tuned table *is* the new
routing and an untuned process behaves bitwise as before.

The *Tensor Processing Primitives* design (PAPERS.md): a small set of
tuned primitives selected by measurement, not one-off hand-tuning — and
the lever that makes a TPU window cheap: every shape the fleet serves is
measured once at publish time and cached, instead of hand-tuned.

Persistence follows the execcache artifact contract exactly:

* **content-addressed artifact** — ``MAGIC + sha256hex(blob) + "\\n" +
  blob`` (blob is canonical JSON, no pickle), written tmp +
  ``os.replace``;
* **full identity fingerprint in the filename** — a table is only valid
  for the toolchain + backend + device kind that measured it
  (``table-<fingerprint_key[:40]>.jtune``), so a foreign table is a
  silent filename miss, never a wrong selection;
* **typed bounded rejects** — :data:`REJECT_REASONS`; every refusal is
  a ``paddle_tpu_kernel_autotune_rejects`` bump plus a
  ``kernel_autotune_reject`` flight event followed by static-routing
  fallback, never an engine failure;
* **manifest pinning** — a published ``<version>/tune/`` dir loads
  read-only with the RAW bytes checked against the manifest's
  ``tune_files`` digests before parsing (``registry.verify`` re-hashes
  the same digests offline, ``gc`` deletes them with the version).

Retrace discipline: the attached table's digest lives in the
``kernel_autotune_digest`` flag, which is in the executor's
``_JIT_KEY_FLAGS`` — attaching/detaching a table bumps the flags
version, so every jitted program retraces onto the new routing and
every execcache fingerprint keys on the digest (a warm artifact
compiled against table X never loads into a process routing by table Y).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from contextlib import contextmanager

from ..core.flags import get_flag, set_flags
from ..obs.metrics import REGISTRY as _METRICS
from .pallas import record_fallback, use_pallas

TUNE_DIRNAME = "tune"
ARTIFACT_SUFFIX = ".jtune"
_MAGIC = b"PDTPUTUNE1\n"

# typed bounded reject vocabulary (the execcache shape, minus run_failed
# — a tuning table is never executed, only read):
#   format       — bad magic / truncated / bit-flipped payload
#   manifest     — raw bytes not certified by the version manifest
#   fingerprint  — embedded identity != this process's identity
#   deserialize  — JSON/schema violations inside a well-formed envelope
REJECT_REASONS = ("format", "manifest", "fingerprint", "deserialize")

_M_SELECTIONS = _METRICS.counter(
    "paddle_tpu_kernel_autotune_selections",
    "dispatches routed by a tuned-table entry (counted at trace time, "
    "once per retrace — steady state adds zero), per kernel family",
    labels=("kernel",))
_M_TUNES = _METRICS.counter(
    "paddle_tpu_kernel_autotune_tunes",
    "tuner measurements recorded into a tuning table (one per (kernel, "
    "shape key) tuned), per kernel family",
    labels=("kernel",))
_M_REJECTS = _METRICS.counter(
    "paddle_tpu_kernel_autotune_rejects",
    "tuning-table artifacts refused, by typed reason "
    "(ops.autotune.REJECT_REASONS); every reject falls back to static "
    "AUTO_PALLAS routing, never an engine failure",
    labels=("reason",))
_M_SELECTED = _METRICS.gauge(
    "paddle_tpu_kernel_variant_selected",
    "entries in the ATTACHED tuning table per (kernel, winning variant) "
    "— zero everywhere when no table is attached",
    labels=("kernel", "variant"))

_M_VARIANT_FAILURES = _METRICS.counter(
    "paddle_tpu_kernel_autotune_variant_failures",
    "tuner candidates dropped because building or first running the "
    "variant raised (on a chip: a kernel that cannot compile) — the "
    "exception text rides a kernel_autotune_variant_failed flight event",
    labels=("kernel", "variant"))

_LOCK = threading.RLock()
_ACTIVE = None              # the attached TuneTable (process-wide)
_FORCED = {}                # kernel -> forced variant (tuner/tests)
_CAPTURE = None             # active capture list, or None


# ---------------------------------------------------------------------------
# shape keys
# ---------------------------------------------------------------------------

def make_key(**fields):
    """Canonical shape key for one dispatch: a sorted tuple of
    (name, value) pairs with shapes as int tuples and dtypes as strings
    — hashable, and JSON-stable via :func:`key_str`."""
    def canon(v):
        if isinstance(v, (list, tuple)):
            return tuple(canon(x) for x in v)
        if v is None or isinstance(v, (bool, int, float, str)):
            return v
        return str(v)                       # np/jnp dtypes and friends
    return tuple(sorted((str(k), canon(v)) for k, v in fields.items()))

def key_str(key):
    """The table's storage key: compact JSON of the key tuple (tuples
    encode as lists, deterministically)."""
    return json.dumps(key, separators=(",", ":"), default=list)


# ---------------------------------------------------------------------------
# the variant registry
# ---------------------------------------------------------------------------

class _VariantSpec:
    __slots__ = ("name", "build", "bf16")

    def __init__(self, name, build, bf16=False):
        self.name = name
        self.build = build          # build(key) -> zero-arg runner | None
        self.bf16 = bool(bf16)


class VariantRegistry:
    """Named variants per tunable kernel family. ``build(key)`` returns
    a zero-arg timed callable that runs ONE step of the variant on
    inputs synthesized from the shape key (or None when the key cannot
    be synthesized standalone — the tuner then records the routing
    winner without timings)."""

    def __init__(self):
        self._kernels = {}

    def register(self, kernel, name, build, bf16=False):
        self._kernels.setdefault(kernel, {})[name] = \
            _VariantSpec(name, build, bf16=bf16)

    def variants(self, kernel):
        return dict(self._kernels.get(kernel, {}))

    def kernels(self):
        return sorted(self._kernels)


VARIANTS = VariantRegistry()


def variant_allowed(kernel, name, registry=None):
    """May the table route this kernel to this variant HERE? Unknown
    names (a table from a newer build) and bf16-flagged variants without
    the ``kernel_autotune_bf16`` opt-in are refused — the dispatch falls
    through to static routing instead. ``registry`` defaults to the
    process-wide :data:`VARIANTS` (the Tuner passes its own)."""
    spec = (registry or VARIANTS).variants(kernel).get(name)
    if spec is None:
        return False
    return not spec.bf16 or bool(get_flag("kernel_autotune_bf16"))


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

@contextmanager
def force_variant(kernel, name):
    """Pin one kernel family to one variant for the duration (tuner
    runners and parity tests; trace-time effect — re-trace inside the
    context for jitted callers)."""
    with _LOCK:
        prev = _FORCED.get(kernel)
        _FORCED[kernel] = name
    try:
        yield
    finally:
        with _LOCK:
            if prev is None:
                _FORCED.pop(kernel, None)
            else:
                _FORCED[kernel] = prev


@contextmanager
def capture():
    """Record every (kernel, key, supported-variants) a traced region
    dispatches — what ``registry.warm(tune=True)`` runs around the
    engine's real warmup to learn which shapes to tune."""
    global _CAPTURE
    with _LOCK:
        prev, _CAPTURE = _CAPTURE, []
        keys = _CAPTURE
    try:
        yield keys
    finally:
        with _LOCK:
            _CAPTURE = prev


def dispatch_variant(kernel, key, supported, tier_kernel=None):
    """The ONE routing decision for a tunable dispatch site: which named
    variant executes this call. Host-side and trace-time (under jit it
    runs once per retrace), so steady state costs nothing.

    ``supported`` maps variant name -> this call's shape/config
    predicate. Order: a :func:`force_variant` pin wins; else under
    ``kernel_tier=auto`` with autotuning on, the attached table's entry
    for ``key`` (if its variant is supported and allowed); else the
    static pre-autotune routing via ``use_pallas(tier_kernel or
    kernel)`` — bitwise the old behavior. ``tier_kernel`` names the
    ``AUTO_PALLAS``/fallback-counter family when it differs from the
    table's kernel name (e.g. table kernel "rnn", tier family "lstm")."""
    tier = tier_kernel or kernel
    if _CAPTURE is not None:
        _CAPTURE.append((kernel, key,
                         tuple(sorted(n for n, ok in supported.items()
                                      if ok))))
    forced = _FORCED.get(kernel)
    if forced is not None:
        if supported.get(forced, False):
            return forced
        if forced != "jnp":
            record_fallback(tier)
        return "jnp"
    if (get_flag("kernel_tier") == "auto" and get_flag("kernel_autotune")
            and _ACTIVE is not None):
        choice = _ACTIVE.lookup(kernel, key)
        if (choice is not None and supported.get(choice, False)
                and variant_allowed(kernel, choice)):
            _M_SELECTIONS.labels(kernel=kernel).inc()
            return choice
    return "pallas" if use_pallas(tier, supported.get("pallas", False)) \
        else "jnp"


def note_variant_failure(kernel, variant, stage, error):
    """Count and record a tuner candidate that raised (``stage`` is
    "build" or "warmup"): a counter bump plus a flight event carrying the
    exception text — a variant that cannot compile must not lose
    silently."""
    from ..obs.recorder import record as _flight_record

    _M_VARIANT_FAILURES.labels(kernel=kernel, variant=variant).inc()
    _flight_record("kernel_autotune_variant_failed",
                   component="ops.autotune", kernel=kernel, variant=variant,
                   stage=stage,
                   error=f"{type(error).__name__}: {error}"[:2000])


# ---------------------------------------------------------------------------
# measurement core — THE interleaved best-of-N implementation
# ---------------------------------------------------------------------------

def measure(runners, repeats=3, inner=2, kernel="?"):
    """Time each runner: ``repeats`` interleaved windows of ``inner``
    calls each, best window kept — the bench.py best-of-N discipline,
    interleaved across variants so drift (thermal, a noisy neighbor)
    hits every variant equally instead of biasing whichever ran last.
    One untimed warmup call per runner absorbs trace+compile. Returns
    ``{name: best ms/call}``; a runner that raises during warmup is
    dropped (a variant that cannot run cannot win) — counted and recorded
    with its exception text under ``kernel`` (:func:`note_variant_failure`),
    never silently."""
    import jax

    def block(out):
        for leaf in jax.tree_util.tree_leaves(out):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()

    order = []
    for name in sorted(runners):
        try:
            block(runners[name]())
        except Exception as e:
            note_variant_failure(kernel, name, "warmup", e)
            continue
        order.append(name)
    best = {}
    for _ in range(max(1, int(repeats))):
        for name in order:
            fn = runners[name]
            t0 = time.perf_counter()
            out = None
            for _i in range(max(1, int(inner))):
                out = fn()
            block(out)
            ms = (time.perf_counter() - t0) * 1e3 / max(1, int(inner))
            if name not in best or ms < best[name]:
                best[name] = ms
    return best


# ---------------------------------------------------------------------------
# the tuning table + store (execcache fingerprint contract)
# ---------------------------------------------------------------------------

def table_fingerprint():
    """Identity a table's measurements are valid for: format/schema +
    toolchain + backend + device kind. Shapes and dtypes live in the
    per-entry keys; everything environmental lives here, so a table
    measured on another backend/toolchain is a filename miss (and a
    doctored one a typed ``fingerprint`` reject)."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    return {
        "format": 1,
        "kind": "kernel_tune_table",
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": str(dev.platform),
        "device_kind": str(getattr(dev, "device_kind", dev.platform)),
    }


def fingerprint_key(fp):
    """Stable digest of a fingerprint dict (the artifact filename key)."""
    return hashlib.sha256(
        json.dumps(fp, sort_keys=True, default=str).encode()).hexdigest()


class TuneTable:
    """{(kernel, key) -> winning variant (+ the timings that decided
    it)} under one :func:`table_fingerprint` identity."""

    def __init__(self, fingerprint=None, entries=None):
        self.fingerprint = dict(fingerprint) if fingerprint is not None \
            else table_fingerprint()
        # (kernel, key_str) -> {"variant": str, "timings_ms": {...}}
        self.entries = dict(entries or {})

    def set(self, kernel, key, variant, timings_ms=None):
        self.entries[(str(kernel), key_str(key))] = {
            "variant": str(variant),
            "timings_ms": {k: float(v)
                           for k, v in (timings_ms or {}).items()},
        }

    def lookup(self, kernel, key):
        e = self.entries.get((str(kernel), key_str(key)))
        return None if e is None else e["variant"]

    def merge(self, other):
        """Fold another table's entries in (same-key entries from
        ``other`` win — it is the newer measurement)."""
        self.entries.update(other.entries)
        return self

    def to_doc(self):
        return {
            "schema": "pdtpu-tune-table-v1",
            "fingerprint": dict(self.fingerprint),
            "entries": [
                {"kernel": k, "key": json.loads(ks),
                 "variant": e["variant"],
                 "timings_ms": dict(e["timings_ms"])}
                for (k, ks), e in sorted(self.entries.items())],
        }

    @classmethod
    def from_doc(cls, doc):
        """Strict schema validation — any violation raises ValueError
        (the store's ``deserialize`` reject)."""
        if not isinstance(doc, dict) \
                or doc.get("schema") != "pdtpu-tune-table-v1":
            raise ValueError("not a pdtpu-tune-table-v1 document")
        fp = doc.get("fingerprint")
        entries_doc = doc.get("entries")
        if not isinstance(fp, dict) or not isinstance(entries_doc, list):
            raise ValueError("malformed tuning-table document")
        table = cls(fingerprint=fp)
        for e in entries_doc:
            if not isinstance(e, dict) \
                    or not isinstance(e.get("kernel"), str) \
                    or not isinstance(e.get("variant"), str) \
                    or not isinstance(e.get("key"), list):
                raise ValueError("malformed tuning-table entry")
            timings = e.get("timings_ms", {})
            if not isinstance(timings, dict):
                raise ValueError("malformed tuning-table timings")
            table.entries[(e["kernel"],
                           json.dumps(e["key"], separators=(",", ":")))] \
                = {"variant": e["variant"],
                   "timings_ms": {str(k): float(v)
                                  for k, v in timings.items()}}
        return table

    def digest(self):
        """Content identity of the whole table (the
        ``kernel_autotune_digest`` flag value while attached)."""
        return hashlib.sha256(
            json.dumps(self.to_doc(), sort_keys=True).encode()).hexdigest()


class TuneStore:
    """One directory of tuning-table artifacts, execcache-disciplined:
    content-addressed envelope, identity in the filename, typed bounded
    rejects, optional manifest pinning, tmp+replace writes. ``load``
    and ``save`` never raise — a broken table must only ever cost the
    static routing it failed to replace."""

    def __init__(self, path, readonly=False, expected_digests=None):
        self.path = str(path)
        self.readonly = bool(readonly)
        self._expected = None if expected_digests is None \
            else dict(expected_digests)
        if not self.readonly:
            os.makedirs(self.path, exist_ok=True)
        self._touched = set()

    def artifact_path(self, fp=None):
        fp = fp if fp is not None else table_fingerprint()
        return os.path.join(
            self.path, f"table-{fingerprint_key(fp)[:40]}{ARTIFACT_SUFFIX}")

    def note_reject(self, reason, error=None):
        from ..obs.recorder import record as _flight_record

        if reason not in REJECT_REASONS:
            reason = "deserialize"
        _M_REJECTS.labels(reason=reason).inc()
        _flight_record("kernel_autotune_reject", component="ops.autotune",
                       dir=self.path, reason=reason,
                       error=None if error is None
                       else f"{type(error).__name__}: {error}")

    def load(self, fp=None):
        """The table for this process's identity, or None (miss or
        typed reject — the caller keeps static routing). A missing file
        is a silent miss; everything else wrong is a counted reject."""
        fp = fp if fp is not None else table_fingerprint()
        path = self.artifact_path(fp)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None
        stage = "format"
        try:
            if self._expected is not None:
                # manifest pinning: raw bytes must be exactly what the
                # version manifest certifies, BEFORE any parsing
                stage = "manifest"
                want = self._expected.get(os.path.basename(path))
                if want is None:
                    raise ValueError("artifact is not listed in the "
                                     "version manifest's tune_files")
                if hashlib.sha256(raw).hexdigest() != want:
                    raise ValueError("artifact bytes do not match the "
                                     "manifest's tune_files digest")
                stage = "format"
            if not raw.startswith(_MAGIC):
                raise ValueError("bad magic (not a tuning-table artifact)")
            header_end = raw.index(b"\n", len(_MAGIC))
            digest = raw[len(_MAGIC):header_end].decode("ascii")
            blob = raw[header_end + 1:]
            if hashlib.sha256(blob).hexdigest() != digest:
                raise ValueError("payload digest mismatch (truncated or "
                                 "bit-flipped artifact)")
            stage = "deserialize"
            table = TuneTable.from_doc(json.loads(blob.decode("utf-8")))
            stage = "fingerprint"
            if table.fingerprint != fp:
                raise ValueError("table fingerprint does not match this "
                                 "process's identity")
        except Exception as e:
            self.note_reject(stage, error=e)
            return None
        self._touched.add(os.path.basename(path))
        return table

    def save(self, table):
        """Persist one table (tmp + ``os.replace``); returns the
        artifact path, or None when read-only / unwritable."""
        if self.readonly:
            return None
        from ..obs.recorder import record as _flight_record

        try:
            blob = json.dumps(table.to_doc(), sort_keys=True).encode()
            data = (_MAGIC + hashlib.sha256(blob).hexdigest().encode()
                    + b"\n" + blob)
            path = self.artifact_path(table.fingerprint)
            tmp = path + f".{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
        except Exception as e:
            _flight_record("kernel_autotune_save_failed",
                           component="ops.autotune", dir=self.path,
                           error=f"{type(e).__name__}: {e}")
            return None
        self._touched.add(os.path.basename(path))
        return path

    def touched(self):
        return sorted(self._touched)


# ---------------------------------------------------------------------------
# attach / resolve (the active-table plumbing engines use)
# ---------------------------------------------------------------------------

def _refresh_selected_gauge():
    _M_SELECTED.reset()
    if _ACTIVE is None:
        return
    counts = {}
    for (kernel, _ks), e in _ACTIVE.entries.items():
        pair = (kernel, e["variant"])
        counts[pair] = counts.get(pair, 0) + 1
    for (kernel, variant), n in counts.items():
        _M_SELECTED.labels(kernel=kernel, variant=variant).set(n)


def attach_table(table, merge=True):
    """Make ``table`` the process-wide routing table and key every
    retrace + execcache fingerprint on its digest (the
    ``kernel_autotune_digest`` flag). ``merge=True`` folds it into an
    already-attached table (entries are shape-keyed and
    model-independent, so two bundles' tables coexist). Returns the
    active digest."""
    global _ACTIVE
    with _LOCK:
        if merge and _ACTIVE is not None:
            table = TuneTable(fingerprint=table.fingerprint,
                              entries=_ACTIVE.entries).merge(table)
        _ACTIVE = table
        digest = table.digest()
        _refresh_selected_gauge()
    set_flags({"kernel_autotune_digest": digest})
    return digest


def detach_table():
    """Drop the active table: routing reverts to static AUTO_PALLAS and
    the digest flag clears (flags-version bump -> retrace)."""
    global _ACTIVE
    with _LOCK:
        _ACTIVE = None
        _refresh_selected_gauge()
    set_flags({"kernel_autotune_digest": ""})


def active_table():
    return _ACTIVE


def active_digest():
    """Digest of the attached table, or None — what bench records stamp
    as ``tune_digest`` and engine stats surface."""
    with _LOCK:
        return None if _ACTIVE is None else _ACTIVE.digest()


def manifest_tune_digests(model_dir):
    """basename -> sha256 pin set from the version manifest's
    ``tune_files``. Manifest without the field pins the empty set (an
    uncertified tune dir next to a manifest loads nothing); no readable
    manifest returns None (not a registry version — the artifact
    self-digest is the only integrity layer)."""
    try:
        with open(os.path.join(model_dir, "VERSION.json")) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        return None
    return {os.path.basename(rel): digest
            for rel, digest in manifest.get("tune_files", {}).items()}


def resolve_store(model_dir=None):
    """The store an engine should read its table from: the bundle's
    published ``tune/`` dir (read-only, manifest-pinned) when it
    exists, else the ``kernel_autotune_dir`` flag's local dir, else
    None — the execcache ``resolve_cache`` precedence."""
    if model_dir:
        tdir = os.path.join(str(model_dir), TUNE_DIRNAME)
        if os.path.isdir(tdir):
            return TuneStore(tdir, readonly=True,
                             expected_digests=manifest_tune_digests(
                                 str(model_dir)))
    local = get_flag("kernel_autotune_dir")
    if local and os.path.isdir(local):
        return TuneStore(local, readonly=True)
    return None


def attach_for_bundle(model_dir=None):
    """Engine-warmup hook: resolve + load + attach the bundle's table
    BEFORE any executable is compiled or acquired, so the digest flag
    is already in the jit key and every execcache fingerprint. No-op
    (returns None) unless ``kernel_tier=auto`` with ``kernel_autotune``
    on and a loadable table exists; corruption downgrades to static
    routing via the store's typed rejects — never a raise."""
    if not get_flag("kernel_autotune") or get_flag("kernel_tier") != "auto":
        return None
    store = resolve_store(model_dir)
    if store is None:
        return None
    table = store.load()
    if table is None:
        return None
    return attach_table(table)


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------

class Tuner:
    """Measure captured dispatch keys and record the winners.

    ``repeats``/``inner`` are the interleaved best-of-N window shape
    (see :func:`measure`). bf16-flagged variants join the candidate set
    only under the ``kernel_autotune_bf16`` opt-in — a value-changing
    variant must be chosen, never stumbled into."""

    def __init__(self, repeats=3, inner=2, registry=None):
        self.repeats = int(repeats)
        self.inner = int(inner)
        self.registry = registry or VARIANTS

    def tune(self, captured, table=None):
        """-> :class:`TuneTable` with one entry per distinct
        (kernel, key) in ``captured`` (the :func:`capture` output).
        Single-candidate keys record their only routing without
        timings; multi-candidate keys are measured."""
        table = table if table is not None else TuneTable()
        seen = set()
        for kernel, key, supported_names in captured:
            ks = (kernel, key_str(key))
            if ks in seen:
                continue
            seen.add(ks)
            specs = self.registry.variants(kernel)
            cands = [n for n in supported_names
                     if n in specs
                     and variant_allowed(kernel, n, self.registry)]
            if not cands:
                continue
            winner, timings = cands[0], {}
            if len(cands) > 1:
                runners = {}
                for n in cands:
                    try:
                        r = specs[n].build(key)
                    except Exception as e:
                        note_variant_failure(kernel, n, "build", e)
                        r = None
                    if r is not None:
                        runners[n] = r
                if len(runners) > 1:
                    timings = measure(runners, repeats=self.repeats,
                                      inner=self.inner, kernel=kernel)
                if timings:
                    winner = min(timings, key=timings.get)
                elif "jnp" in cands:
                    winner = "jnp"
            table.set(kernel, key, winner, timings)
            _M_TUNES.labels(kernel=kernel).inc()
        return table


# ---------------------------------------------------------------------------
# variant registrations — runner builders synthesize inputs from keys
# ---------------------------------------------------------------------------

def _fields(key):
    return dict(key)


def _rng_fill(shape, dtype, seed):
    import numpy as np

    rng = np.random.RandomState(seed)
    return rng.standard_normal(size=shape).astype(dtype)


def _conv_bn_build(variant):
    def build(key):
        import jax
        import jax.numpy as jnp

        k = _fields(key)
        dtype = k["dtype"]
        x = jnp.asarray(_rng_fill(k["x"], dtype, 11))
        w = jnp.asarray(_rng_fill(k["w"], dtype, 13))
        cout = int(k["w"][0])
        scale = jnp.ones((cout,), jnp.float32)
        bias = jnp.zeros((cout,), jnp.float32)
        rm = jnp.zeros((cout,), jnp.float32)
        rv = jnp.ones((cout,), jnp.float32)
        strides, paddings = k["strides"], k["paddings"]
        act, is_test = k["act"], bool(k["is_test"])
        eps = 1e-5
        if variant == "jnp":
            from .conv_ops import _conv2d_compute
            from .norm_ops import bn_forward_math

            def f(x, w, scale, bias, rm, rv):
                z = _conv2d_compute(x, w, strides, paddings,
                                    k["dilations"], k["groups"], k["df"])
                y = bn_forward_math(z, scale, bias, rm, rv, eps, 0.9,
                                    k["df"], is_test)[0]
                return jnp.maximum(y, 0) if act == "relu" else y
            fn = jax.jit(f)
            return lambda: fn(x, w, scale, bias, rm, rv)
        from .pallas import conv_bn as cbk
        block_n = 2 if variant == "pallas_db" else 1
        if variant == "pallas_bf16":
            x = x.astype(jnp.bfloat16)
            w = w.astype(jnp.bfloat16)
        if is_test:
            def f(x, w, a, b):
                return cbk.conv_affine_pallas(x, w, a, b, strides,
                                              paddings, act,
                                              block_n=block_n)
            fn = jax.jit(f)
            return lambda: fn(x, w, scale, bias)
        def f(x, w, scale, bias):
            return cbk.conv_bn_train_pallas(x, w, scale, bias, eps,
                                            strides, paddings, act,
                                            block_n=block_n)
        fn = jax.jit(f)
        return lambda: fn(x, w, scale, bias)
    return build


def _paged_attention_build(variant):
    def build(key):
        import jax
        import jax.numpy as jnp
        import numpy as np

        k = _fields(key)
        s, h, d = (int(v) for v in k["q"])
        nb, bs = int(k["kc"][0]), int(k["kc"][1])
        p = int(k["tables"])
        qh = jnp.asarray(_rng_fill((s, h, d), k["dtype"], 17))
        kc = jnp.asarray(_rng_fill((nb, bs, h, d), k["dtype"], 19))
        vc = jnp.asarray(_rng_fill((nb, bs, h, d), k["dtype"], 23))
        bt = jnp.asarray((np.arange(s * p) % nb).reshape(s, p)
                         .astype(np.int32))
        ctx = jnp.full((s,), min(p * bs, nb * bs), jnp.int32)
        from .pallas import paged_attention as pa
        fn = jax.jit(pa.paged_attention_pallas if variant == "pallas"
                     else pa.paged_attention_jnp)
        return lambda: fn(qh, kc, vc, bt, ctx)
    return build


def _rnn_build(variant):
    def build(key):
        import jax
        import jax.numpy as jnp

        k = _fields(key)
        cell = k["cell"]
        b, L, hx = (int(v) for v in k["x"])
        H = hx // (4 if cell == "lstm" else 3)
        dtype = k["dtype"]
        x = jnp.asarray(_rng_fill((b, L, hx), dtype, 29)) * 0.1
        w = jnp.asarray(_rng_fill((H, hx), dtype, 31)) * 0.1
        lens = jnp.full((b,), L, jnp.int32)
        from . import rnn_ops
        if cell == "lstm":
            h0 = jnp.zeros((b, H), x.dtype)
            c0 = jnp.zeros((b, H), x.dtype)
            fn = jax.jit(lambda x, lens, w, h0, c0: rnn_ops._lstm_scan(
                x, lens, w, h0, c0, "sigmoid", "tanh", "tanh"))
            args = (x, lens, w, h0, c0)
        else:
            fn = jax.jit(lambda x, lens, w: rnn_ops._gru_compute(
                x, lens, w, None, None, {}))
            args = (x, lens, w)

        def run():
            # re-enter the force context every call: the first call
            # traces INSIDE it (pinning the variant into the jaxpr),
            # later calls are cache hits
            with force_variant("rnn", variant):
                return fn(*args)
        return run
    return build


def _embedding_build(variant):
    def build(key):
        import jax
        import jax.numpy as jnp
        import numpy as np

        k = _fields(key)
        rows, dim, nnz = int(k["rows"]), int(k["dim"]), int(k["nnz"])
        p = jnp.asarray(_rng_fill((rows, dim), k["dtype"], 37))
        vals = jnp.asarray(_rng_fill((nnz, dim), k["dtype"], 41))
        # Knuth-hash row ids: spread like real minibatch ids
        idx = jnp.asarray(((np.arange(nnz) * 2654435761) % rows)
                          .astype(np.int32))
        lr = jnp.asarray(0.01, p.dtype)
        if variant == "pallas":
            from .pallas.embedding import embedding_sgd_pallas
            fn = jax.jit(embedding_sgd_pallas)
            return lambda: fn(p, idx, vals, lr)
        fn = jax.jit(lambda p, r, v, lr: p.at[r].add(-lr * v, mode="drop"))
        return lambda: fn(p, idx, vals, lr)
    return build


def _optimizer_build(variant):
    def build(key):
        import jax
        import jax.numpy as jnp

        k = _fields(key)
        kind, tensors, elems = k["kind"], int(k["tensors"]), int(k["elems"])
        per = max(1, elems // max(1, tensors))
        from .optimizer_ops import (_adam_dense, _momentum_dense,
                                    _sgd_dense)
        ps = [jnp.asarray(_rng_fill((per,), "float32", 43 + i))
              for i in range(tensors)]
        gs = [jnp.asarray(_rng_fill((per,), "float32", 53 + i))
              for i in range(tensors)]
        ss = [jnp.asarray(_rng_fill((per,), "float32", 67 + i))
              for i in range(tensors)]
        s2 = [jnp.abs(jnp.asarray(_rng_fill((per,), "float32", 79 + i)))
              for i in range(tensors)]
        lr, mu = 0.01, 0.9
        if variant == "pallas":
            from .pallas import optimizer as opk

            def f(ps, gs, ss, s2):
                shapes = [p.shape for p in ps]
                if kind == "sgd":
                    arenas = [opk.flatten_arena(t)[0] for t in (ps, gs)]
                    results = (opk.sgd_arena_pallas(*arenas, lr),)
                elif kind == "momentum":
                    arenas = [opk.flatten_arena(t)[0]
                              for t in (ps, gs, ss)]
                    results = opk.momentum_arena_pallas(*arenas, lr, mu)
                else:
                    arenas = [opk.flatten_arena(t)[0]
                              for t in (ps, gs, ss, s2)]
                    results = opk.adam_arena_pallas(*arenas, lr, 0.9,
                                                    0.999, 1e-8)
                return [opk.split_arena(r, shapes) for r in results]
        else:
            def f(ps, gs, ss, s2):
                out = []
                for i in range(tensors):
                    if kind == "sgd":
                        out.append(_sgd_dense(ps[i], gs[i], lr))
                    elif kind == "momentum":
                        out.append(_momentum_dense(ps[i], gs[i], ss[i],
                                                   lr, mu, False))
                    else:
                        out.append(_adam_dense(ps[i], gs[i], ss[i],
                                               s2[i], lr, 0.9, 0.999,
                                               1e-8))
                return out
        fn = jax.jit(f)
        return lambda: fn(ps, gs, ss, s2)
    return build


VARIANTS.register("conv_bn", "jnp", _conv_bn_build("jnp"))
VARIANTS.register("conv_bn", "pallas", _conv_bn_build("pallas"))
VARIANTS.register("conv_bn", "pallas_db", _conv_bn_build("pallas_db"))
VARIANTS.register("conv_bn", "pallas_bf16", _conv_bn_build("pallas_bf16"),
                  bf16=True)
VARIANTS.register("paged_attention", "jnp", _paged_attention_build("jnp"))
VARIANTS.register("paged_attention", "pallas",
                  _paged_attention_build("pallas"))
# chunked prefill has one lowering today; registering it keeps its
# warmup shapes in tuned tables so a future pallas variant tunes in
# with zero dispatch-site changes
VARIANTS.register("chunked_prefill_attention", "jnp", lambda key: None)
VARIANTS.register("rnn", "jnp", _rnn_build("jnp"))
VARIANTS.register("rnn", "pallas", _rnn_build("pallas"))
VARIANTS.register("embedding", "jnp", _embedding_build("jnp"))
VARIANTS.register("embedding", "pallas", _embedding_build("pallas"))
VARIANTS.register("optimizer", "jnp", _optimizer_build("jnp"))
VARIANTS.register("optimizer", "pallas", _optimizer_build("pallas"))


__all__ = [
    "ARTIFACT_SUFFIX", "REJECT_REASONS", "TUNE_DIRNAME", "TuneStore",
    "TuneTable", "Tuner", "VARIANTS", "VariantRegistry", "active_digest",
    "active_table", "attach_for_bundle", "attach_table", "capture",
    "detach_table", "dispatch_variant", "fingerprint_key",
    "force_variant", "key_str", "make_key", "manifest_tune_digests",
    "measure", "note_variant_failure", "resolve_store", "table_fingerprint",
    "variant_allowed",
]
