"""Persistent compiled-executable cache: replicas LOAD instead of compile.

Every serving warmup pays one XLA compile per executable (bucket, phase)
— PR 13's compile telemetry measured exactly one CompileRecord each —
and every replica spin-up, rolling reload, and online-loop rollout pays
them all again. This module closes that loop: AOT-lower each warmup
executable exactly as the engine dispatches it (the ``obs.perf.
lower_program`` path), serialize it via
``jax.experimental.serialize_executable``, and persist it next to the
bundle so the NEXT process deserializes in milliseconds instead of
recompiling in seconds. "Compile once, dispatch forever" — applied to
whole executables instead of kernels.

The safety contract is the whole design:

* **Full identity fingerprint.** An artifact is keyed by everything that
  could change the compiled bits: the bundle's registry ``content_hash``
  (the exact parameter/program bytes), the executable's feed
  shapes+dtypes and ordered fetch list (the jit cache's aval key), every
  ``_JIT_KEY_FLAGS`` value (``kernel_tier``!), the jax/jaxlib versions,
  and the backend platform + device kind. ANY mismatch is a silent miss
  followed by a normal compile — a stale or foreign artifact must never
  load, because a toolchain-skewed executable silently miscompiles.
* **Corruption is a miss, never a failure.** Artifacts carry a sha256
  over their payload; a truncated or bit-flipped file, a deserialize
  raise, or an executable that deserializes but fails its first dispatch
  all fall back to the compile path with a
  ``paddle_tpu_exec_cache_rejects`` bump and a flight-recorder event.
* **Bitwise-parity dispatch glue.** :class:`WarmExecutable` reproduces
  ``Executor.run``'s state/feed resolution around the deserialized
  executable — the SAME trace lowered the artifact (``lower_program``
  reuses the Executor's ``_compiled`` jit wrapper), so warm and cold
  dispatches run the same XLA computation and return bitwise-identical
  outputs (pinned by tests and the ``warm_start_serving`` bench lane).

Storage layouts: a published registry version holds its artifacts under
``<version>/warm/`` (built by :meth:`~.registry.ModelRegistry.warm`,
listed with per-file sha256 in ``VERSION.json``, covered by
``verify()``, deleted by ``gc()`` — engines open it READ-ONLY); the
``serving_exec_cache_dir`` flag names a per-process read-write local
cache for unpublished bundles. The ``serving_exec_cache`` flag is the
kill switch: off = every engine compiles exactly as before.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time

import numpy as np

from ..core.flags import get_flag
from ..obs.metrics import REGISTRY as _METRICS, json_safe, next_instance

WARM_DIRNAME = "warm"
ARTIFACT_SUFFIX = ".jexec"
_MAGIC = b"PDTPUEXEC1\n"

# reject reasons form a bounded enum (they become a metric label):
#   format      — bad magic / truncated / payload digest mismatch
#   manifest    — artifact unlisted in (or mismatching) the version
#                 manifest's warm_files digests — published warm dirs
#                 only; checked over the RAW bytes before unpickling
#   fingerprint — artifact is intact but keyed for a different identity
#   deserialize — unpickle / backend deserialize_executable raised
#   run_failed  — deserialized fine but the first dispatch raised
REJECT_REASONS = ("format", "manifest", "fingerprint", "deserialize",
                  "run_failed")

_M_HITS = _METRICS.counter(
    "paddle_tpu_exec_cache_hits",
    "persisted executables loaded instead of compiled, per cache instance",
    labels=("instance",))
_M_MISSES = _METRICS.counter(
    "paddle_tpu_exec_cache_misses",
    "warm-cache lookups with no artifact on disk (normal compile follows)",
    labels=("instance",))
_M_REJECTS = _METRICS.counter(
    "paddle_tpu_exec_cache_rejects",
    "artifacts refused at load (corrupt bytes, foreign fingerprint, "
    "deserialize/dispatch failure) — compile fallback, never an error",
    labels=("instance", "reason"))
_M_SAVE_SECONDS = _METRICS.histogram(
    "paddle_tpu_exec_cache_save_seconds",
    "wall seconds serializing + persisting one compiled executable",
    labels=("instance",), span_name="serving/exec_cache_save",
    span_kind="stage")


# ---------------------------------------------------------------------------
# identity
# ---------------------------------------------------------------------------

def bundle_content_hash(model_dir):
    """Content identity of the bundle at ``model_dir``: the registry
    manifest's ``content_hash`` when the dir is a published version,
    else recomputed over the bundle files with the registry's hashing
    discipline (sorted per-file sha256 combined) — so unpublished export
    dirs get the same exact-bytes keying published ones have."""
    from .registry import VERSION_MANIFEST, _content_hash, _sha256_file

    mpath = os.path.join(model_dir, VERSION_MANIFEST)
    if os.path.exists(mpath):
        try:
            with open(mpath) as f:
                ch = json.load(f).get("content_hash")
            if ch:
                return str(ch)
        except (OSError, ValueError):
            pass          # torn/corrupt manifest: fall through to re-hash
    files = {}
    for name in sorted(os.listdir(model_dir)):
        path = os.path.join(model_dir, name)
        if not os.path.isfile(path) or name == VERSION_MANIFEST \
                or name.endswith(".tmp"):
            continue
        files[name] = _sha256_file(path)
    return _content_hash(files)


def fingerprint(content_hash, tag, feeds, fetch_names, donated=()):
    """The full identity of ONE executable, as a JSON-safe dict. ``tag``
    names which executable of the bundle this is (``infer_b8``,
    ``gen_decode_b4``, ...); ``feeds`` are the PREPARED feed arrays (the
    exact values the jit boundary sees, so dtype/shape here == the
    compiled avals); ``fetch_names`` is the ordered fetch tuple (a
    reordered fetch list is a different executable). Everything else is
    toolchain: the ``_JIT_KEY_FLAGS`` tuple the Executor keys its own
    jit cache on (``kernel_tier`` flips must miss — no cross-tier
    artifact reuse), jax/jaxlib versions, and the backend platform +
    device kind (an artifact compiled for another backend must never
    load here)."""
    import jax
    import jaxlib

    from ..core.executor import _JIT_KEY_FLAGS

    dev = jax.devices()[0]
    fp = {
        "format": 1,
        "content_hash": str(content_hash),
        "tag": str(tag),
        "feeds": {str(k): [str(v.dtype),
                           [int(d) for d in getattr(v, "shape", ())]]
                  for k, v in feeds.items()},
        "fetch": [str(n) for n in fetch_names],
        "flags": {n: get_flag(n) for n in _JIT_KEY_FLAGS},
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": str(dev.platform),
        "device_kind": str(getattr(dev, "device_kind", dev.platform)),
    }
    if donated:
        # donated feeds change the compiled signature (third jit arg +
        # buffer aliasing) — keyed only when present so every pre-
        # donation artifact fingerprint is byte-identical to before
        fp["donated"] = sorted(str(n) for n in donated)
    return fp


def fingerprint_key(fp):
    """Stable digest of a fingerprint dict (the artifact filename key)."""
    return hashlib.sha256(
        json.dumps(fp, sort_keys=True, default=str).encode()).hexdigest()


# ---------------------------------------------------------------------------
# dispatch glue
# ---------------------------------------------------------------------------

class WarmExecutable:
    """A compiled executable plus the Executor.run glue around it.

    ``compiled`` is a ``jax.stages.Compiled`` — either freshly AOT-built
    (``source="compile"``: a cache fill) or deserialized from an
    artifact (``source="cache"``: the warm path). :meth:`run` reproduces
    exactly what ``Executor.run`` does around its jitted step fn — feed
    preparation, state resolution from the scope, state write-back — so
    a warm dispatch is indistinguishable from a jit dispatch except that
    it can never compile."""

    __slots__ = ("compiled", "source")

    def __init__(self, compiled, source):
        self.compiled = compiled
        self.source = source

    def run(self, executor, program, feed, scope, return_numpy=True,
            donate_feeds=()):
        import jax

        from ..core.executor import _RNG_KEY, _collect_free_inputs

        block = program.global_block()
        feed_vals = executor._prepare_feed(block, dict(feed))
        # the same donated/regular feed split lower_program made at save
        # time, so the call's arity matches the lowered signature
        donated = {n: feed_vals.pop(n) for n in donate_feeds
                   if n in feed_vals} if donate_feeds else {}
        if scope.find_var(_RNG_KEY) is None:
            scope.set(_RNG_KEY, jax.random.PRNGKey(program.random_seed or 0))
        # the same state surface lower_program resolved at save time, so
        # the call's pytree matches the lowered signature exactly
        free = _collect_free_inputs(program, 0)
        state = {n: scope.find_var(n) for n in free
                 if n not in feed_vals and n not in donated
                 and scope.has_var(n)}
        state[_RNG_KEY] = scope.find_var(_RNG_KEY)
        args = (state, feed_vals) + ((donated,) if donated else ())
        new_state, fetches = self.compiled(*args)
        for n, v in new_state.items():
            scope.set(n, v)
        return [np.asarray(v) if return_numpy else v for v in fetches]


def compile_and_save(cache, fp, program, feed, fetch_names, executor,
                     scope, site, identity=None, donate_feeds=()):
    """Cache fill: AOT-lower one dispatch exactly as the Executor
    compiles it (``obs.perf.lower_program`` — same jit wrapper, same
    state/feed resolution), persist the executable under ``fp``, and
    return it as a :class:`WarmExecutable` for immediate dispatch. The
    compile lands in the compile-telemetry layer with
    ``cache_hit: False`` (this is the one compile the cache exists to
    amortize); a failed SAVE only costs persistence — the freshly
    compiled executable is still returned and used."""
    from ..obs import perf as _perf

    t0 = time.perf_counter()
    with _perf.building():
        _lowered, compiled = _perf.lower_program(
            program, feed, list(fetch_names), executor=executor,
            scope=scope, donate_feeds=donate_feeds)
    seconds = time.perf_counter() - t0
    ident = dict(identity or {})
    ident["tag"] = fp["tag"]
    ident["cache_hit"] = False
    _perf.note_compile(site, seconds, identity=ident)
    cache.save(fp, compiled)
    return WarmExecutable(compiled, "compile")


# ---------------------------------------------------------------------------
# the on-disk cache
# ---------------------------------------------------------------------------

class ExecCache:
    """Directory of serialized executables, fingerprint-keyed.

    Artifact format: ``MAGIC + sha256hex(blob) + "\\n" + blob`` where
    ``blob`` pickles ``{"fingerprint", "payload", "in_tree",
    "out_tree"}`` (the ``serialize_executable.serialize`` triple). The
    digest detects truncation/bit rot before unpickling; the embedded
    fingerprint must equal the expected one, so a renamed or
    hash-colliding file is refused too. Writes are tmp + ``os.replace``
    (concurrent fillers race benignly — same key, same content).

    ``readonly=True`` is the published ``warm/`` dir contract: replicas
    load but never mutate a registry version; missing artifacts just
    compile without persisting.

    ``expected_digests`` (basename -> sha256 of the whole file, from the
    version manifest's ``warm_files``) pins what this cache may load:
    the RAW bytes must match the manifest BEFORE anything is unpickled,
    so a published version's artifacts carry exactly the bundle files'
    trust level — an artifact the manifest doesn't certify (tampered,
    swapped, or simply unlisted) is rejected without ever reaching
    ``pickle.loads``. Without it (local cache dirs this process writes
    itself) the artifact's self-digest covers corruption only."""

    def __init__(self, path, readonly=False, expected_digests=None):
        self.path = str(path)
        self.readonly = bool(readonly)
        self._expected = None if expected_digests is None \
            else dict(expected_digests)
        if not self.readonly:
            os.makedirs(self.path, exist_ok=True)
        self.obs_instance = next_instance("execcache")
        self._m_hits = _M_HITS.labels(instance=self.obs_instance)
        self._m_misses = _M_MISSES.labels(instance=self.obs_instance)
        self._m_save = _M_SAVE_SECONDS.labels(instance=self.obs_instance)
        self._m_rejects = {
            r: _M_REJECTS.labels(instance=self.obs_instance, reason=r)
            for r in REJECT_REASONS}
        # artifact basenames this instance successfully loaded or saved
        # — registry.warm() lists exactly this set in the manifest (a
        # stale artifact from an older toolchain/flag configuration is
        # unloadable forever and must not be re-certified)
        self._touched = set()

    # ------------------------------------------------------------------
    def artifact_path(self, fp):
        return os.path.join(
            self.path, f"{fp['tag']}-{fingerprint_key(fp)[:40]}"
                       f"{ARTIFACT_SUFFIX}")

    def note_reject(self, tag, reason, error=None):
        """Count + flight-record one refused artifact (engines call this
        for ``run_failed`` — a deserialized executable whose first
        dispatch raised; :meth:`load` calls it for the on-disk ones)."""
        from ..obs.recorder import record as _flight_record

        if reason not in self._m_rejects:
            reason = "deserialize"
        self._m_rejects[reason].inc()
        _flight_record("exec_cache_reject", component=self.obs_instance,
                       tag=str(tag), reason=reason,
                       error=None if error is None
                       else f"{type(error).__name__}: {error}")

    def load(self, fp, device):
        """The warm path: the artifact for ``fp``, deserialized and loaded
        onto ``device`` (the engine executor's — without it jax loads the
        executable for EVERY device of the backend, and on a multi-device
        host a one-device artifact then fails its first dispatch), or None
        (miss / reject — the caller compiles). Never raises: corruption at
        ANY depth is a reject + compile fallback, because a broken cache
        must only ever cost the compile it failed to save."""
        path = self.artifact_path(fp)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            self._m_misses.inc()
            return None
        stage = "format"
        try:
            if self._expected is not None:
                # manifest pinning: the raw bytes must be exactly what
                # the version manifest certifies, checked BEFORE any
                # unpickling — unlisted or mismatching bytes never
                # reach pickle.loads
                stage = "manifest"
                want = self._expected.get(os.path.basename(path))
                if want is None:
                    raise ValueError(
                        "artifact is not listed in the version "
                        "manifest's warm_files")
                if hashlib.sha256(raw).hexdigest() != want:
                    raise ValueError(
                        "artifact bytes do not match the manifest's "
                        "warm_files digest")
                stage = "format"
            if not raw.startswith(_MAGIC):
                raise ValueError("bad magic (not an artifact)")
            header_end = raw.index(b"\n", len(_MAGIC))
            digest = raw[len(_MAGIC):header_end].decode("ascii")
            blob = raw[header_end + 1:]
            if hashlib.sha256(blob).hexdigest() != digest:
                raise ValueError("payload digest mismatch (truncated or "
                                 "bit-flipped artifact)")
            stage = "deserialize"
            doc = pickle.loads(blob)
            stage = "fingerprint"
            if doc.get("fingerprint") != fp:
                raise ValueError("artifact fingerprint does not match the "
                                 "requested identity")
            stage = "deserialize"
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            compiled = deserialize_and_load(doc["payload"], doc["in_tree"],
                                            doc["out_tree"],
                                            execution_devices=[device])
        except Exception as e:
            self.note_reject(fp.get("tag", "?"), stage, error=e)
            return None
        self._m_hits.inc()
        self._touched.add(os.path.basename(path))
        return WarmExecutable(compiled, "cache")

    def save(self, fp, compiled):
        """Persist one AOT-compiled executable under ``fp``. Returns the
        artifact path, or None when the cache is read-only or the
        backend refuses serialization (both leave the caller with its
        working in-memory executable — persistence is best-effort)."""
        if self.readonly:
            return None
        from jax.experimental.serialize_executable import serialize

        from ..obs.recorder import record as _flight_record

        t0 = time.perf_counter()
        try:
            payload, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps(
                {"fingerprint": fp, "payload": payload,
                 "in_tree": in_tree, "out_tree": out_tree},
                protocol=pickle.HIGHEST_PROTOCOL)
            data = (_MAGIC + hashlib.sha256(blob).hexdigest().encode()
                    + b"\n" + blob)
            path = self.artifact_path(fp)
            tmp = path + f".{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
        except Exception as e:
            _flight_record("exec_cache_save_failed",
                           component=self.obs_instance,
                           tag=fp.get("tag", "?"),
                           error=f"{type(e).__name__}: {e}")
            return None
        self._m_save.observe(time.perf_counter() - t0)
        self._touched.add(os.path.basename(path))
        return path

    # ------------------------------------------------------------------
    def touched(self):
        """Artifact basenames this instance loaded or saved (sorted) —
        what a just-run warmup actually proved usable."""
        return sorted(self._touched)

    def artifacts(self):
        """Artifact filenames currently on disk (sorted)."""
        try:
            return sorted(n for n in os.listdir(self.path)
                          if n.endswith(ARTIFACT_SUFFIX))
        except OSError:
            return []

    def stats(self):
        # no filesystem I/O here: this rides every engine/server stats()
        # scrape (possibly against a network filesystem) — artifact
        # inventory is the touched set, not a per-scrape listdir
        save = self._m_save.snapshot()
        return json_safe({
            "dir": self.path,
            "readonly": self.readonly,
            "touched": len(self._touched),
            "hits": int(self._m_hits.value),
            "misses": int(self._m_misses.value),
            "rejects": {r: int(c.value)
                        for r, c in self._m_rejects.items()},
            "saves": int(save.get("count", 0)),
        })


def acquire(cache, content_hash, tag, program, feed, fetch_names,
            executor, scope, identity=None, donate_feeds=()):
    """Load-or-build ONE warm executable — the shared engine-side
    sequence: prepare the feed exactly as the jit boundary will see it,
    fingerprint, :meth:`ExecCache.load`, and (writable caches) AOT
    compile-and-persist on a miss. Returns a :class:`WarmExecutable` or
    None; NEVER raises — any failure is an ``exec_cache_skip`` flight
    event and the caller's bucket/phase just compiles through the
    normal jit path (a broken cache must only ever cost the compile it
    failed to skip)."""
    try:
        prepared = executor._prepare_feed(program.global_block(),
                                          dict(feed))
        donated = tuple(sorted(n for n in donate_feeds if n in prepared))
        fp = fingerprint(content_hash, tag, prepared, fetch_names,
                         donated=donated)
        entry = cache.load(fp, executor.device)
        if entry is None and not cache.readonly:
            entry = compile_and_save(cache, fp, program, prepared,
                                     fetch_names, executor=executor,
                                     scope=scope, site="exec_cache_save",
                                     identity=identity,
                                     donate_feeds=donated)
        return entry
    except Exception as e:
        from ..obs.recorder import record as _flight_record
        _flight_record("exec_cache_skip", component=cache.obs_instance,
                       tag=str(tag), error=f"{type(e).__name__}: {e}")
        return None


def manifest_warm_digests(model_dir):
    """basename -> sha256 pin set for the warm dir at ``model_dir``,
    from the version manifest's ``warm_files``. A manifest WITHOUT the
    field pins the empty set (a warm dir next to a manifest that never
    certified it loads nothing — replicas compile); no readable
    manifest at all returns None (not a registry version: the artifact
    self-digest is the only integrity layer)."""
    from .registry import VERSION_MANIFEST

    try:
        with open(os.path.join(model_dir, VERSION_MANIFEST)) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        return None
    return {os.path.basename(rel): digest
            for rel, digest in manifest.get("warm_files", {}).items()}


def resolve_cache(model_dir, exec_cache=None):
    """The cache an engine should use. An explicit ``exec_cache``
    (ExecCache or directory path) always wins — that is how
    ``ModelRegistry.warm`` opens a version's ``warm/`` dir writable.
    Otherwise, with the ``serving_exec_cache`` flag on (default): the
    bundle's published ``warm/`` dir read-only when it exists, else the
    ``serving_exec_cache_dir`` flag's local read-write dir, else None
    (no cache — bitwise the pre-cache behavior, which is also what a
    ``model_dir``-less engine gets: without bundle bytes there is no
    content identity to key artifacts on). ``exec_cache=False``
    disables the cache for this engine regardless of flags."""
    if exec_cache is False:
        return None
    if isinstance(exec_cache, ExecCache):
        return exec_cache
    if exec_cache is not None:
        return ExecCache(str(exec_cache))
    if model_dir is None or not get_flag("serving_exec_cache"):
        return None
    warm = os.path.join(str(model_dir), WARM_DIRNAME)
    if os.path.isdir(warm):
        return ExecCache(warm, readonly=True,
                         expected_digests=manifest_warm_digests(
                             str(model_dir)))
    local = get_flag("serving_exec_cache_dir")
    if local:
        return ExecCache(local)
    return None


__all__ = ["ExecCache", "WarmExecutable", "WARM_DIRNAME", "acquire",
           "bundle_content_hash", "compile_and_save", "fingerprint",
           "fingerprint_key", "manifest_warm_digests", "resolve_cache"]
