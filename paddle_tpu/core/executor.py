"""Executor: lowers a Program block to ONE jitted XLA computation.

The reference Executor interprets a block op-by-op, dispatching a per-op
CPU/CUDA kernel each step (/root/reference/paddle/fluid/framework/
executor.cc:96,317-319 — the hot loop) with a Prepare/RunPreparedContext split
for reuse (executor.cc:271) and a Python-side program cache
(python/paddle/fluid/executor.py:166,309-377).

TPU-native re-design (SURVEY.md §7 "make the Executor a compiler"): the hot loop
becomes a *trace* — ops' jax.numpy lowerings run under ``jax.jit``, so the whole
block (forward + backward + optimizer ops, which live in the same program, see
reference optimizer.py:224) compiles to a single fused XLA computation per
(program-version, feed-signature). XLA does the kernel fusion/tiling the
reference hand-wrote in CUDA. An eager mode (``mode="eager"``) keeps the
op-at-a-time interpreter semantics for debugging and OpTest parity — the analog
of the reference's CPU kernel path.

State contract: persistable variables (parameters, optimizer accumulators,
learning rates) live in a Scope between runs, exactly like the reference's
global scope (executor.cc:286-315 creates persistables in the global scope and
temporaries in a dropped local scope). The compiled step function is pure:
``(state, feeds, rng) -> (new_state, fetches, rng')``.
"""

from __future__ import annotations

import weakref
from time import perf_counter as _perf_counter

import numpy as np
import jax
import jax.numpy as jnp

from . import registry
from .amp import amp_guard
from .profiler import record_event
from .lod import LoDArray, flat_to_lodarray, pack_sequences
from .scope import Scope, global_scope
from .types import np_dtype
from ..obs.metrics import REGISTRY as _METRICS
from ..obs import perf as _perf

_RNG_KEY = "__rng_key__"

# ---------------------------------------------------------------------------
# obs_op_metrics flag: executor counters in the obs.metrics registry.
# Deliberately NOT in _JIT_KEY_FLAGS — flipping the flag must never
# retrace (the hooks are host-side only); when off, the hot path pays one
# flag lookup per run(). Eager dispatches get REAL per-op wall time;
# jit runs count each block-0 op once per step from the cached
# _ProgramAnalysis op inventory (single ops have no host-visible duration
# inside a compiled step). The retrace counter counts compiled-function
# (re)builds unconditionally — compiles are already expensive, and a
# steady-state training loop must keep it flat.
# ---------------------------------------------------------------------------

_M_OP_DISPATCHES = _METRICS.counter(
    "paddle_tpu_executor_op_dispatches",
    "op dispatches by op type (obs_op_metrics flag; jit steps count "
    "each top-level op once per run from the cached program inventory)",
    labels=("op_type",))
_M_OP_SECONDS = _METRICS.counter(
    "paddle_tpu_executor_op_seconds",
    "cumulative per-op-type eager DISPATCH wall time in seconds — timed "
    "around the op forward only, independent of co-enabled debug flags; "
    "the async tail is not awaited (obs_op_metrics flag; control-flow "
    "ops include their sub-blocks)", labels=("op_type",))
_M_STEPS = _METRICS.counter(
    "paddle_tpu_executor_steps",
    "Executor.run dispatches, by executor mode (obs_op_metrics flag)",
    labels=("mode",))
_M_RETRACES = _METRICS.counter(
    "paddle_tpu_executor_retraces",
    "compiled step-function (re)builds — one per trace/retrace event, "
    "flat in steady state", labels=("kind",))

# op_type -> (dispatch child, seconds child); lazy so only op types that
# actually dispatch create series
_OP_CHILDREN: dict = {}


def _op_children(op_type):
    mc = _OP_CHILDREN.get(op_type)
    if mc is None:
        mc = _OP_CHILDREN[op_type] = (
            _M_OP_DISPATCHES.labels(op_type=op_type),
            _M_OP_SECONDS.labels(op_type=op_type))
    return mc


class Place:
    pass


class CPUPlace(Place):
    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    """The device the reference calls CUDAPlace (platform/place.h) — here a TPU
    chip addressed through JAX. An explicit TPU place that cannot be
    honoured (no TPU attached, or ``device_id`` past the last chip) raises
    when the Executor resolves it; it never lands on another device."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


def _resolve_device(place):
    if place is None:
        return jax.devices()[0]
    if isinstance(place, TPUPlace):
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise RuntimeError(
                f"{place!r}: JAX attached no TPU (platform="
                f"{devs[0].platform!r}); use CPUPlace() or place=None to "
                "run where JAX puts the program")
        if not 0 <= place.device_id < len(devs):
            raise RuntimeError(
                f"{place!r}: only {len(devs)} TPU device(s) attached")
        return devs[place.device_id]
    if isinstance(place, CPUPlace):
        return jax.devices("cpu")[0]
    return place  # already a jax Device


class _PreparedSteps:
    """Handle from Executor.prepare_steps: the compiled K-step scan bound to
    device-staged stacked feeds (the reference's ExecutorPrepareContext,
    framework/executor.cc:271)."""

    __slots__ = ("fn", "stacked", "carry_keys", "scope")

    def __init__(self, fn, stacked, carry_keys, scope):
        self.fn = fn
        self.stacked = stacked
        self.carry_keys = carry_keys
        self.scope = scope


class ExecContext:
    """Per-op view of the environment handed to op lowerings — the analog of
    the reference's ExecutionContext (framework/operator.h:183)."""

    __slots__ = ("op", "block", "env", "_exec")

    def __init__(self, op, block, env, exec_state):
        self.op = op
        self.block = block
        self.env = env
        self._exec = exec_state

    # ---- inputs / outputs ----
    def has_input(self, slot):
        names = self.op.input(slot)
        return bool(names) and names[0] in self.env

    def input(self, slot):
        names = self.op.input(slot)
        if not names:
            raise KeyError(f"op {self.op.type}: missing input slot {slot!r}")
        return self._read(names[0])

    def inputs(self, slot):
        return [self._read(n) for n in self.op.input(slot)]

    def _read(self, name):
        if name not in self.env:
            raise KeyError(
                f"op {self.op.type}: variable {name!r} used before definition")
        return self.env[name]

    def set_output(self, slot, value):
        names = self.op.output(slot)
        if names:
            self.env[names[0]] = value

    def set_outputs(self, slot, values):
        for n, v in zip(self.op.output(slot), values):
            self.env[n] = v

    # ---- attrs ----
    def attr(self, name, default=None):
        return self.op.attrs.get(name, default)

    # ---- var metadata ----
    def var(self, name):
        return self.block.var(name)

    def out_dtype(self, slot="Out"):
        """Declared numpy dtype of the (first) output var, when annotated."""
        names = self.op.output(slot)
        if names and self.block.has_var(names[0]):
            d = self.block.var(names[0]).dtype
            if d is not None:
                return np_dtype(d)
        return None

    # ---- rng ----
    def next_rng(self):
        key, sub = jax.random.split(self.env[_RNG_KEY])
        self.env[_RNG_KEY] = key
        return sub

    # ---- control flow: run a sub-block over the current env ----
    def run_sub_block(self, block_idx):
        sub = self.block.program.blocks[block_idx]
        _run_ops(sub, self.env, self._exec)

    def sub_block(self, attr_name="sub_block"):
        return self.block.program.blocks[self.attr(attr_name)]


def _check_op_outputs_finite(op, env):
    """Eager NaN/Inf sweep after each op (reference --check_nan_inf,
    framework/executor.cc:325-333 CheckTensorNANOrInf). Tracer leaves
    (control-flow sub-blocks trace through lax.scan/while even in eager
    mode) are skipped — those regions are covered by the jit-path
    debug_nans/debug_infs instead."""
    for name in op.output_arg_names():
        v = env.get(name)
        for leaf in jax.tree_util.tree_leaves(v):
            if isinstance(leaf, jax.core.Tracer):
                continue
            arr = np.asarray(leaf)
            if np.issubdtype(arr.dtype, np.floating) and \
                    not np.isfinite(arr).all():
                kind = "NaN" if np.isnan(arr).any() else "Inf"
                raise FloatingPointError(
                    f"{kind} in output {name!r} of op {op.type!r} "
                    "(check_nan_inf flag)")


def _run_ops(block, env, exec_state):
    """Run/trace every op of a block over ``env`` in order. This is both the
    eager interpreter and the function traced by jit."""
    from .flags import get_flag
    scopes = _analyze_program(block.program).op_scopes(block)
    # dispatch-coverage recording happens per-op AFTER each forward below
    # (an op that raises must not mark the block's remaining ops as
    # dispatched); no-op lambda when disabled keeps the loops branch-free
    record = registry.record_dispatch \
        if registry.dispatch_coverage_enabled() else (lambda t: None)
    if not getattr(exec_state, "_tracing", False) and \
            (get_flag("check_nan_inf") or get_flag("benchmark")
             or get_flag("obs_op_metrics")):
        # eager-path debug/metering modes: per-op NaN/Inf host sweep (jit
        # covers this via debug_nans/debug_infs around dispatch), per-op
        # wall timing (reference --benchmark, executor.cc:321-324), and
        # obs_op_metrics dispatch/wall-time counters (real op times here;
        # control-flow ops recurse through run_sub_block, so their time
        # includes their sub-blocks')
        import time as _time
        bench = get_flag("benchmark")
        check = get_flag("check_nan_inf")
        opm = get_flag("obs_op_metrics")
        for op, op_scope in zip(block.ops, scopes):
            t0 = _time.perf_counter() if (bench or opm) else 0.0
            info = registry.get_op_info(op.type)
            with record_event(op.type, kind="op"), jax.named_scope(op_scope):
                info.forward(ExecContext(op, block, env, exec_state))
            record(op.type)
            if opm:
                # timed BEFORE the check/bench extras below, so the
                # counter means the same thing regardless of which debug
                # flags ride along (eager dispatch time; the async tail
                # is not awaited)
                disp, secs = _op_children(op.type)
                disp.inc()
                secs.inc(_time.perf_counter() - t0)
            if check:
                _check_op_outputs_finite(op, env)
            if bench:
                outs = [env.get(n) for n in op.output_arg_names()]
                jax.block_until_ready([o for o in outs
                                       if isinstance(o, jax.Array)])
                print(f"[benchmark] {op.type}: "
                      f"{(_time.perf_counter() - t0) * 1e3:.3f} ms",
                      flush=True)
        return
    # per-op host spans, the reference's RecordEvent around op->Run
    # (executor.cc:317, operator.cc:488). In eager mode these are real op
    # times; under jit they are trace-time spans (still useful for finding
    # slow-to-trace ops) while the compiled step is covered by the
    # executor.run spans in Executor.run. The named scope is what the
    # compiled step's instructions carry (below).
    for op, op_scope in zip(block.ops, scopes):
        info = registry.get_op_info(op.type)
        ctx = ExecContext(op, block, env, exec_state)
        with record_event(op.type, kind="op"), jax.named_scope(op_scope):
            info.forward(ctx)
        record(op.type)


# ---------------------------------------------------------------------------
# A named scope per Fluid op, with its phase. ``_run_ops`` traces every op
# under ``jax.named_scope("<phase>/<op type>")``, so each instruction of the
# compiled step carries its Fluid op in its ``op_name`` metadata and a device
# trace can be summed by phase and by op (a fusion that spans two Fluid ops
# carries its root's, and is credited to that op). Sub-blocks nest: a reader
# takes the outermost pair. Trace time only; the instructions are unchanged.
#
# SCOPE_SCHEME names the scheme in the jitted step functions' names
# (``jit_step_<scheme>``). JAX's persistent compile cache does not see
# metadata, the module's name it does: without the tag a cache that holds
# executables compiled before the scopes existed would hand those back,
# scope-less, for ever. Change it only when the scheme below changes; every
# existing cache then compiles each program once more.
# ---------------------------------------------------------------------------

SCOPE_SCHEME = "ps1"
_GRAD_MARK = "@GRAD"            # fluid.framework.GRAD_SUFFIX


def _scheme_named(fn, base):
    fn.__name__ = f"{base}_{SCOPE_SCHEME}"
    return fn


def _op_phase(op):
    """``opt``: the op updates a parameter from its gradient (``Param`` and
    ``Grad`` input slots). ``bwd``: one of its outputs is a gradient
    variable, which also takes the ``sum`` / ``fill_constant`` /
    ``fill_zeros_like`` ops ``append_backward`` inserts and the clip and
    regulariser ops that rewrite gradients. ``fwd``: the rest."""
    if op.inputs.get("Param") and op.inputs.get("Grad"):
        return "opt"
    if any(_GRAD_MARK in n for n in op.output_arg_names()):
        return "bwd"
    return "fwd"


def _op_scope(op):
    """``<phase>/<op type>``, and ``/<module>`` after it for an op a
    ``fluid.name_scope`` tagged (its ``op_namescope``): inside the op's own
    pair, so whatever reads the outermost pair reads what it read."""
    tag = op.attrs.get("op_namescope")
    scope = f"{_op_phase(op)}/{op.type}"
    return f"{scope}/{tag}" if tag else scope


class _ProgramAnalysis:
    """Cached per-(program, version) block-walk results: the free-read and
    written name lists plus the persistable subset of the writes. Computing
    these walks every ``Executor.run`` made the steady-state dispatch path
    re-traverse the whole block graph per step; with the cache a hot run()
    does dict lookups only (the reference caches the analog Prepare work in
    its ExecutorPrepareContext, framework/executor.cc:271)."""

    __slots__ = ("version", "free", "written", "persistable_written",
                 "verified", "op_inventory", "_op_metric_children",
                 "_op_scopes")

    def __init__(self, version, free, written, persistable_written,
                 op_inventory=()):
        self.version = version
        self.free = free
        self.written = written
        self.persistable_written = persistable_written
        # block-0 op-type inventory ((op_type, count), ...): what a jit
        # step dispatches per run. obs_op_metrics rides this instead of
        # re-walking the block — registry children resolve lazily ONCE
        # per analysis and are cached here, so a metered steady-state
        # run() pays len(inventory) counter incs, no dict walks.
        self.op_inventory = op_inventory
        self._op_metric_children = None
        # executor_verify memo: the (feed names, fetch names) surfaces the
        # program at THIS version has passed verify_program under.
        # Fetch-clobber (PTL010) depends on the fetch set, so each distinct
        # surface verifies once; the steady-state hot path pays one set
        # lookup, and a version bump rebuilds the analysis and re-verifies.
        self.verified = set()
        self._op_scopes = {}

    def op_scopes(self, block):
        """``"<phase>/<op type>"`` of each op of ``block`` (any block of the
        program), decided from the op itself once per program version."""
        names = self._op_scopes.get(block.idx)
        if names is None or len(names) != len(block.ops):
            names = self._op_scopes[block.idx] = tuple(
                _op_scope(op) for op in block.ops)
        return names


# program -> _ProgramAnalysis for block 0. Keyed by the program OBJECT via
# weakref (with the version stored inside and revalidated on lookup): the
# same identity contract as an (id(program), _version) key, minus the
# id-reuse hazard after a program is garbage collected.
_ANALYSIS_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _analyze_program(program):
    cached = _ANALYSIS_CACHE.get(program)
    if cached is not None and cached.version == program._version:
        return cached
    from . import block_walk
    free = block_walk.free_reads(program, 0)
    written = block_walk.written_names(program, 0)
    block = program.global_block()
    persistable = frozenset(
        n for n in written if block.has_var(n) and block.var(n).persistable)
    inventory: dict = {}
    for op in block.ops:
        inventory[op.type] = inventory.get(op.type, 0) + 1
    cached = _ProgramAnalysis(program._version, free, written, persistable,
                              tuple(sorted(inventory.items())))
    _ANALYSIS_CACHE[program] = cached
    return cached


def _note_jit_ops(analysis):
    """obs_op_metrics, jit path: count each block-0 op once for this step
    from the cached inventory (children resolved once per analysis)."""
    children = analysis._op_metric_children
    if children is None:
        children = analysis._op_metric_children = tuple(
            (_op_children(t)[0], n) for t, n in analysis.op_inventory)
    for child, n in children:
        child.inc(n)


def _maybe_verify(program, analysis, feed_names, fetch_names=(), scope=None):
    """executor_verify flag: verify once per (program version, feed/fetch
    surface) through the analysis cache — zero steady-state cost (one set
    lookup, no verifier run). Scope-bound free reads (reader vars,
    tensor-array arenas seeded via ``scope.set``) are dataflow roots just
    like feeds: the executor binds them at dispatch, so a program that
    legitimately reads them must not be rejected as use-before-def. (The
    memo keys on the feed/fetch surface, not the scope contents — a name
    that LEAVES the scope between runs keeps the first run's verdict until
    the program version bumps.) Raises the typed ProgramVerifyError naming
    the executor as the rejecting stage."""
    from .flags import get_flag
    verified = analysis.verified
    # default (flag off, nothing memoized): one attr read + one flag lookup,
    # no frozenset construction on the hot path
    if not verified and not get_flag("executor_verify"):
        return
    key = (frozenset(feed_names), frozenset(fetch_names))
    if key in verified:
        return
    if not get_flag("executor_verify"):
        return
    roots = set(feed_names)
    if scope is not None:
        roots.update(n for n in analysis.free if scope.has_var(n))
    from ..fluid.analysis import verify_program
    verify_program(program, feed_names=roots, fetch_names=fetch_names,
                   pass_name="executor")
    verified.add(key)


def _collect_free_inputs(program, block_idx):
    """Names a block (and its sub-blocks) reads before writing — the state +
    feed surface of the compiled function. Mirrors what the reference resolves
    dynamically through Scope parent lookup (executor.cc:286-315). Block 0
    (every run()/prepare_steps call) hits the _ProgramAnalysis cache."""
    if block_idx == 0:
        return _analyze_program(program).free
    from .block_walk import free_reads
    return free_reads(program, block_idx)


def _written_names(program, block_idx):
    if block_idx == 0:
        return _analyze_program(program).written
    from .block_walk import written_names
    return written_names(program, block_idx)


# the flag-tuple portion of the jit-cache key: revalidated against the flag
# registry's version counter so a steady-state run() costs one compare, not
# a registry lookup per flag per dispatch
_JIT_KEY_FLAGS = ("xla_compiler_options", "bn_fusion_barrier",
                  "bn_fusion_barrier_fwd", "bn_fusion_barrier_bwd",
                  "conv_space_to_depth", "conv_1x1_grad_as_dot",
                  "kernel_tier")

_JIT_FLAG_KEY = (None, ())


def _jit_flag_key():
    global _JIT_FLAG_KEY
    from .flags import flags_version, get_flag
    v = flags_version()
    if _JIT_FLAG_KEY[0] != v:
        _JIT_FLAG_KEY = (v, tuple(get_flag(n) for n in _JIT_KEY_FLAGS))
    return _JIT_FLAG_KEY[1]


def _compiler_options():
    """Backend compiler options from the flags registry (the env-route
    XLA_FLAGS parser rejects TPU-only flag names client-side; the
    compiler_options channel reaches the backend compiler)."""
    from .flags import get_flag
    s = get_flag("xla_compiler_options")
    if not s:
        return None
    return dict(kv.split("=", 1) for kv in s.split(",") if "=" in kv)


def tpu_jit(fn, auto_state_layout=False, **jit_kwargs):
    """jax.jit with the flag-registry compiler options applied — the ONE
    jit wrapper every compiled path (Executor, run_steps, sharded step)
    goes through, so the xla_compiler_options flag reaches them all.

    auto_state_layout lets XLA pick the entry layout of the first argument
    (the persistent state dict) instead of forcing row-major at the jit
    boundary. Parameters then live in the scope in their compute-preferred
    layout (e.g. conv filters pre-transposed for the MXU), which removes the
    per-step relayout copies the default boundary forces (~8 GB/step of
    weight copies on the ResNet-50 flagship, measured via tools/
    hlo_report.py). Feeds keep the default layout so pre-staged input
    buffers never relayout. First call with row-major state pays a one-time
    transpose; every subsequent step reuses the returned arrays unchanged
    (donation aliases input/output so the layouts agree)."""
    if auto_state_layout:
        from jax.experimental.layout import Format, Layout
        auto = Format(Layout.AUTO)
        jit_kwargs.setdefault("in_shardings", (auto, None))
        jit_kwargs.setdefault("out_shardings", (auto, None))
    return jax.jit(fn, compiler_options=_compiler_options(), **jit_kwargs)


def _is_traceable(v):
    from .sparse import SparseRows
    return isinstance(v, (jax.Array, np.ndarray, LoDArray, SparseRows, int,
                          float, np.number))


def _feed_shapes(feeds):
    """Small identity summary of a feed dict for CompileRecords (computed
    only when a compile was detected — never on the steady-state path)."""
    out = {}
    for k, v in feeds.items():
        s = getattr(v, "shape", None)
        out[k] = list(s) if s is not None else type(v).__name__
    return out


class _InstrumentedFn:
    """Compiled-fn wrapper (obs.perf compile telemetry): detects
    executable builds by probing the jit trace-cache size around each
    dispatch (~0.02 us — per-bucket internal retraces of ONE jitted fn
    are each attributed, which the build-time retrace counter cannot
    see) and lands every build as a ``paddle_tpu_compile_seconds``
    observation + CompileRecord + ``compile`` flight event, labeled by
    the active ``obs.perf.compile_site`` (engines set theirs) or this
    wrapper's default kind. While the call is under way the thread's
    ``obs.perf`` build state names it as the owner, so the stage seconds
    JAX reports (trace, lower, XLA compile, cache load) go to the build
    found after it and not to the ``eager`` site. With the layer off
    (``obs_compile_log`` 0 — NOT in ``_JIT_KEY_FLAGS``, flipping never
    retraces) a dispatch pays one flag lookup."""

    __slots__ = ("_fn", "_kind", "_version", "_n_ops", "_n_fetch")

    def __init__(self, fn, kind, program, n_fetch):
        self._fn = fn
        self._kind = kind
        self._version = program._version
        self._n_ops = len(program.global_block().ops)
        self._n_fetch = n_fetch

    def __call__(self, state, feeds, *rest):
        # *rest carries the optional donated-feed dict (KV-arena
        # donation, _compiled(donate_feed_names=...)) through untouched
        if not _perf.enabled():
            return self._fn(state, feeds, *rest)
        try:
            before = self._fn._cache_size()
        except Exception:
            before = None
        t0 = _perf_counter()
        with _perf.building():
            out = self._fn(state, feeds, *rest)
        if before is not None:
            try:
                grew = self._fn._cache_size() > before
            except Exception:
                grew = False
            if grew:
                dt = _perf_counter() - t0
                site, detail = _perf.current_site(default=self._kind)
                identity = dict(detail)
                identity.setdefault("program_version", self._version)
                identity["n_ops"] = self._n_ops
                identity["n_fetch"] = self._n_fetch
                identity["feeds"] = _feed_shapes(feeds)
                _perf.note_compile(site, dt, identity=identity)
        return out

    def lower(self, *args, **kwargs):
        # AOT entry (obs.perf.lower_program, tools/hlo_report.py)
        return self._fn.lower(*args, **kwargs)

    @property
    def traceable(self):
        # the jitted step itself (obs.perf.program_jaxpr)
        return self._fn


class Executor:
    """User-facing executor (reference python/paddle/fluid/executor.py Executor).

    mode="jit"   : compile the block to one XLA computation (TPU path)
    mode="eager" : op-at-a-time interpreter (debug / OpTest path)
    """

    def __init__(self, place=None, mode="jit", donate=False, amp=False,
                 auto_layout=False):
        self.place = place
        self.device = _resolve_device(place)
        self.mode = mode
        self.donate = donate
        # AMP: bf16 compute with fp32 master weights (core/amp.py). The flag
        # is applied around tracing/execution so op lowerings autocast.
        self.amp = amp
        # auto_layout: XLA picks the persistent-state entry layout (see
        # tpu_jit). Scope arrays then carry compute-preferred layouts.
        self.auto_layout = auto_layout
        self._cache = {}
        self._step_num = 0      # the step spans' step_num

    # ------------------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True, donate_feeds=()):
        """Run ``program`` once. The call is one step span, ``executor.run``,
        whose children (``executor.feed`` / ``state`` / ``lookup`` /
        ``enqueue`` / ``writeback``) lie in a ``jax.profiler`` trace while
        one is taken; the same statements run with or without one. It
        returns when the step is enqueued, not when the device is done."""
        from ..fluid.framework import default_main_program
        from .flags import get_flag

        self._step_num += 1
        with record_event("executor.run", kind="stage",
                          step_num=self._step_num):
            program = program or default_main_program()
            feed = dict(feed or {})
            fetch_list = list(fetch_list or [])
            scope = scope or global_scope()
            fetch_names = [f if isinstance(f, str) else f.name
                           for f in fetch_list]
            block = program.global_block()
            jit = self.mode != "eager" and use_program_cache

            with record_event("executor.feed", kind="stage"):
                feed_vals = self._prepare_feed(block, feed)

            with record_event("executor.state", kind="stage"):
                if scope.find_var(_RNG_KEY) is None:
                    scope.set(_RNG_KEY,
                              jax.random.PRNGKey(program.random_seed or 0))
                # steady-state hot path: every per-program set below comes
                # from the _ProgramAnalysis cache — no block walk after the
                # first run. (A free name with no runtime value anywhere is
                # produced by an earlier op, e.g. a fill; if an op truly
                # reads it first, _run_ops raises a clean error.)
                analysis = _analyze_program(program)
                _maybe_verify(program, analysis, tuple(feed_vals),
                              tuple(fetch_names), scope=scope)
                if get_flag("obs_op_metrics"):
                    # jit: per-step op-type counts from the cached inventory
                    # (eager dispatches are timed per op inside _run_ops)
                    _M_STEPS.labels(mode=self.mode).inc()
                    if jit:
                        _note_jit_ops(analysis)
                state_in = [n for n in analysis.free
                            if n not in feed_vals and scope.has_var(n)]
                state_out = [n for n in analysis.written
                             if n in analysis.persistable_written
                             or scope.has_var(n)]
                state = {n: scope.find_var(n) for n in state_in}
                state[_RNG_KEY] = scope.find_var(_RNG_KEY)
                if jit:
                    # donated feeds (KV-arena donation) split into a third
                    # jit argument AFTER the analysis above saw them as
                    # feeds; eager dispatch ignores the split (no buffers to
                    # alias there)
                    donated = {n: feed_vals.pop(n) for n in donate_feeds
                               if n in feed_vals} if donate_feeds else {}
                    # non-traceable state (readers, rank tables) can't
                    # cross jit
                    state = {k: v for k, v in state.items()
                             if _is_traceable(v)}
                    if self.place is not None:
                        # explicit place: commit state so jit follows the
                        # operands
                        state = {k: jax.device_put(v, self.device)
                                 for k, v in state.items()}

            if not jit:
                env = dict(state)
                env.update(feed_vals)
                with amp_guard(self.amp):
                    _run_ops(block, env, self)
                new_state = {n: env[n] for n in state_out if n in env}
                new_state[_RNG_KEY] = env[_RNG_KEY]
                fetches = [env[n] for n in fetch_names]
            else:
                with record_event("executor.lookup", kind="stage"):
                    fn = self._compiled(program, tuple(sorted(feed_vals)),
                                        tuple(fetch_names), tuple(state_in),
                                        tuple(state_out),
                                        tuple(sorted(donated)))
                args = (state, feed_vals) + ((donated,) if donated else ())
                check = get_flag("check_nan_inf")
                # amp guard wraps dispatch because jax traces lazily (first
                # call and any shape-driven retrace happen inside fn())
                with record_event("executor.enqueue", kind="stage"), \
                        amp_guard(self.amp):
                    if check:
                        # the jit analog of the eager per-op sweep: jax
                        # re-runs the computation op-by-op and points at the
                        # offending primitive (reference --check_nan_inf
                        # covers BOTH NaN and Inf, hence debug_infs too)
                        with jax.debug_nans(True), jax.debug_infs(True):
                            new_state, fetches = fn(*args)
                            jax.block_until_ready(fetches)
                    else:
                        new_state, fetches = fn(*args)

            with record_event("executor.writeback", kind="stage"):
                for n, v in new_state.items():
                    scope.set(n, v)
                return [self._fetch_value(v, return_numpy) for v in fetches]

    # ------------------------------------------------------------------
    def prepare_steps(self, program=None, feeds=(), fetch_list=None,
                      scope=None, steps=None):
        """Stage a K-step scanned train loop: stack the feeds on device and
        bind the compiled scan — the analog of the reference's
        Executor::Prepare (framework/executor.cc:271), which splits the
        per-run setup from the hot RunPreparedContext loop. The returned
        handle is dispatched with :meth:`run_prepared`; feeds are transferred
        ONCE here, so repeated dispatches (epochs over the same staged data,
        benchmark loops, remote-attachment links where every host->device
        transfer costs a round trip) pay only the dispatch."""
        from ..fluid.framework import default_main_program

        program = program or default_main_program()
        feeds = list(feeds)
        if not feeds:
            raise ValueError("prepare_steps needs at least one feed dict")
        K = int(steps or len(feeds))
        scope = scope or global_scope()
        fetch_list = list(fetch_list or [])
        fetch_names = [f if isinstance(f, str) else f.name for f in fetch_list]

        block = program.global_block()
        prepared = [self._prepare_feed(block, dict(f)) for f in feeds]

        # per-leaf stacking so structured feeds (LoDArray: data + lens pytree)
        # ride the scan too — each leaf gains a leading [n_feeds] axis. Host
        # leaves stack on host first so the device_put below is ONE transfer
        # per leaf (n_feeds separate transfers cost a round trip each on
        # remote attachments); already-device leaves stack device-side.
        def _stack(*xs):
            if all(isinstance(x, np.ndarray) for x in xs):
                return np.stack(xs)
            return jnp.stack([jnp.asarray(x) for x in xs])

        stacked = {k: jax.tree_util.tree_map(_stack, *(p[k] for p in prepared))
                   for k in prepared[0]}
        stacked = jax.device_put(stacked)

        if scope.find_var(_RNG_KEY) is None:
            scope.set(_RNG_KEY, jax.random.PRNGKey(program.random_seed or 0))

        analysis = _analyze_program(program)
        _maybe_verify(program, analysis, tuple(stacked), tuple(fetch_names),
                      scope=scope)
        feed_keys = set(stacked)
        state_in = [n for n in analysis.free
                    if n not in feed_keys and scope.has_var(n)]
        state_out = [n for n in analysis.written
                     if n in analysis.persistable_written or scope.has_var(n)]
        # scan carry must have a fixed structure: carry everything read or
        # persistently written (all present in scope after startup ran)
        carry = list(dict.fromkeys(state_in + [n for n in state_out
                                               if scope.has_var(n)]))
        state = {n: scope.find_var(n) for n in carry}
        state[_RNG_KEY] = scope.find_var(_RNG_KEY)
        carry_keys = tuple(sorted(
            k for k, v in state.items() if _is_traceable(v)))

        fn = self._compiled_steps(program, tuple(sorted(stacked)),
                                  tuple(fetch_names), carry_keys,
                                  K, len(prepared))
        return _PreparedSteps(fn, stacked, carry_keys, scope)

    def run_prepared(self, prepared, return_numpy=True):
        """Dispatch a handle from :meth:`prepare_steps` once: reads the
        current carry state from the scope, runs the K-step scan, writes the
        new state back, and returns the per-step stacked fetches — the
        reference's RunPreparedContext (executor.cc:296)."""
        from .flags import get_flag

        self._step_num += 1
        with record_event("executor.run_prepared", kind="stage",
                          step_num=self._step_num):
            scope = prepared.scope
            state = {n: scope.find_var(n) for n in prepared.carry_keys}
            check = get_flag("check_nan_inf")
            with record_event("executor.enqueue", kind="stage"), \
                    amp_guard(self.amp):
                if check:
                    with jax.debug_nans(True), jax.debug_infs(True):
                        new_state, fetches = prepared.fn(state,
                                                         prepared.stacked)
                        jax.block_until_ready(fetches)
                else:
                    new_state, fetches = prepared.fn(state, prepared.stacked)
            with record_event("executor.writeback", kind="stage"):
                for n, v in new_state.items():
                    scope.set(n, v)
                return [np.asarray(v) if return_numpy else v
                        for v in fetches]

    def run_steps(self, program=None, feeds=(), fetch_list=None, scope=None,
                  steps=None, return_numpy=True):
        """Run ``steps`` training steps as ONE XLA computation (lax.scan over
        the step body), cycling through ``feeds`` (a list of feed dicts with
        identical shapes). Returns per-step fetch values stacked on axis 0.

        TPU-native extension with no reference analog: the reference's
        executor pays a kernel-launch loop per op per step; here even the
        per-*step* dispatch cost (host→device latency, nontrivial through
        remote TPU attachments) amortizes across the scan. Parameters and
        optimizer state thread through the scan carry, so the whole K-step
        train loop is device-resident. prepare_steps/run_prepared expose the
        stage-once/dispatch-many split when the same feeds run repeatedly.
        """
        prepared = self.prepare_steps(program, feeds, fetch_list, scope,
                                      steps)
        return self.run_prepared(prepared, return_numpy=return_numpy)

    def _compiled_steps(self, program, feed_names, fetch_names, carry_keys,
                        K, B):
        key = ("multi", id(program), program._version, feed_names,
               fetch_names, carry_keys, K, B, self.donate, self.amp,
               _jit_flag_key())
        fn = self._cache.get(key)
        if fn is not None:
            return fn
        _M_RETRACES.labels(kind="jit_scan").inc()

        block = program.global_block()
        exec_state = self

        def multi(state, stacked):
            idx = jnp.arange(K, dtype=jnp.int32) % B

            def body(st, i):
                env = dict(st)
                for k, v in stacked.items():
                    env[k] = jax.tree_util.tree_map(
                        lambda leaf: jax.lax.dynamic_index_in_dim(
                            leaf, i, axis=0, keepdims=False), v)
                exec_state._tracing = True
                try:
                    _run_ops(block, env, exec_state)
                finally:
                    exec_state._tracing = False
                new_st = {n: env.get(n, st[n]) for n in carry_keys}
                new_st[_RNG_KEY] = env[_RNG_KEY]
                fetches = [env[n] for n in fetch_names]
                return new_st, fetches

            return jax.lax.scan(body, state, idx)

        donate = (0,) if self.donate else ()
        fn = _InstrumentedFn(
            tpu_jit(_scheme_named(multi, "multi"), donate_argnums=donate),
            "jit_scan", program, len(fetch_names))
        self._cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _compiled(self, program, feed_names, fetch_names, state_in, state_out,
                  donate_feed_names=()):
        key = (id(program), program._version, feed_names, fetch_names,
               state_in, state_out, donate_feed_names, self.donate, self.amp,
               self.auto_layout, _jit_flag_key())
        fn = self._cache.get(key)
        if fn is not None:
            return fn
        _M_RETRACES.labels(kind="jit_step").inc()

        block = program.global_block()

        def _step_body(state, env):
            self._tracing = True
            try:
                _run_ops(block, env, self)
            finally:
                self._tracing = False
            new_state = {n: env[n] for n in state_out if n in env}
            # pass unwritten state through so that, under buffer donation,
            # the scope never retains a donated (deleted) input buffer
            for n in state:
                if n not in new_state:
                    new_state[n] = env[n]
            new_state[_RNG_KEY] = env[_RNG_KEY]
            fetches = [env[n] for n in fetch_names]
            return new_state, fetches

        if donate_feed_names:
            # donated feeds (the generation engine's KV arena) ride a
            # THIRD argument so donate_argnums can alias their buffers
            # into the matching fetches without donating regular feeds —
            # the functional arena update then stays on device instead
            # of allocating a fresh arena every dispatch
            def step(state, feeds, donated):
                env = dict(state)
                env.update(feeds)
                env.update(donated)
                return _step_body(state, env)

            donate = ((0,) if self.donate else ()) + (2,)
        else:
            def step(state, feeds):
                env = dict(state)
                env.update(feeds)
                return _step_body(state, env)

            donate = (0,) if self.donate else ()
        fn = _InstrumentedFn(
            tpu_jit(_scheme_named(step, "step"),
                    auto_state_layout=self.auto_layout,
                    donate_argnums=donate),
            "jit_step", program, len(fetch_names))
        self._cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _prepare_feed(self, block, feed):
        out = {}

        def place_lod(v):
            return jax.device_put(v, self.device) if self.place is not None \
                else v

        for name, value in feed.items():
            if isinstance(value, jax.Array):
                # already device-resident (pre-staged / double-buffered feed):
                # never round-trip through the host
                out[name] = value
                continue
            if isinstance(value, LoDArray):
                out[name] = place_lod(value)
                continue
            if isinstance(value, tuple) and len(value) == 2 and not np.isscalar(value[0]):
                # reference feed form: (flat ndarray, lod offsets)
                out[name] = place_lod(flat_to_lodarray(value[0], value[1]))
                continue
            if isinstance(value, list) and value and isinstance(
                    value[0], (np.ndarray, list)):
                v = block.var(name) if block.has_var(name) else None
                if (v is not None and v.lod_level >= 2
                        and isinstance(value[0], list)):
                    # nested python lists to arbitrary depth (reference
                    # create_lod_tensor's recursive_seq_lens form,
                    # lod_tensor.h:55 N-level LoD): peel exactly the declared
                    # outer levels (lod_level - 1), so empty outer groups
                    # pack as zero-length entries instead of stopping the
                    # peel
                    levels, cur = [], value
                    for _ in range(v.lod_level - 1):
                        if not all(isinstance(g, list) for g in cur):
                            break
                        levels.append(np.asarray([len(g) for g in cur],
                                                 np.int32))
                        cur = [s for g in cur for s in g]
                    arr = pack_sequences([np.asarray(s) for s in cur])
                    if levels:
                        arr.outer_lens = tuple(levels)
                    out[name] = place_lod(arr)
                    continue
                if v is not None and v.lod_level > 0:
                    out[name] = place_lod(
                        pack_sequences([np.asarray(s) for s in value]))
                    continue
            arr = np.asarray(value)
            if block.has_var(name):
                v = block.var(name)
                if v.dtype is not None and arr.dtype != np_dtype(v.dtype):
                    arr = arr.astype(np_dtype(v.dtype))
            if self.place is not None:
                out[name] = jax.device_put(arr, self.device)
            else:
                out[name] = jnp.asarray(arr)
        return out

    @staticmethod
    def _fetch_value(v, return_numpy):
        from .sparse import SparseRows
        if isinstance(v, (LoDArray, SparseRows)):
            return v  # caller unpacks (core.lod.lodarray_to_flat / .to_dense)
        if return_numpy:
            return np.asarray(v)
        return v


__all__ = ["Executor", "CPUPlace", "TPUPlace", "Scope", "global_scope"]
