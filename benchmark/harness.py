"""The benchmark's harness: finds a cell's files by the names in
``BENCHMARK.json``, sets the cell up, measures one window, checks that the
outputs are correct, and builds the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in files of its own (``configs/<config>/``,
``traffic/<traffic>.json`` + ``generators/<kind>.py``,
``layer_metrics/<metric>.json`` + ``readers/<kind>.py``); this module names
none of them. From the program it takes only what a user calls and the
counters ``PERF.md`` lists.
"""

import importlib.util
import json
import os
import sys
import time
from types import SimpleNamespace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

# host spans the window loop records (on the profiler's clock too, as
# TraceAnnotations, so that idle gaps of the device can be laid at them)
SPAN_FEED, SPAN_CALL, SPAN_WAIT = "feed_next", "step_call", "loss_wait"
# a traced run records the profiler from TRACE_AFTER_S into the window until
# it holds TRACE_MIN_STEPS steps and TRACE_MIN_S seconds, TRACE_MAX_S at most
TRACE_AFTER_S, TRACE_MIN_STEPS, TRACE_MIN_S, TRACE_MAX_S = 1.0, 30, 0.5, 4.0
SEED_MOD = 2 ** 31 - 1          # --seed may pass 2**31; a PRNG key may not


class CellError(Exception):
    """The cell cannot be set up or run as its files describe it."""


# ------------------------------------------------------------ finding files
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """Import one of the benchmark's own modules by its path."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise CellError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest(path=MANIFEST):
    return load_json(path)


def load_cell(manifest, workload, cfg_override=None, traffic_override=None):
    """Everything the manifest's entry ``workload`` names, loaded. The
    overrides shrink a cell for the CPU rehearsal tests; run.py passes
    none."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(known: {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg_path = os.path.join(ROOT, config["file"])
    cfg = load_json(cfg_path)
    cfg.update(cfg_override or {})
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    traffic.update(traffic_override or {})
    model = load_module(os.path.join(os.path.dirname(cfg_path), "model.py"),
                        f"benchmark_config_{cell['config']}")
    generator = load_module(
        os.path.join(BENCH_DIR, "generators", traffic["generator"] + ".py"),
        f"benchmark_generator_{traffic['generator']}")
    return SimpleNamespace(name=workload, chips=int(cell["chips"]), cfg=cfg,
                           traffic=traffic, model=model, generator=generator)


def metrics_of(manifest, workload, group):
    """The manifest's metrics of ``group`` that ``workload`` reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_peaks(device_kind, path=os.path.join(BENCH_DIR, "peaks.json")):
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise CellError(f"no peaks for device_kind {device_kind!r} in "
                        f"{path} (known: {sorted(table)})")
    return table[device_kind]


# ------------------------------------------------------------------- spans
class Spans:
    """Host spans kept in memory: name -> list of seconds. Each is also a
    ``TraceAnnotation`` (made anew at every entry: it starts when it is
    made), which costs nothing while no trace is taken."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._annotate = TraceAnnotation
        self.seconds = {}

    def span(self, name):
        return _Span(self, name)


class _Span:
    __slots__ = ("_owner", "_name", "_ann", "_t0")

    def __init__(self, owner, name):
        self._owner, self._name = owner, name

    def __enter__(self):
        self._ann = self._owner._annotate(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._owner.seconds.setdefault(self._name, []).append(dt)
        return False


# ------------------------------------------------------ the program's side
def make_context(cell, seed):
    """What a generator kind is opened with: the cell's configuration, model
    module and traffic, the seed folded into a PRNG key's range, the span
    recorder, and ``start_program`` to build and start the program."""
    ctx = SimpleNamespace(cfg=cell.cfg, model=cell.model,
                          traffic=cell.traffic, chips=cell.chips,
                          seed=int(seed) % SEED_MOD, spans=Spans())
    ctx.start_program = lambda: start_program(ctx)
    return ctx


def start_program(ctx):
    """Build the configuration's program and run its start-up program on a
    fresh scope, as a user does. Returns the pieces a generator drives."""
    import jax

    import paddle_tpu.fluid as fluid

    with ctx.spans.span("build"):
        main, startup, loss, probe = ctx.model.build(ctx.cfg)
    startup.random_seed = main.random_seed = ctx.seed
    ex = ctx.cfg["executor"]
    exe = fluid.Executor(mode=ex["mode"], donate=ex["donate"], amp=ex["amp"])
    scope = fluid.Scope()
    with ctx.spans.span("startup_program"):
        exe.run(startup, scope=scope)
        params = [p.name for p in main.global_block().all_parameters()]
        jax.block_until_ready([scope.find_var(n) for n in params])
    return SimpleNamespace(main=main, startup=startup, loss=loss, probe=probe,
                           exe=exe, scope=scope, params=params)


def snapshot_weights(prog):
    """The parameters as the start-up program left them, on the host, in
    creation order — what the plain reference computes with."""
    import numpy as np
    return [np.asarray(prog.scope.find_var(n)) for n in prog.params]


def program_counters(cache_stats):
    """The program's own counts, read as they are."""
    from paddle_tpu.obs.metrics import REGISTRY
    from paddle_tpu.obs.perf import COMPILE_LOG

    out = dict(REGISTRY.totals())
    out["compile_log_count"] = COMPILE_LOG.stats()["count"]
    out["compile_cache_hits"] = cache_stats.hits
    out["compile_cache_misses"] = cache_stats.misses
    return out


def pallas_report():
    from paddle_tpu.ops.pallas import (AUTO_PALLAS, dispatch_counts,
                                       fallback_counts)
    return {"auto_pallas": sorted(AUTO_PALLAS),
            "dispatches": dispatch_counts(), "fallbacks": fallback_counts()}


# ------------------------------------------------------------- correctness
def check_reference(cell, weights, feed, system_loss, system_probe=None):
    """(a) the first step's outputs, before any update, against the plain
    reference on the same weights: the loss and, where the configuration
    names a probe (per-sample outputs that carry the network's signal where
    the mean loss does not), the probe against each of the reference's
    versions of it, by the largest error over the reference's largest
    magnitude, each within its tolerance in the configuration's file."""
    import numpy as np

    ref_loss, ref_probes = cell.model.run_reference(cell.cfg, weights, feed)
    spec = cell.cfg["reference"]
    err = abs(system_loss - ref_loss) / max(abs(ref_loss), 1e-12)
    out = {"ok": bool(err <= spec["rel_tolerance"]), "system": system_loss,
           "reference": ref_loss, "rel_err": err,
           "rel_tolerance": spec["rel_tolerance"]}
    if ref_probes and system_probe is None:
        raise CellError(f"the configuration compares {sorted(ref_probes)} "
                        "and the generator kind's check_step gave no probe")
    for name, ref in (ref_probes or {}).items():
        ref = np.asarray(ref, np.float64)
        diff = np.asarray(system_probe, np.float64).reshape(ref.shape) - ref
        scale = float(np.max(np.abs(ref)))
        perr = float(np.max(np.abs(diff))) / scale if scale else float("inf")
        tol = spec["probe_rel_tolerance"][name]
        out[name] = {"rel_err": perr, "rel_tolerance": tol,
                     "largest_reference": scale}
        out["ok"] = bool(out["ok"] and perr <= tol)
    return out


def check_losses(losses, failed):
    """(b) every loss finite, and training made progress: the mean of the
    last tenth of the window's losses is below the mean of the first
    tenth."""
    import numpy as np

    tenths = [float(np.mean(t)) for t in
              np.array_split(np.asarray(losses, np.float64),
                             min(10, max(1, len(losses))))]
    return {"finite": failed == 0, "tenths": tenths,
            "ok": bool(failed == 0 and len(tenths) > 1
                       and tenths[-1] < tenths[0])}


def check_counters(before, after):
    """(c) nothing compiled inside the window."""
    names = ("compile_log_count", "paddle_tpu_executor_retraces",
             "compile_cache_hits", "compile_cache_misses")
    delta = {n: after.get(n, 0) - before.get(n, 0) for n in names}
    return {"ok": not any(delta.values()), "delta": delta}


def check_pallas(report):
    """(d) no Pallas kernel ran through the interpreter."""
    bad = {k: c["interpret"] for k, c in report["dispatches"].items()
           if c.get("interpret")}
    return {"ok": not bad, "interpreted": bad}


# ---------------------------------------------------------------- the window
def measure(session, spans, seconds, tracer=None):
    """Run synchronous steps for ``seconds``. Each step: take the next feed,
    call into the step, wait until its loss is ready. Returns per-step
    records; the losses are read back after the window."""
    import jax

    feeds = session.feeds()
    step_s, infos, losses = [], [], []
    raised = 0
    sp_feed, sp_call, sp_wait = (spans.span(n) for n in
                                 (SPAN_FEED, SPAN_CALL, SPAN_WAIT))
    clock = time.perf_counter
    t_begin = clock()
    deadline = t_begin + seconds
    t_end = t_begin
    while t_end < deadline:
        if tracer is not None:          # its own time is not the window's
            tracer.tick(t_end - t_begin, len(step_s))
            took = clock() - t_end
            t_begin, deadline = t_begin + took, deadline + took
        with sp_feed:
            feed, info = next(feeds)
        t0 = clock()
        try:
            with sp_call:
                loss = session.step(feed)
            with sp_wait:
                jax.block_until_ready(loss)
        except Exception as e:                    # a failed step is counted
            raised += 1
            print(f"step raised: {type(e).__name__}: {e}", file=sys.stderr)
            loss = None
        t_end = clock()
        step_s.append(t_end - t0)
        infos.append(info)
        losses.append(loss)
    if tracer is not None:
        tracer.finish(len(step_s))
    return SimpleNamespace(step_s=step_s, infos=infos, losses=losses,
                           raised=raised, elapsed=t_end - t_begin)


class Tracer:
    """Takes the profiler's trace of a part of the window (see TRACE_*)."""

    def __init__(self, directory):
        self.directory = directory
        self.state = "waiting"
        self.first_step = self.last_step = 0
        self.t_start = self.t_stop = 0.0

    def tick(self, t, n_steps):
        import jax
        if self.state == "waiting" and t >= TRACE_AFTER_S:
            jax.profiler.start_trace(self.directory)
            self.state, self.first_step = "tracing", n_steps
            self.t_start = time.perf_counter()
        elif self.state == "tracing":
            dt = time.perf_counter() - self.t_start
            if (dt >= TRACE_MAX_S or
                    (dt >= TRACE_MIN_S
                     and n_steps - self.first_step >= TRACE_MIN_STEPS)):
                self.finish(n_steps)

    def finish(self, n_steps):
        import jax
        if self.state == "tracing":
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.state, self.last_step = "done", n_steps

    @property
    def steps(self):
        return range(self.first_step, self.last_step)


def percentile(values, q):
    """The q-th percentile, linear between order statistics."""
    xs = sorted(values)
    if not xs:
        raise CellError("no steps completed inside the window")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end_values(window, setup_s):
    samples = sum(i["samples"] for i, l in zip(window.infos, window.losses)
                  if l is not None)
    ms = [1e3 * s for s in window.step_s]
    return {"samples_per_s": samples / window.elapsed,
            "step_ms_p50": percentile(ms, 50),
            "step_ms_p95": percentile(ms, 95),
            "setup_s": setup_s}


# ------------------------------------------------------ per-layer metrics
def read_layer_metrics(manifest, workload, run, log=print):
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_of(manifest, workload, "per_layer"):
        spec = load_json(os.path.join(BENCH_DIR, "layer_metrics",
                                      m["name"] + ".json"))
        reader = load_module(
            os.path.join(BENCH_DIR, "readers", spec["reader"] + ".py"),
            f"benchmark_reader_{spec['reader']}")
        value = reader.read(spec.get("params", {}), run)
        if value is None:
            log(f"layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ device
def device_report(chips, log=print):
    """The device as JAX reports it. ``memory_peak_bytes`` is, on the fullest
    of the cell's chips, the allocator's ``peak_bytes_in_use`` (live buffers:
    state, feeds) plus ``peak_bytes_reserved``, the memory the runtime
    reserves for the running program's temporaries, which the first does not
    count (ResNet-50 at batch 256: 0.63 GB + 9.07 GB)."""
    import jax
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs[:chips]]
    log(f"memory_stats of device 0: {json.dumps(stats[0])}")
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


# ----------------------------------------------------------------- one run
def run_cell(manifest, workload, seed, seconds, trace, t_process_start,
             cfg_override=None, traffic_override=None, log=print,
             trace_dir=None):
    """Set one cell up, measure one window, check it, and return the result
    line's object. ``log`` gets everything that is not the result. The
    overrides and ``trace_dir`` are the CPU rehearsal tests' seam (a cell
    shrunk to a tiny size, its trace in a temporary directory); run.py
    passes none."""
    import jax
    import numpy as np

    from paddle_tpu.core import compile_cache

    from . import trace as trace_mod

    t_entered = time.perf_counter() - t_process_start
    cell = load_cell(manifest, workload, cfg_override, traffic_override)
    if len(jax.devices()) < cell.chips:
        raise CellError(f"{workload} asks for {cell.chips} chips, JAX found "
                        f"{len(jax.devices())}")
    cache_dir, cache_stats = compile_cache.enable()
    dev = jax.devices()[0]
    log(f"cell {workload}: seed {seed}, {seconds} s, trace {trace}; device "
        f"{dev.platform} {dev.device_kind} x{len(jax.devices())}; compile "
        f"cache {cache_dir}")

    ctx = make_context(cell, seed)
    spans = ctx.spans
    session = cell.generator.open_session(ctx)
    try:
        # the first step: on the check feed, from the start-up weights, so
        # its loss is the one the plain reference must reproduce
        weights = session.initial_weights
        feed, _ = session.check_feed
        with spans.span("first_step"):
            loss0, probe0 = jax.device_get(session.check_step(feed))
        with spans.span("reference"):
            checks = {"reference": check_reference(
                cell, weights, feed, float(np.reshape(loss0, ())), probe0)}
        del weights
        session.initial_weights = None
        with spans.span("warm_up"):
            for feed, _ in session.warm_feeds:
                for _ in range(2):
                    jax.block_until_ready(session.step(feed))
        placement = session.placement()
        if placement is not None:
            checks["placement"] = placement
        before = program_counters(cache_stats)
        setup_s = time.perf_counter() - t_process_start
        log(f"set-up {setup_s:.3f} s: imports and device {t_entered:.3f}, "
            + ", ".join(
            f"{k} {sum(v):.3f}" for k, v in spans.seconds.items()))
        log(f"compile cache at the end of set-up: hits {cache_stats.hits} "
            f"misses {cache_stats.misses}")

        tracer = None
        if trace:
            trace_dir = trace_dir or os.path.join(ROOT, ".bench_trace",
                                                  workload)
            tracer = Tracer(trace_mod.fresh_dir(trace_dir))
        window = measure(session, spans, seconds, tracer)
        after = program_counters(cache_stats)
    finally:
        session.close()

    losses = [float(x) for x in jax.device_get(
        [l for l in window.losses if l is not None])]
    failed = window.raised + sum(1 for x in losses if not np.isfinite(x))
    checks["losses"] = check_losses(losses, failed)
    checks["no_compile_in_window"] = check_counters(before, after)
    pallas = pallas_report()
    checks["pallas_native"] = check_pallas(pallas)
    correct = all(c["ok"] for c in checks.values())

    n = len(window.step_s)
    elements = sum(i["elements"] for i in window.infos)
    log(f"window {window.elapsed:.3f} s: {n} steps, "
        f"{sum(i['samples'] for i in window.infos)} samples, {elements} "
        f"elements ({elements / window.elapsed:.1f}/s; tokens where the "
        f"samples are sequences)")
    longest = sorted(range(n), key=lambda i: -window.step_s[i])[:3]
    median = percentile(window.step_s, 50)
    slow = [x for x in window.step_s if x > 1.5 * median]
    log(f"steps over 1.5x the median: {len(slow)} of {n}, "
        f"{sum(slow) - median * len(slow):.3f} s above it; steps take "
        f"{sum(window.step_s):.3f} s of the window's {window.elapsed:.3f}")
    log("longest steps (index: ms = call + wait, after a feed wait): "
        + "; ".join(
            f"{i}: {1e3 * window.step_s[i]:.2f} = "
            f"{1e3 * spans.seconds[SPAN_CALL][i]:.2f} + "
            f"{1e3 * spans.seconds[SPAN_WAIT][i]:.2f}, "
            f"{1e3 * spans.seconds[SPAN_FEED][i]:.2f}"
            for i in longest if i < len(spans.seconds.get(SPAN_WAIT, ()))))
    if losses:
        log(f"losses: first {losses[0]:.5f} last {losses[-1]:.5f} "
            f"min {min(losses):.5f} max {max(losses):.5f}")
    log(f"pallas: {json.dumps(pallas)}")
    log(f"checks: {json.dumps(checks)}")

    result = {"correct": bool(correct), "attempted": n, "failed": int(failed)}
    if not trace:
        values = end_to_end_values(window, setup_s)
        units = {m["name"]: m["unit"]
                 for m in metrics_of(manifest, workload, "end_to_end")}
        result["metrics"] = {k: {"value": float(values[k]), "unit": u}
                             for k, u in units.items()}
        result["device"] = device_report(cell.chips, log)
        return result

    reduced = None
    if tracer.state == "done" and len(tracer.steps):
        reduced = trace_mod.reduce_run(
            tracer.directory, (SPAN_FEED, SPAN_CALL, SPAN_WAIT), cell.chips)
    steps = list(tracer.steps)
    run = SimpleNamespace(
        spans=spans.seconds, counters={"setup_end": before, "window_end": after},
        trace=reduced, chips=cell.chips, peaks=load_peaks(dev.device_kind)
        if dev.platform != "cpu" else None,
        traced_steps=len(steps),
        traced_wall_s=tracer.t_stop - tracer.t_start,
        traced_flops=sum(window.infos[i]["flops"] for i in steps),
        notes=[])
    result["metrics"] = read_layer_metrics(manifest, workload, run, log)
    for note in run.notes:
        log(note)
    result["device"] = device_report(cell.chips, log)
    if reduced is not None:
        log(f"trace: {len(steps)} steps in {run.traced_wall_s:.3f} s; "
            f"planes {reduced['planes']}")
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    return result
