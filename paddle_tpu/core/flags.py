"""Global flags registry.

Reference: gflags defined beside their subsystems and re-exported to Python
via core.init_gflags(sys.argv) (/root/reference/paddle/fluid/platform/,
framework/init.cc:31, pybind.cc:423; the legacy ~40-flag registry
paddle/utils/Flags.h:19-43). Here one process-wide registry: subsystems
declare flags with DEFINE_flag, users set them via fluid.set_flags /
init_flags(argv) / the PDTPU_FLAGS env var ("a=1,b=2" at import time).
"""

from __future__ import annotations

import os

_FLAGS: dict[str, dict] = {}

# bumped on every mutation of the registry; lets callers that derive keys
# from flag values (the Executor's jit-cache flag tuple) cache the derived
# form and revalidate with one integer compare instead of N dict lookups
_FLAGS_VERSION = 0


def flags_version():
    """Monotonic counter of registry mutations (DEFINE_flag / set_flags)."""
    return _FLAGS_VERSION


def _bump_version():
    global _FLAGS_VERSION
    _FLAGS_VERSION += 1


def DEFINE_flag(name, default, help_str=""):
    if name not in _FLAGS:
        _FLAGS[name] = {"value": default, "default": default,
                        "help": help_str, "type": type(default)}
        _bump_version()
    return _FLAGS[name]["value"]


def get_flag(name):
    return _FLAGS[name]["value"]


def set_flags(flags: dict):
    """fluid.set_flags({'check_nan_inf': True}) — unknown flags raise, like
    gflags' unknown-flag error."""
    for name, value in flags.items():
        if name not in _FLAGS:
            raise KeyError(f"unknown flag {name!r}; known: {sorted(_FLAGS)}")
        ty = _FLAGS[name]["type"]
        if ty is bool and isinstance(value, str):
            value = value.lower() in ("1", "true", "yes", "on")
        _FLAGS[name]["value"] = ty(value)
        _bump_version()


def flags():
    """Snapshot of all flags (name -> value)."""
    return {n: f["value"] for n, f in _FLAGS.items()}


def init_flags(argv):
    """Parse --name=value entries (the reference's core.init_gflags(argv)
    contract); returns unconsumed argv entries."""
    rest = []
    for a in argv:
        if a.startswith("--") and "=" in a:
            name, value = a[2:].split("=", 1)
            if name in _FLAGS:
                set_flags({name: value})
                continue
        rest.append(a)
    return rest


# ---- core flags (reference executor.cc:26-29, platform/) ----
DEFINE_flag("check_nan_inf", False,
            "sweep op outputs for NaN/Inf after each op (eager) and enable "
            "jax debug_nans under jit — reference --check_nan_inf "
            "(framework/executor.cc:325-333)")
DEFINE_flag("benchmark", False,
            "log per-op timing in eager mode — reference --benchmark "
            "(executor.cc:321-324)")
DEFINE_flag("kernel_tier", "auto",
            "which lowering tier the hot-op dispatch sites use: 'auto' "
            "(Pallas on TPU for the families in ops/pallas.AUTO_PALLAS — "
            "lstm, attention, grouped_matmul; membership needs an on-chip "
            "observation, see there — jnp elsewhere, so CPU suites never "
            "pay interpret-mode kernels), 'pallas' (Pallas for every "
            "family; interpret mode on CPU — the parity-test "
            "setting; on a TPU a kernel Mosaic cannot compile raises), "
            "or 'jnp' (the plain jax.numpy lowerings, bitwise "
            "the pre-tier behavior). Per-kernel fallback: an unsupported "
            "shape under a Pallas tier routes to the jnp twin silently "
            "and bumps ops.pallas.fallback_counts()")

DEFINE_flag("xla_compiler_options", "",
            "comma-separated k=v TPU compiler options forwarded to "
            "jit(compiler_options=...), e.g. "
            "xla_tpu_scoped_vmem_limit_kib=114688 — the analog of the "
            "reference's backend gflags (platform/gpu_info.cc)")

DEFINE_flag("conv_space_to_depth", False,
            "rewrite eligible stem convs (NHWC, stride 2, C_in<=4, k>1 — "
            "the ResNet/VGG 7x7/s2 stem over HxWx3 images) as a stride-1 "
            "conv over the 2x2 space-to-depth transform of the input. "
            "Mathematically exact (filter stays OIHW 7x7 in checkpoints; "
            "the rearrangement happens inside the compiled step) and "
            "quadruples MXU lane occupancy at C_in=3 — the standard TPU "
            "ResNet stem transform (MLPerf). Off by default so reference "
            "numeric parity tests see the untransformed summation order")

DEFINE_flag("bn_fusion_barrier", False,
            "A/B probe (default off): optimization barrier between a conv "
            "output and batch_norm's statistics reductions so XLA cannot "
            "fuse the reduces INTO the conv kernel. 13% worse on the "
            "ResNet-50 step when last measured (2026-07-30, on an earlier "
            "revision; not measured on today's code) — the conv+stats "
            "fusion XLA picks was net positive; the flag remains for A/B "
            "runs only. The op checks "
            "OR this flag together with the one-sided flags below (this "
            "flag does not write them; read all three to know the state)")

DEFINE_flag("bn_fusion_barrier_fwd", False,
            "barrier only in batch_norm forward (conv -> stat reduces)")

DEFINE_flag("bn_fusion_barrier_bwd", False,
            "barrier only in batch_norm_grad (dy -> dbias/dscale reduces): "
            "round-5 probe motivated by the profile showing backward "
            "data-grad convs with fused BN-grad reductions picking a ~2x "
            "slower conv emitter (EmitAllBatchInSublanes) than the "
            "unencumbered forward convs")

DEFINE_flag("bn_bf16_stats", False,
            "A/B probe: accumulate batch_norm batch statistics in bfloat16 "
            "instead of the default fp32 stability island (VERDICT r4 "
            "lever (b)). Numerically inadvisable for real training "
            "(E[x^2]-E[x]^2 in 8-bit mantissa); exists to measure whether "
            "accumulator width is on the critical path of the conv+stat "
            "reduce fusions")

DEFINE_flag("pserver_barrier_timeout_s", 60.0,
            "parameter-server wait bound in seconds: how long a sync-mode "
            "push waits at the fan-in barrier (and an async push waits on "
            "bounded staleness) before declaring the round broken by a dead "
            "peer and raising TimeoutError. Overridable per server via "
            "ParameterServer(barrier_timeout_s=...)/serve(); the flag is "
            "the process-wide default (was a hardcoded 60.0)")

DEFINE_flag("pserver_trainer_lease_s", 10.0,
            "heartbeat-lease duration in seconds for sync-mode trainer "
            "membership on a parameter-server shard. A trainer that calls "
            "register_trainer joins the shard's lease set (pushes and "
            "further registrations renew it); a sync round's barrier waits "
            "on the lease set snapshotted at round-open, and a member "
            "whose lease expires mid-round SHRINKS the barrier instead of "
            "timing it out. 0 disables lease bookkeeping entirely "
            "(count-based fan_in barriers only). Overridable per server "
            "via ParameterServer(trainer_lease_s=...)/serve()")

DEFINE_flag("rpc_timeout_s", 90.0,
            "host-RPC response deadline in seconds (was a hardcoded 90.0): "
            "how long RpcClient waits for a reply before declaring the "
            "call timed out (timeouts are never retried — the call may "
            "have applied). Threaded through ParamClient and the "
            "PserverSupervisor heartbeat clients; overridable per client "
            "via RpcClient(timeout=)/ParamClient(rpc_timeout=)")

DEFINE_flag("pserver_wire_dtype", "fp32",
            "dtype dense gradients travel in on the trainer->pserver push "
            "wire: fp32 (exact, default) or fp16 (half the push bytes; "
            "the server upcasts and accumulates in fp32, the reference's "
            "half-precision parameter-server transfer). Pulled params "
            "always return fp32")

DEFINE_flag("conv_1x1_grad_as_dot", False,
            "A/B probe: emit 1x1-conv input/filter gradients as dot_general "
            "channel matmuls instead of jax's transposed convolutions (see "
            "conv2d_grad)")

DEFINE_flag("serving_batch_buckets", "1,2,4,8,16,32",
            "comma-separated power-of-two batch buckets the serving "
            "InferenceEngine pads incoming batches up to. Each bucket is "
            "one jitted executable shape, compiled at warmup; the largest "
            "bucket is the DynamicBatcher's coalesce target and the "
            "chunk width for oversized direct batches. A small fixed set "
            "keeps the XLA trace cache bounded and the hot path "
            "recompile-free (serving/engine.py)")

DEFINE_flag("serving_max_delay_ms", 5.0,
            "how long the serving DynamicBatcher holds an under-full "
            "batch open for more concurrent requests before dispatching "
            "it anyway — the latency bound a single quiet-traffic "
            "request pays for batching (a full bucket dispatches "
            "immediately)")

DEFINE_flag("serving_queue_capacity", 256,
            "bound on requests waiting in the serving DynamicBatcher "
            "queue. When full, new requests are rejected fast with a "
            "typed ServerOverloaded the client can back off on, instead "
            "of stretching everyone's latency without bound")

DEFINE_flag("serving_fleet_replicas", 2,
            "default replica count for serving.FleetSupervisor: how many "
            "supervised ModelServer child processes serve one registry "
            "model (each on a fixed address, restarted from the "
            "registry's current version on crash)")

DEFINE_flag("serving_probe_interval_ms", 100.0,
            "how often the serving FleetClient's background prober "
            "health-checks EJECTED replicas (healthy replicas are not "
            "probed — real traffic is their probe)")

DEFINE_flag("serving_probation_probes", 2,
            "consecutive successful health probes an ejected replica "
            "must pass before the FleetClient re-admits it to the "
            "routing set — one lucky probe doesn't un-eject a flapping "
            "replica")

DEFINE_flag("serving_kv_block_size", 16,
            "tokens per KV-cache block in the generation-serving paged "
            "arena (serving/generate/kvcache.py): each sequence's context "
            "occupies ceil(len/block_size) blocks addressed through its "
            "block table, so smaller blocks waste less tail capacity but "
            "widen the table. One block is also the copy-on-write unit "
            "for beam forks")

DEFINE_flag("serving_kv_num_blocks", 256,
            "blocks in the pre-allocated per-layer KV arena "
            "([num_blocks, block_size, heads, head_dim] per layer, K and "
            "V). Sizes the whole serving memory budget up front; when a "
            "request's worst case cannot be promised from the free "
            "blocks, admission rejects typed with CacheExhausted and the "
            "scheduler keeps it queued")

DEFINE_flag("serving_prefix_cache_blocks", 0,
            "budget of refcount-0 KV blocks the paged arena RETAINS as a "
            "shared-prefix cache instead of recycling eagerly "
            "(serving/generate/kvcache.py): full prompt-prefix blocks are "
            "content-hash-chained at prefill, a new request whose prompt "
            "starts with a cached chain attaches to those blocks "
            "(refcount sharing, copy-on-write protected) and prefills "
            "only its uncached tail. Evicted least-recently-used when "
            "the pool exceeds this budget or admission needs the blocks; "
            "blocks a live sequence holds (refcount > 0) are never "
            "eviction candidates. 0 (default) disables retention — "
            "release recycles eagerly, the pre-cache behavior. Host-side "
            "only: flipping it never retraces")

DEFINE_flag("serving_prefill_chunk", 0,
            "when > 0, a prompt's uncached prefill runs in chunks of at "
            "most this many tokens instead of one whole-window dispatch, "
            "and the generation engine interleaves ONE chunk per decode "
            "step boundary — a long cold prompt admits without stalling "
            "in-flight decode streams for its whole prefill. 0 (default) "
            "keeps single-dispatch prefill. Chunks run through the "
            "chunked-prefill executable (per prompt bucket, compiled at "
            "warmup when chunking or the prefix cache is enabled), so "
            "the hot path stays retrace-free")

DEFINE_flag("serving_exec_cache", True,
            "whether serving engines LOAD persisted compiled executables "
            "(serving/execcache.py): a bundle's published warm/ artifacts "
            "(read-only) or the serving_exec_cache_dir local cache. Every "
            "artifact is fingerprint-checked (bundle content hash, feed "
            "shapes/dtypes, jit-key flags incl. kernel_tier, jax/jaxlib "
            "version, backend platform/device kind) — any mismatch is a "
            "silent miss followed by a normal compile. False = always "
            "compile, bitwise the pre-cache behavior even on warmed "
            "bundles. Host-side only: flipping it never retraces")

DEFINE_flag("serving_exec_cache_dir", "",
            "per-process READ-WRITE compiled-executable cache directory "
            "for bundles without published warm/ artifacts: engine warmup "
            "saves each executable it compiles there and later engines on "
            "the same bundle bytes load instead of compiling. Empty "
            "(default) disables the local cache; published registry "
            "versions use their own <version>/warm/ dir regardless (see "
            "ModelRegistry.warm / publish(warm_cache=True))")

DEFINE_flag("serving_kv_spill_dir", "",
            "per-process READ-WRITE persistent KV-prefix spill directory "
            "(serving/generate/kvstore.py): when set, the paged arena's "
            "LRU eviction DEMOTES refcount-0 registered prefix blocks to "
            "this host-RAM/disk tier instead of discarding them, and "
            "attach_prefix restores spilled blocks into the arena with "
            "zero prefill steps on a hash-chain hit. Every artifact is "
            "fingerprint-checked (bundle content hash, arena geometry, "
            "kernel_tier, jax/jaxlib version, backend) — any mismatch is "
            "a silent miss followed by a normal prefill. Empty (default) "
            "disables spilling; published registry versions use their own "
            "<version>/kv/ dir regardless (see ModelRegistry.warm / "
            "publish(kv_prompts=...))")

DEFINE_flag("serving_kv_spill_bytes", 0,
            "byte budget for the serving_kv_spill_dir tier: when > 0, "
            "writing a KV artifact that would push the directory past the "
            "budget first evicts the oldest artifacts (mtime order) until "
            "the new one fits; an artifact bigger than the whole budget "
            "is not written at all. 0 (default) = unbounded. Published "
            "<version>/kv/ dirs are read-only and never evict")

DEFINE_flag("serving_max_seqs", 8,
            "decode slots in the generation engine's ONE fixed-shape "
            "[max_seqs, 1] decode executable. Bounds concurrent in-flight "
            "sequences; ragged sequences share the executable via block "
            "tables and an active mask, so this is a capacity knob, "
            "never a retrace trigger")

DEFINE_flag("serving_max_models", 4,
            "bound on engines a multi-model ModelServer hosts at once: "
            "adding a model past the budget evicts the least-recently-"
            "used IDLE hosted model first (a model with in-flight "
            "requests is never an eviction candidate, and the server's "
            "default model never evicts); when every candidate is busy "
            "the add fails typed instead of over-committing arena memory")

DEFINE_flag("serving_tenant_rate", 0.0,
            "default per-tenant request rate (tokens per second) for "
            "serving TenantQuotas buckets. Each request spends one "
            "token; an empty bucket rejects typed with QuotaExceeded "
            "carrying the refill ETA — and quota rejects never trigger "
            "router failover/spillover (the request is over budget on "
            "every replica). <= 0 (default) means unlimited unless a "
            "tenant has an explicit override")

DEFINE_flag("serving_tenant_burst", 0,
            "default per-tenant token-bucket ceiling for serving "
            "TenantQuotas: how many requests a tenant can burst above "
            "its steady rate. 0 (default) derives ceil(rate) so a "
            "configured rate always admits at least one request")

DEFINE_flag("serving_tenant_label_cap", 16,
            "bound on distinct tenant ids mirrored into the "
            "paddle_tpu_tenant_* metric label set per TenantQuotas "
            "instance: tenant ids arrive off the wire, so past the cap "
            "(or for a non-identifier name) the label funnels into "
            "__other__ exactly like RPC method names — quota "
            "ENFORCEMENT stays exact per tenant either way")

DEFINE_flag("serving_autoscale_min_replicas", 1,
            "floor the serving FleetAutoscaler never scales below: "
            "idle polls retire replicas one at a time down to this "
            "count and no further")

DEFINE_flag("serving_autoscale_max_replicas", 4,
            "ceiling the serving FleetAutoscaler never scales above: "
            "a burning SLO rule spawns replicas one canary-gated step "
            "at a time up to this count and no further")

DEFINE_flag("serving_autoscale_queue_depth", 8.0,
            "objective for the FleetAutoscaler's default SLO rule: the "
            "fleet-summed paddle_tpu_server_queue_depth a replica set "
            "should stay under. Sustained burn over the rule's windows "
            "triggers a warm scale-out; zero depth with zero burn "
            "counts toward scale-in idle polls")

DEFINE_flag("serving_autoscale_idle_polls", 3,
            "consecutive idle FleetAutoscaler polls (no burning rule, "
            "empty fleet queues) before ONE replica is retired — "
            "scale-in damping so a burst lull doesn't thrash the fleet "
            "(the BacklogAutoscaler precedent, serving-side)")

DEFINE_flag("verify_passes", False,
            "make every program-transforming pass (append_backward, "
            "DistributeTranspiler, memory_optimize/release_memory, "
            "fuse_conv_bn, the GenerationEngine prefill/decode rewrite, "
            "save_inference_model's prune) run fluid.analysis."
            "verify_program over its OUTPUT program and raise a typed "
            "ProgramVerifyError naming the pass on structural damage — "
            "the reference's build-time InferShape/arity net "
            "(op_registry.h), applied at every IR rewrite instead of an "
            "opaque XLA trace error later. Off by default (passes are "
            "already verified by their suites); tests/book runs with it on")

DEFINE_flag("executor_verify", False,
            "verify each program at Executor.run dispatch, once per "
            "(program version, feed/fetch surface), memoized through the "
            "_ProgramAnalysis cache so the steady-state hot path pays one "
            "set lookup; scope-bound free reads (readers, arenas) count "
            "as dataflow roots. Catches hand-mutated programs that never "
            "went through a verifying pass; bench.py stamps this flag "
            "into lane records and the flagship lane asserts the "
            "once-per-version contract")

DEFINE_flag("online_publish_every_steps", 100,
            "how many global steps the online StreamingTrainer trains "
            "between freeze/publish triggers (online/trainer.py). 0 "
            "disables the step trigger; the time trigger "
            "(online_publish_every_s) still applies. The trigger fires at "
            "a step BOUNDARY (after the push acked on every shard), which "
            "is what makes the freezer's cut barrier-consistent")

DEFINE_flag("online_publish_every_s", 0.0,
            "wall-clock publish trigger for the online StreamingTrainer: "
            "freeze/publish when this many seconds elapsed since the last "
            "successful freeze request, checked at step boundaries. 0.0 "
            "(default) disables the time trigger — step cadence "
            "(online_publish_every_steps) drives publishes alone")

DEFINE_flag("online_trainers_min", 1,
            "lower bound on the online TrainerPool's worker count: the "
            "backlog-driven autoscaler never retires below this many "
            "StreamingTrainer workers, and the pool hot-joins "
            "replacements for crashed workers back up to it "
            "(online/pool.py)")

DEFINE_flag("online_trainers_max", 4,
            "upper bound on the online TrainerPool's worker count: a "
            "Master-backlog spike grows the pool (one hot-join per "
            "autoscaler poll while the scale-up SloRule burns) up to "
            "this many StreamingTrainer workers, never past it")

DEFINE_flag("online_min_serve_s", 2.0,
            "rollout hysteresis: the RolloutController will not start a "
            "new rolling_reload until the currently served version has "
            "been serving this long — a flapping trainer publishing "
            "every few steps cannot churn the fleet; intermediate "
            "versions are skipped (the controller always rolls to the "
            "newest published version)")

DEFINE_flag("online_rollout_poll_ms", 250.0,
            "how often the online RolloutController polls the "
            "ModelRegistry for a newer published version than the fleet "
            "is serving")

DEFINE_flag("online_registry_keep", 0,
            "when > 0, the RolloutController garbage-collects the "
            "registry after each successful rollout via "
            "ModelRegistry.gc(keep_latest=N) — old version dirs are "
            "pruned, but never the currently-served, pinned, latest, or "
            "rollback-target (previous) versions. 0 (default) disables "
            "gc: every published version is retained")

DEFINE_flag("obs_op_metrics", False,
            "executor observability hooks: per-op-type dispatch/wall-time "
            "counters (eager: real per-op time; jit: per-step op-type "
            "counts riding the cached _ProgramAnalysis op inventory) and "
            "per-step dispatch counters into the obs.metrics registry. "
            "Deliberately NOT in the executor's _JIT_KEY_FLAGS: flipping "
            "it never retraces — the hooks are host-side only, off the "
            "hot path when disabled (one flag lookup per run)")

DEFINE_flag("obs_metrics_window", 2048,
            "default sample-window capacity of obs.metrics Histogram "
            "children (each wraps a core.profiler.LatencyWindow ring of "
            "this many recent observations for p50/p99 readout); "
            "families may override per-histogram via window=")

DEFINE_flag("obs_slo_interval_s", 1.0,
            "evaluation period of a background obs.slo.SloMonitor: how "
            "often each declared SLO rule is reduced against a registry "
            "snapshot, its burn rate updated "
            "(paddle_tpu_slo_burn_rate) and its multi-window breach "
            "state re-judged. Overridable per monitor via "
            "SloMonitor(interval_s=)")

DEFINE_flag("obs_flight_events", 2048,
            "capacity of the per-process flight recorder ring "
            "(obs.recorder): how many recent structured lifecycle "
            "events (admissions, evictions, restarts, rollout/canary "
            "outcomes, retry/failover/spillover decisions, Pallas "
            "fallbacks) each process retains for the built-in "
            "flight_dump RPC and incident bundles. Oldest events are "
            "overwritten (the dropped count is reported in dumps)")

DEFINE_flag("obs_compile_log", 256,
            "capacity of the per-process obs.perf CompileLog ring: how "
            "many recent CompileRecords (site, wall seconds and their "
            "split by stage, executable identity) are retained "
            "for stats()/bench stamps; 0 disables compile telemetry "
            "entirely (no histogram observations, no stage counters, no "
            "records, no 'compile' flight events; the listener on JAX's "
            "monitoring events returns at once). NOT in the executor jit "
            "key — flipping it never retraces")

DEFINE_flag("obs_incident_dir", "",
            "directory obs.recorder.IncidentCollector writes incident "
            "bundles (one JSON file per trigger: breach / canary_failed "
            "/ child_restart) into; empty (default) keeps bundles "
            "in-memory only (IncidentCollector.bundles, bounded)")

DEFINE_flag("plan_memory_budget_bytes", 0,
            "per-device memory budget the placement planner "
            "(parallel.planner) prunes mesh candidates against — a "
            "candidate whose modeled per-device bytes (params + grads + "
            "optimizer state + activations) exceed the budget is marked "
            "pruned with a why-note and never ranked; 0 (default) "
            "disables the budget. Host-side: part of the plan "
            "fingerprint, never in the jit key")

DEFINE_flag("plan_max_candidates", 16,
            "maximum ranked candidates a PlacementReport keeps; the "
            "search still costs every legal mesh, then drops the tail "
            "past this cap (the report records how many were dropped). "
            "0 keeps everything. Host-side: part of the plan "
            "fingerprint, never in the jit key")

DEFINE_flag("plan_cache_dir", "",
            "local directory of placement-plan artifacts (.jplan) "
            "consulted read-write by parallel.planner.plan() when no "
            "published bundle plan/ dir applies: a fingerprint-matching "
            "artifact skips the search (paddle_tpu_plan_cache_hits), a "
            "fresh search persists its report there; empty (default) "
            "disables the local cache. Not in the jit key: the plan "
            "only chooses mesh/ShardingPlan arguments, the compiled "
            "step's identity is theirs")

# PDTPU_FLAGS=check_nan_inf=1,benchmark=0 — unknown names warn and are
# ignored (a typo'd env var must not make the package unimportable)
_env = os.environ.get("PDTPU_FLAGS", "")
if _env:
    import warnings

    for _kv in _env.split(","):
        if "=" not in _kv:
            continue
        _name, _value = _kv.split("=", 1)
        try:
            set_flags({_name: _value})
        except KeyError:
            warnings.warn(f"PDTPU_FLAGS: ignoring unknown flag {_name!r} "
                          f"(known: {sorted(_FLAGS)})")
