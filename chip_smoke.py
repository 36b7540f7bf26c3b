"""chip_smoke.py — the quickest proof that the main path still starts on the
chip.

One process, no children. It drives the ResNet-50 ImageNet training step
through the entry points a user calls (``fluid.Program`` ->
``optimizer.minimize`` -> ``fluid.Executor.run``) at full width — batch 256,
224x224x3 NHWC, 1000 classes, Momentum, ``Executor(mode="jit", donate=True,
amp=True)`` — under the default flags, so ``kernel_tier=auto`` routes exactly
as it does for a user on a TPU. Weights come from the program's seeded
initialisers and the batch from a seeded generator: nothing is read from
outside the checkout and no network is needed.

What it checks: the device is a TPU; every loss is finite and the last is
below the first; no Pallas kernel ran interpreted. What it prints on the
way: device, jax/jaxlib/libtpu versions, the compile-cache directory with
its hits and misses, compile seconds and steady step milliseconds (smoke
observations — NOT benchmark numbers), per-family Pallas dispatch and
fallback counts. The last line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

On a host with four or more chips it then runs the same step data-parallel
over four of them (``parallel.shard_program_step``, ``dp=4`` with sharded
optimizer state) and checks that feeds and optimizer state really live on
four distinct devices.

Any failure of any phase exits non-zero and prints no result line. Flags
pass through as ``--name=value`` (``python chip_smoke.py --kernel_tier=jnp``).

The phases are functions of their sizes; ``tests/test_chip_smoke.py`` runs
them tiny on the CPU. Only ``main`` demands the chip and the full width.
"""

import json
import sys
import time

FULL = dict(batch=256, image_size=224, class_dim=1000)
STEPS = 12
# the flagship's 0.1 needs a warm-up schedule to fall from a random init
# (without one the loss spikes for the first steps); the smoke checks the
# step, not the schedule
LR = 0.01


def device_report():
    """What JAX attached: platform, kind, count and the versions in play."""
    import jax
    import jaxlib

    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                       # not installed on a CPU-only box
        libtpu = None
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu}


def make_feed(batch, image_size, class_dim, seed=0):
    """One batch from a seed, on the host (bf16 images: the cast-at-feed
    the input pipeline does)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    img = rng.normal(0, 1, (batch, image_size, image_size, 3))
    label = rng.randint(0, class_dim, (batch, 1))
    return {"img": img.astype(jnp.bfloat16), "label": label.astype("int32")}


def _build(batch, image_size, class_dim, depths):
    """(main, startup, loss, fused?) — the flagship program as bench.py
    builds it, fused exactly when the kernel tier routes to Pallas."""
    import bench

    fuse = bench.flagship_fuse()
    main_prog, startup, avg_loss = bench.build(
        batch, image_size, class_dim, fuse=fuse, lr=LR,
        depths=depths or bench.RESNET50_DEPTHS)
    return main_prog, startup, avg_loss, fuse


def _check_losses(losses):
    import numpy as np
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")


def _timings(startup_s, step_s):
    steady = sorted(step_s[2:]) or step_s[-1:]
    return {"startup_s": startup_s,
            # first step = trace + XLA compile (or cache load) + one run
            "first_step_s": step_s[0],
            "steady_step_ms": 1e3 * steady[len(steady) // 2]}


def train_phase(batch, image_size, class_dim, steps=STEPS, depths=None):
    """Build the flagship program, run startup and ``steps`` training steps
    on one pre-staged batch, every step timed to ``block_until_ready``.
    Returns losses and the compile/steady timings; raises if a loss is not
    finite or the last is not below the first."""
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid

    main_prog, startup, avg_loss, fuse = _build(batch, image_size, class_dim,
                                                depths)
    feed = jax.block_until_ready(
        jax.device_put(make_feed(batch, image_size, class_dim)))
    scope = fluid.Scope()
    exe = fluid.Executor(mode="jit", donate=True, amp=True)

    t0 = time.perf_counter()
    exe.run(startup, scope=scope)
    jax.block_until_ready([scope.find_var(n) for n in scope.local_names()])
    startup_s = time.perf_counter() - t0

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        (v,) = exe.run(main_prog, feed=feed, fetch_list=[avg_loss],
                       scope=scope, return_numpy=False)
        jax.block_until_ready(v)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(v).reshape(())))   # one element
    _check_losses(losses)
    return {"fused": fuse, "losses": losses, **_timings(startup_s, step_s)}


def dp_phase(n_devices, batch, image_size, class_dim, steps=STEPS,
             depths=None):
    """The same step data-parallel over ``n_devices`` in this one process:
    ``shard_program_step`` under ``ShardingPlan(make_mesh(n, ("dp",)),
    shard_opt_state=True)``. Besides the loss checks, the feeds and the
    largest optimizer accumulator must each hold one shard on each of
    ``n_devices`` DISTINCT devices (spread, not replicated)."""
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel import (ShardingPlan, make_mesh,
                                     shard_program_step)

    main_prog, startup, avg_loss, _ = _build(batch, image_size, class_dim,
                                             depths)
    scope = fluid.Scope()
    exe = fluid.Executor(mode="jit", amp=True)
    t0 = time.perf_counter()
    exe.run(startup, scope=scope)
    mesh = make_mesh(n_devices, axes=("dp",))
    plan = ShardingPlan(mesh, shard_opt_state=True)
    fn, state, feeds = shard_program_step(
        exe, main_prog, make_feed(batch, image_size, class_dim), [avg_loss],
        plan, scope=scope, donate=True)
    jax.block_until_ready((state, feeds))
    startup_s = time.perf_counter() - t0

    acc = max((n for n in state if "_velocity" in n),
              key=lambda n: state[n].size)
    placement = {}
    for name, x in (("img", feeds["img"]), (acc, state[acc])):
        shards = x.addressable_shards
        n_dev, shard = len({s.device for s in shards}), shards[0].data.shape
        if n_dev != n_devices or shard[0] * n_devices != x.shape[0]:
            raise AssertionError(
                f"{name} is not spread over {n_devices} devices: {n_dev} "
                f"device(s), shard {shard} of {x.shape}")
        placement[name] = {"devices": n_dev, "shard": list(shard)}

    losses, step_s = [], []
    with mesh:
        for _ in range(steps):
            t0 = time.perf_counter()
            state, (v,) = fn(state, feeds)
            jax.block_until_ready(v)
            step_s.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(v).reshape(())))
    _check_losses(losses)
    return {"losses": losses, "placement": placement,
            **_timings(startup_s, step_s)}


def kernel_report():
    """Per Pallas family: native / interpreted dispatches and fallbacks."""
    from paddle_tpu.ops.pallas import (AUTO_PALLAS, dispatch_counts,
                                       fallback_counts)
    return {"auto_pallas": sorted(AUTO_PALLAS),
            "dispatches": dispatch_counts(), "fallbacks": fallback_counts()}


def assert_native(report):
    """No Pallas kernel may have run through the interpreter."""
    bad = {k: c["interpret"] for k, c in report["dispatches"].items()
           if c["interpret"]}
    if bad:
        raise AssertionError(f"Pallas kernels ran with interpret=True: {bad}")


def main(argv):
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform!r}, "
              f"JAX_PLATFORMS={jax.config.jax_platforms!r}); the smoke "
              "only passes on the chip", file=sys.stderr)
        return 2

    from paddle_tpu.core import compile_cache
    from paddle_tpu.core.flags import get_flag, init_flags

    rest = init_flags(argv)
    if rest:
        print(f"chip_smoke: unknown arguments {rest}", file=sys.stderr)
        return 2
    cache_dir, cache = compile_cache.enable()
    rep = device_report()
    print(f"device: {json.dumps(rep)}")
    print(f"compile cache dir: {cache_dir} "
          f"({compile_cache.ENV_VAR} "
          f"{'set' if cache_dir != compile_cache.DEFAULT_DIR else 'unset'})")
    print(f"kernel_tier: {get_flag('kernel_tier')}")

    out = train_phase(**FULL)
    print(f"program: ResNet-50 bs{FULL['batch']} {FULL['image_size']}px "
          f"{FULL['class_dim']} classes, Momentum lr={LR}, amp, "
          f"fused={out['fused']}")
    print("losses: " + " ".join(f"{v:.4f}" for v in out["losses"]))
    print(f"smoke observation (not a benchmark number): startup "
          f"{out['startup_s']:.1f} s, first step incl. compile "
          f"{out['first_step_s']:.1f} s, steady step "
          f"{out['steady_step_ms']:.1f} ms (median of {STEPS - 2})")
    print(f"compile cache: hits={cache.hits} misses={cache.misses} "
          f"({'nothing compiled' if not cache.misses else 'compiled'})")

    if rep["count"] >= 4:
        dp = dp_phase(4, **FULL)
        print(f"dp=4 (ShardingPlan shard_opt_state=True), global batch "
              f"{FULL['batch']}: placement {json.dumps(dp['placement'])}")
        print("dp=4 losses: " + " ".join(f"{v:.4f}" for v in dp["losses"]))
        # same seed, same initial weights: the first loss (taken before any
        # update) must agree with the one-chip run up to reduction order
        if abs(dp["losses"][0] - out["losses"][0]) > 1e-2 * out["losses"][0]:
            raise AssertionError(
                f"dp=4 first loss {dp['losses'][0]} disagrees with the "
                f"one-chip reference {out['losses'][0]}")
        print(f"dp=4 smoke observation (not a benchmark number): startup + "
              f"placement {dp['startup_s']:.1f} s, first step incl. compile "
              f"{dp['first_step_s']:.1f} s, steady step "
              f"{dp['steady_step_ms']:.1f} ms (median of {STEPS - 2})")

    kr = kernel_report()
    print(f"pallas: {json.dumps(kr)}")
    assert_native(kr)
    rio = sys.modules.get("paddle_tpu.recordio")
    print(f"recordio native library touched: "
          f"{bool(rio is not None and rio._LIB_TRIED)}")

    print(json.dumps({"ok": True, "device": {
        "platform": rep["platform"], "kind": rep["kind"],
        "count": rep["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
