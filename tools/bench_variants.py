"""Decompose the ResNet-50 step cost by timing model variants on the chip.

Variants:
  full        — the bench.py training step (fwd+bwd+momentum)
  fwd         — forward + loss only (infer program, no backward)
  nobn        — BN removed entirely (identity + activation): the delta vs
                full bounds BN's total cost, slightly overstating it since
                the substitute has no per-channel affine traffic at all
  bnfrozen    — BN with is_test=True (running stats; no reduction pass)

Timing rides the kernel probe's measurement core
(tools/kernel_probe.measure): interleaved best-of-N windows across all
requested variants.

Usage: python tools/bench_variants.py [--steps 8] [--windows 3]
       [--batch 256] [--which all]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_variant(batch, image_size, class_dim, variant):
    import bench
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        shape = [image_size, image_size, 3]
        img = fluid.layers.data("img", shape=shape)
        label = fluid.layers.data("label", shape=[1], dtype="int64")

        if variant in ("nobn", "bnfrozen"):
            orig = fluid.layers.batch_norm

            def patched(input, act=None, is_test=False, **kw):
                if variant == "bnfrozen":
                    return orig(input, act=act, is_test=True, **kw)
                # nobn: identity (+act) — no normalization, no affine
                helper_out = fluid.layers.scale(input, scale=1.0)
                if act:
                    helper_out = getattr(fluid.layers, act)(helper_out)
                return helper_out

            fluid.layers.batch_norm = patched
            try:
                logits = bench.resnet50(img, class_dim)
            finally:
                fluid.layers.batch_norm = orig
        else:
            logits = bench.resnet50(img, class_dim)

        loss = fluid.layers.softmax_with_cross_entropy(logits, label)
        avg_loss = fluid.layers.mean(loss)
        if variant != "fwd":
            fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(
                avg_loss, startup)
    return main, startup, avg_loss


def build_runner(variant, batch):
    """Zero-arg timed step closure for one variant — what the shared
    measurement core (tools/kernel_probe.measure) times. Startup
    runs here, once; the first measured call absorbs the jit compile as
    the measurement core's per-runner warmup call."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    image_size, class_dim = 224, 1000
    main_prog, startup, avg_loss = build_variant(batch, image_size, class_dim,
                                                 variant)
    rng = np.random.RandomState(0)
    feeds = [{
        "img": jax.device_put(rng.normal(0, 1, (batch, image_size, image_size,
                                                 3)).astype("float32")
                              ).astype(jnp.bfloat16),
        "label": jax.device_put(
            rng.randint(0, class_dim, (batch, 1)).astype("int32")),
    } for _ in range(2)]

    scope = fluid.Scope()
    exe = fluid.Executor(mode="jit", donate=(variant != "fwd"), amp=True)
    with jax.default_matmul_precision("bfloat16"):
        exe.run(startup, scope=scope)
    state = {"i": 0}

    def run():
        i = state["i"]
        state["i"] += 1
        with jax.default_matmul_precision("bfloat16"):
            v = exe.run(main_prog, feed=feeds[i % 2], fetch_list=[avg_loss],
                        scope=scope, return_numpy=False)
        return v[0]
    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8,
                    help="steps per timing window")
    ap.add_argument("--windows", type=int, default=3,
                    help="best-of-N windows per variant (interleaved "
                         "across variants)")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--which", default="all")
    args = ap.parse_args()

    # ONE interleaved best-of-N implementation in the tree, so drift hits
    # every variant's windows equally instead of biasing whichever ran last
    from kernel_probe import measure

    variants = ["full", "fwd", "bnfrozen", "nobn"] if args.which == "all" \
        else args.which.split(",")
    runners = {v: build_runner(v, args.batch) for v in variants}
    times, dropped = measure(runners, repeats=args.windows,
                             inner=args.steps)
    for v in variants:
        if v in dropped:
            print(f"{v:10s} failed to run: {dropped[v]}", flush=True)
            continue
        dt = times[v] / 1e3
        print(f"{v:10s} {times[v]:8.2f} ms/step  "
              f"({args.batch/dt:.0f} img/s)", flush=True)


if __name__ == "__main__":
    main()
