"""Build/feed scaffolding for ``tools/hlo_report.py``.

The flagship ResNet-50 build (program + pre-staged bf16 feeds + jit
executor + startup under bf16 matmul precision), plus a ``--bundle``
target so ANY published model — a
``save_inference_model`` export dir or a registry ``<model>/<version>``
dir — can be profiled, not just the flagship.

The CLI consumes a :class:`Target`: the program, a rotating feed list,
the fetch names, the executor/scope that would dispatch it in
production, and a ``ctx()`` context manager reproducing the numeric
environment the target trains/serves under.
"""

from __future__ import annotations

import contextlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np


class Target:
    """One profilable dispatch: ``exe.run(program, feed=feeds[i],
    fetch_list=fetch_names, scope=scope)`` under ``ctx()``."""

    def __init__(self, label, program, feeds, fetch_names, exe, scope,
                 ctx=None):
        self.label = label
        self.program = program
        self.feeds = list(feeds)
        self.fetch_names = list(fetch_names)
        self.exe = exe
        self.scope = scope
        self._ctx = ctx

    def ctx(self):
        return self._ctx() if self._ctx is not None \
            else contextlib.nullcontext()

    def step_fn(self):
        """A zero-arg one-dispatch callable cycling the staged feeds."""
        i = [0]

        def step():
            feed = self.feeds[i[0] % len(self.feeds)]
            i[0] += 1
            return self.exe.run(self.program, feed=feed,
                                fetch_list=self.fetch_names,
                                scope=self.scope, return_numpy=False)
        return step


def add_target_args(ap):
    """The target-selection arguments both CLIs share."""
    ap.add_argument("--batch", type=int, default=256,
                    help="batch size (flagship default 256; bundle "
                         "targets synthesize feeds at this many rows)")
    ap.add_argument("--bundle", default=None, metavar="DIR",
                    help="profile the save_inference_model / registry "
                         "version bundle at DIR instead of building the "
                         "flagship ResNet-50 training step")
    ap.add_argument("--no-s2d", action="store_true",
                    help="flagship only: disable the space-to-depth "
                         "stem rewrite")


def build_target(args):
    return build_bundle(args.bundle, batch=args.batch) if args.bundle \
        else build_flagship(args.batch, no_s2d=args.no_s2d)


def build_flagship(batch, image_size=224, class_dim=1000, no_s2d=False):
    """The exact bench.py flagship training step: ResNet-50, bf16
    feeds pre-staged on device, jit + donation + AMP executor, startup
    run under bf16 matmul precision."""
    import jax
    import jax.numpy as jnp
    import bench
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.flags import set_flags

    set_flags({"conv_space_to_depth": not no_s2d})
    main_prog, startup, avg_loss = bench.build(batch, image_size, class_dim)
    rng = np.random.RandomState(0)
    feeds = [{
        "img": jax.device_put(
            rng.normal(0, 1, (batch, image_size, image_size, 3))
            .astype("float32")).astype(jnp.bfloat16),
        "label": jax.device_put(
            rng.randint(0, class_dim, (batch, 1)).astype("int32")),
    } for _ in range(2)]
    scope = fluid.Scope()
    exe = fluid.Executor(mode="jit", donate=True, amp=True)

    def ctx():
        return jax.default_matmul_precision("bfloat16")

    with ctx():
        exe.run(startup, scope=scope)
    return Target(f"flagship resnet50 bs{batch}", main_prog, feeds,
                  [avg_loss.name], exe, scope, ctx=ctx)


def build_bundle(model_dir, batch=1):
    """Any published model: load the bundle into a private scope (the
    serving engine's load path) and synthesize a ``batch``-row template
    feed from the program's feed-var metadata."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.obs import perf

    scope = fluid.Scope()
    exe = fluid.Executor(mode="jit")
    program, feed_names, fetch_vars = fluid.io.load_inference_model(
        model_dir, exe, scope=scope)
    from paddle_tpu.serving.engine import commit_scope_arrays
    commit_scope_arrays(scope)
    feed = perf.template_feed(program, feed_names, batch=batch)
    fetch_names = [v if isinstance(v, str) else v.name for v in fetch_vars]
    return Target(f"bundle {model_dir} bs{batch}", program, [feed],
                  fetch_names, exe, scope)
