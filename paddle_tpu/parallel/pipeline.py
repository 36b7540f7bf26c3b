"""Pipeline parallelism: a GPipe-style microbatch pipeline over a ``pp``
mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.3 lists it as
TPU-native new work); its model-parallel story is per-layer device
placement (legacy parallel_nn). TPU-first construction: a stack of S
identical stages lives stage-sharded as ``params[S, ...]`` with stage s's
slice on device s; microbatches stream through a shift register
of activations that advances via ``ppermute`` over the ICI ring each tick
(the scaling-book pipelining recipe). M microbatches drain in M + S - 1
ticks with the usual (S-1)/M bubble; reverse-mode AD through the shard_map
(ppermute transposes to the reverse ring) gives the backward schedule for
free.

    mesh = make_mesh(4, axes=("pp",))
    y = pipeline_apply(stage_fn, stacked_params, x_microbatches, mesh)

``stage_fn(stage_params, x) -> y`` must keep x/y the same shape (the
inter-stage activation). All devices run every tick (bubble ticks compute
on zeros), exactly like hardware pipelines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def shard_pipeline_params(stacked_params, mesh, axis="pp"):
    """Place a [S, ...] stage-stacked param pytree stage-sharded."""
    ep = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, ep),
                                  stacked_params)


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh, axis="pp",
                   data_spec=None, param_specs=None):
    """Run ``microbatches [M, mb, ...]`` through S pipelined stages.

    stacked_params: pytree of [S, ...] arrays (stage-major, sharded or not);
    returns [M, mb, ...] outputs.

    Composition hooks (dp×pp×tp on one 3-axis mesh): ``data_spec`` shards
    the microbatch dims over other mesh axes (e.g. P(None, "dp") — each dp
    group pipelines its own batch shard; outputs come back with the same
    spec), and ``param_specs`` overrides the per-leaf parameter specs so
    stage weights can ALSO be tensor-sharded (e.g. P("pp", None, "tp") with
    the stage_fn psum-ing its partial matmul over "tp" — the Megatron
    pattern inside each pipeline stage)."""
    n_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stacked_params leading dim {leaf.shape[0]} must equal the "
                f"{axis!r} axis size {n_stages} (one stage per device; "
                "stack-fold larger stacks into the stage_fn)")

    def per_device(params, xs):
        # params: this device's [1, ...] stage slice; xs: full [M, mb, ...]
        local = jax.tree_util.tree_map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (zeros once the stream drains)
            inject = jnp.where(t < m, xs[jnp.minimum(t, m - 1)],
                               jnp.zeros(mb_shape, xs.dtype))
            inp = jnp.where(stage == 0, inject, buf)
            y = stage_fn(local, inp)
            # last stage collects finished microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            take = (stage == n_stages - 1) & (t >= n_stages - 1)
            outs = jax.lax.dynamic_update_slice(
                outs,
                jnp.where(take, y, jax.lax.dynamic_index_in_dim(
                    outs, out_idx, keepdims=False))[None],
                (out_idx,) + (0,) * len(mb_shape))
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        outs0 = jnp.zeros((m,) + mb_shape, xs.dtype)
        buf0 = jnp.zeros(mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(m + n_stages - 1))
        # outputs live on the last stage; broadcast to every device
        keep = (stage == n_stages - 1).astype(xs.dtype)
        return jax.lax.psum(outs * keep, axis)

    spec_params = param_specs if param_specs is not None else \
        jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    dspec = data_spec if data_spec is not None else P()
    if len(dspec) >= 1 and dspec[0] is not None:
        # per_device closes over the GLOBAL microbatch count; sharding the
        # M dim would silently re-feed clamped local microbatches
        raise ValueError(
            f"data_spec {dspec} must not partition the leading microbatch "
            "dim; shard the per-microbatch batch dim (e.g. P(None, 'dp'))")
    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(spec_params, dspec), out_specs=dspec,
                       check_vma=False)
    return fn(stacked_params, microbatches)


def pipeline_stack_reference(stage_fn, stacked_params, microbatches):
    """Sequential (non-pipelined) reference: fold every stage over every
    microbatch — what pipeline_apply must match bit-for-bit modulo
    reduction order."""
    s = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    def apply_all(x):
        for i in range(s):
            local = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
            x = stage_fn(local, x)
        return x

    return jax.vmap(apply_all)(microbatches)
