"""Worker script for the two-process multihost smoke test (spawned by
paddle_tpu.distributed.launch; see tests/test_multihost.py).

Verifies, from inside a 2-process x 4-virtual-device jax.distributed
runtime: process wiring, the DCN-major global mesh, a CROSS-PROCESS psum,
and a sharded fluid training step over the global mesh.
"""

import os
import sys

# a CPU test: hold JAX to the CPU before it touches a backend
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

import numpy as np


def main():
    from paddle_tpu.parallel.multihost import init_multihost, global_mesh

    info = init_multihost()
    assert info["process_count"] == 2, info
    assert info["local_devices"] == 4, info
    assert info["global_devices"] == 8, info

    mesh = global_mesh(axes=("dp",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    # cross-process psum: each device contributes its global row index
    sharding = NamedSharding(mesh, P("dp"))
    rank = info["process_index"]
    local = np.arange(rank * 4, rank * 4 + 4, dtype=np.float32)
    arr = jax.make_array_from_process_local_data(sharding, local, (8,))

    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                              in_specs=P("dp"), out_specs=P()))
    try:
        total = f(arr)
    except Exception as e:
        if "aren't implemented on the CPU backend" in str(e):
            # this jaxlib's CPU backend executes no cross-process
            # collectives at all (XlaRuntimeError INVALID_ARGUMENT
            # "Multiprocess computations aren't implemented on the CPU
            # backend"; its gloo transport abort()s on the sharded step
            # — probed 2026-08). Process wiring, the DCN-major global
            # mesh, and the distributed runtime handshake were all
            # verified above; report the capability gap explicitly so
            # the test can skip with the root cause instead of failing
            # tier-1 on every CPU box.
            print(f"MULTIHOST_WORKER_UNSUPPORTED: {e}")
            return 0
        raise
    got = float(np.asarray(total)[0])
    assert got == sum(range(8)), got
    print(f"psum ok: {got}")

    # a sharded fluid training step over the global mesh (dp over DCN):
    # the same shard_program_step the single-host tests run
    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel import ShardingPlan, shard_program_step
    from paddle_tpu.testing import build_mlp, mlp_feed

    main_p, startup, loss = build_mlp(dim=16, classes=4, hidden=16,
                                      opt="sgd")
    feed = mlp_feed(16, dim=16, classes=4)
    scope = fluid.Scope()
    exe = fluid.Executor(mode="jit")
    exe.run(startup, scope=scope)
    plan = ShardingPlan(mesh)
    fn, state, feeds = shard_program_step(exe, main_p, feed, [loss], plan,
                                          scope=scope)
    losses = []
    with mesh:
        for _ in range(3):
            state, fetches = fn(state, feeds)
            losses.append(float(np.asarray(fetches[0])))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
    print(f"sharded step ok: {losses[0]:.4f} -> {losses[-1]:.4f}")
    print("MULTIHOST_WORKER_OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
