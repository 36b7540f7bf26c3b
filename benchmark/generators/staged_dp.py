"""Data-parallel training over several chips in one process: the step that
``parallel.shard_program_step`` returns under ``ShardingPlan(make_mesh(n,
("dp",)), shard_opt_state=True)``, over a ring of placed global batches.

Traffic parameters: ``batch`` (global), ``ring``, ``mesh`` (the number of
chips on the ``dp`` axis), ``shard_opt_state``."""

import itertools

from benchmark.session import Session, stage_ring


class StagedDP(Session):
    def __init__(self, ctx):
        import jax

        from benchmark.harness import snapshot_weights
        from paddle_tpu.parallel import (ShardingPlan, make_mesh,
                                         shard_program_step)

        self.n = int(ctx.traffic["mesh"])
        if self.n > ctx.chips:
            raise ValueError(f"mesh of {self.n} on a cell of {ctx.chips}")
        self.prog = p = ctx.start_program()
        self.initial_weights = snapshot_weights(p)
        ring = stage_ring(ctx)                 # on device 0, then placed
        with ctx.spans.span("place"):
            self.mesh = make_mesh(self.n, axes=("dp",))
            plan = ShardingPlan(
                self.mesh,
                shard_opt_state=bool(ctx.traffic["shard_opt_state"]))
            self.fn, self.state, first = shard_program_step(
                p.exe, p.main, ring[0][0], [p.loss], plan, scope=p.scope,
                donate=True)
            shardings = jax.tree_util.tree_map(lambda x: x.sharding, first)
            placed = [first] + [jax.device_put(dict(f), shardings)
                                for f, _ in ring[1:]]
            jax.block_until_ready((self.state, placed))
        self.ring = [(f, info) for f, (_, info) in zip(placed, ring)]
        self.check_feed = self.ring[0]
        self.warm_feeds = self.ring[:1]

    def feeds(self):
        return itertools.cycle(self.ring)

    def step(self, feed):
        with self.mesh:
            self.state, (loss,) = self.fn(self.state, feed)
        return loss

    def placement(self):
        """(e) the feeds and the largest optimizer accumulator each hold one
        shard on each of ``n`` DISTINCT devices (chip_smoke.dp_phase's
        check)."""
        accs = [k for k in self.state
                if "_velocity" in k or "_moment" in k]
        acc = max(accs, key=lambda k: self.state[k].size)
        feed = self.ring[0][0]
        name = max(feed, key=lambda k: feed[k].size)
        out = {"ok": True}
        for label, x in ((name, feed[name]), (acc, self.state[acc])):
            shards = x.addressable_shards
            devices = len({s.device for s in shards})
            shard = shards[0].data.shape
            spread = devices == self.n and shard[0] * self.n == x.shape[0]
            out[label] = {"devices": devices, "shard": list(shard),
                          "of": list(x.shape)}
            out["ok"] = out["ok"] and spread
        return out


def open_session(ctx):
    return StagedDP(ctx)
