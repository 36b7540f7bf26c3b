"""A ring of device-resident batches, one synchronous ``Executor.run`` after
another: the feed path is bypassed, the step is what is measured.

Traffic parameters: ``batch``, ``ring`` (how many batches), and whatever the
configuration's ``device_batch`` reads (``length``, ``copies``,
``label_noise``)."""

import itertools

from benchmark.session import (Session, executor_check_step,
                               executor_step, stage_ring)


class StagedRing(Session):
    def __init__(self, ctx):
        from benchmark.harness import snapshot_weights

        self.prog = ctx.start_program()
        self.initial_weights = snapshot_weights(self.prog)
        self.ring = stage_ring(ctx)
        self.check_feed = self.ring[0]
        self.warm_feeds = self.ring[:1]          # every batch has one shape

    def feeds(self):
        return itertools.cycle(self.ring)

    def step(self, feed):
        return executor_step(self.prog, feed)

    def check_step(self, feed):
        return executor_check_step(self.prog, feed)


def open_session(ctx):
    return StagedRing(ctx)
