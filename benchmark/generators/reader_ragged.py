"""A seeded corpus of ragged sequences read LIVE inside the window through
the system's own reader stack: ``reader.bucket_by_length`` ->
``pack_sequences(max_len=bound)`` (in the configuration's ``collate``) ->
``reader.double_buffer``, one ``Executor.run`` per batch, epochs repeated
until the window ends. One compiled shape per bucket, all warmed in set-up.

Traffic parameters: ``batch``, ``bucket_bounds``, ``prefetch`` (the double
buffer's capacity) and the corpus's own (``n_sequences``, ``copies``,
``length_*``, ``label_noise``), which the configuration's ``corpus``
reads."""

import collections

from benchmark.session import (Session, executor_check_step,
                               executor_step, feed_info)


class ReaderRagged(Session):
    def __init__(self, ctx):
        import numpy as np

        from benchmark.harness import snapshot_weights
        from paddle_tpu import reader
        from paddle_tpu.reader import bucket_bound_for

        self.prog = ctx.start_program()
        self.initial_weights = snapshot_weights(self.prog)
        model, cfg, traffic = ctx.model, ctx.cfg, ctx.traffic
        bounds = [int(b) for b in traffic["bucket_bounds"]]
        with ctx.spans.span("corpus"):
            corpus = model.corpus(cfg, np.random.RandomState(ctx.seed),
                                  traffic)
        batches = reader.bucket_by_length(
            lambda: iter(corpus), key=model.sample_length,
            bucket_bounds=bounds, batch_size=int(traffic["batch"]),
            drop_last=True)

        def host_batches():
            for samples in batches():
                bound = bucket_bound_for(
                    bounds, max(model.sample_length(s) for s in samples))
                feed = model.collate(cfg, samples, bound)
                yield bound, feed, feed_info(model, cfg, feed)

        self._infos = collections.deque()    # filled by the feeder, in order

        def host_feeds():
            for _, feed, info in host_batches():
                self._infos.append(info)
                yield feed

        self._staged = reader.double_buffer(
            host_feeds, capacity=int(traffic.get("prefetch", 2)))
        self._live = None

        # one batch of every shape for the warm-up, from the reader, staged
        # by the same double buffer as the window's (another placement would
        # be another compiled program). The check is made on the first
        # bucket's: one shape whatever the seed, so the check step's program
        # is compiled once in a checkout and not once per shape a seed's
        # first batch happens to have, and the widest ratio of lengths inside
        # one batch, which is where a mask that leaks shows
        with ctx.spans.span("stage_feeds"):
            seen = {}
            for bound, feed, info in host_batches():
                seen.setdefault(bound, (feed, info))
                if len(seen) == len(bounds):
                    break
            if len(seen) != len(bounds):
                raise ValueError(f"the corpus fills only the buckets "
                                 f"{sorted(seen)} of {bounds}")
            host = [seen[b] for b in sorted(seen)]
            staged = reader.double_buffer(lambda: (f for f, _ in host))()
            self.warm_feeds = [(d, info)
                               for d, (_, info) in zip(staged, host)]
        self.check_feed = self.warm_feeds[0]

    def feeds(self):
        while True:                                   # epoch after epoch
            self._live = self._staged()
            for feed in self._live:
                yield feed, self._infos.popleft()

    def step(self, feed):
        return executor_step(self.prog, feed)

    def check_step(self, feed):
        return executor_check_step(self.prog, feed)

    def close(self):
        if self._live is not None:
            self._live.close()            # releases the feeder thread


def open_session(ctx):
    return ReaderRagged(ctx)
