"""What every generator kind hands the harness: a session over one built
program. A kind fills in how feeds arrive and how a step is called."""


def feed_info(cell_model, cfg, feed):
    """Counts of one feed, taken on the host before the window: real
    samples, real elements (tokens where samples are sequences) and the
    training FLOPs the configuration's own function gives."""
    samples, elements = cell_model.batch_counts(feed)
    return {"samples": samples, "elements": elements,
            "flops": cell_model.train_flops(cfg, feed)}


class Session:
    """``initial_weights``: the parameters after the start-up program, on the
    host. ``check_feed``: the (feed, info) whose first loss the reference
    must reproduce. ``warm_feeds``: one (feed, info) per compiled shape."""

    initial_weights = None
    check_feed = None
    warm_feeds = ()

    def feeds(self):
        """An endless iterator of (feed, info)."""
        raise NotImplementedError

    def step(self, feed):
        """One call into the system's train step; returns the loss, not
        waited for."""
        raise NotImplementedError

    def check_step(self, feed):
        """The first step, from the start-up weights: (loss, probe), where
        the probe is the configuration's ``build``'s fourth item fetched from
        the same step, or None where the kind or the configuration has
        none."""
        return self.step(feed), None

    def placement(self):
        """A several-chip kind's proof that feeds and optimizer state are
        spread over the chips: a check dict with ``ok``. None on one chip."""
        return None

    def close(self):
        pass


def stage_ring(ctx):
    """The traffic's ``ring`` seeded batches of ``batch``, made on the device
    by the configuration's ``device_batch``, each with its counts."""
    import jax

    with ctx.spans.span("stage_feeds"):
        ring = [ctx.model.device_batch(ctx.cfg, ctx.seed, i,
                                       int(ctx.traffic["batch"]), ctx.traffic)
                for i in range(int(ctx.traffic["ring"]))]
        jax.block_until_ready(ring)
    return [(f, feed_info(ctx.model, ctx.cfg, f)) for f in ring]


def executor_check_step(prog, feed):
    """``executor_step`` that also fetches the configuration's probe (one
    more compiled program, used once; a configuration without one takes the
    window's own program)."""
    if prog.probe is None:
        return executor_step(prog, feed), None
    loss, probe = prog.exe.run(prog.main, feed=feed,
                               fetch_list=[prog.loss, prog.probe],
                               scope=prog.scope, return_numpy=False)
    return loss, probe


def executor_step(prog, feed):
    """One ``Executor.run`` of the train program, as a user calls it; the
    loss stays on the device and is not waited for."""
    (loss,) = prog.exe.run(prog.main, feed=feed, fetch_list=[prog.loss],
                           scope=prog.scope, return_numpy=False)
    return loss
