"""paddle_trainer — the legacy trainer CLI over v2 configs.

Reference: /root/reference/paddle/trainer/TrainerMain.cpp:24-61 — one binary
with ``--config=<v2 config.py>`` and ``--job`` one of train / test /
checkgrad / time, plus --config_args k=v overrides. Here the config is
parsed by v2.parse_config (the same DSL the reference compiles to a
ModelConfig) and the jobs run on the fluid executor:

    python -m paddle_tpu.v2.trainer_cli --config=rnn.py \
        --config_args=batch_size=8,hidden_size=16 --job=train --num_passes=2

Data comes from ``--reader module:callable`` (a reader creator returning
batches of per-layer tuples) or, absent that, a deterministic synthetic
feed generator derived from the config's data layers — the stand-in for
the reference's PyDataProvider2 protocol.

The checkgrad job ports Trainer::checkGradient (Trainer.cpp:315-377):
perturb each parameter along its (noised) gradient direction with a step
sized so the analytic directional delta is ``eps * cost``, then compare
the central finite difference of the cost against the analytic delta.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import time

import numpy as np


from .config_helpers import parse_config_args as _parse_config_args


def _synthetic_reader(topo, batch_size, batches, seed=7):
    """Deterministic feeds shaped by the config's data layers: dense floats
    ~N(0,1); int64 label ids uniform in [0, layer_size); id sequences of
    random length 3..12."""
    layers = [d for d in topo.data_layers if not d.is_pending]
    rng = np.random.RandomState(seed)

    def reader():
        for _ in range(batches):
            rows = []
            for _ in range(batch_size):
                row = []
                for d in layers:
                    v = d._var
                    if v.lod_level > 0 and v.dtype == "int64":
                        ln = int(rng.randint(3, 13))
                        row.append(rng.randint(0, max(d._data_size, 2),
                                               (ln, 1)).astype("int64"))
                    elif v.lod_level > 0:
                        ln = int(rng.randint(3, 13))
                        row.append(rng.normal(
                            0, 1, (ln, d._data_size)).astype("float32"))
                    elif v.dtype == "int64":
                        row.append([int(rng.randint(
                            0, max(d._data_size, 2)))])
                    else:
                        row.append(rng.normal(
                            0, 1, d._data_size).astype("float32"))
                rows.append(tuple(row))
            yield rows

    return reader


def job_checkgrad(topo, main, startup, args):
    """Directional gradient check per parameter (Trainer.cpp:315-377)."""
    import paddle_tpu.fluid as fluid

    with fluid.program_guard(main, startup):
        fluid.append_backward(topo.cost)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)

    params = [p.name for p in main.all_parameters()]
    reader = _make_reader(topo, args, batches=1)
    batch = next(iter(reader()))
    trainer = _make_sgd(topo, main, startup, scope_exe=(scope, exe))
    feed = trainer._feed(batch)

    # snapshot params, fetch cost+grads once, restore: the main program
    # contains the optimizer update ops and must not move the params the
    # finite differences are taken around
    snapshot = {p: np.asarray(scope.find_var(p)).copy() for p in params}
    fetch = [topo.cost] + [fluid.grad_var_name(p) for p in params]
    vals = exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
    cost = float(np.asarray(vals[0]))
    grad_map = {p: np.asarray(g, dtype=np.float64)
                for p, g in zip(params, vals[1:])}
    for p, v in snapshot.items():
        scope.set(p, v)

    # cost evaluations run the FORWARD slice only (no updates)
    from paddle_tpu.fluid.io import _prune_program
    cost_name = topo.cost if isinstance(topo.cost, str) else topo.cost.name
    fwd_prog = _prune_program(main, [d.name for d in topo.data_layers
                                     if not d.is_pending], [cost_name])

    rng = np.random.RandomState(11)
    eps = args.checkgrad_eps
    max_diff, failed = 0.0, []
    for p in params:
        g = grad_map[p].reshape(-1)
        d = g + 0.1 * np.abs(g).mean() * rng.normal(size=g.shape)
        delta = float(g @ d)
        step = (cost / delta * eps) if delta != 0 else eps
        old = np.asarray(scope.find_var(p)).copy()

        def cost_at(vec):
            scope.set(p, vec.reshape(old.shape).astype(old.dtype))
            v, = exe.run(fwd_prog, feed=feed, fetch_list=[cost_name],
                         scope=scope)
            return float(np.asarray(v))

        c1 = cost_at(old.reshape(-1) + step * d)
        c2 = cost_at(old.reshape(-1) - step * d)
        scope.set(p, old)
        true_delta = 0.5 * (c1 - c2)
        diff = (1e-20 + true_delta) / (1e-20 + delta * step) - 1
        flag = " ***" if abs(diff) > 0.01 else ""
        print(f"{p:24s} step={step:<12.4e} cost1={c1:<12.6f} "
              f"cost2={c2:<12.6f} true_delta={true_delta:<12.4e} "
              f"analytic_delta={delta * step:<12.4e} diff={diff:.6f}{flag}")
        max_diff = max(max_diff, abs(diff))
        if abs(diff) > 0.01:
            failed.append(p)
    print(f"checkgrad max diff: {max_diff:.6f}")
    return 1 if failed else 0


def _provider_reader(topo, is_train=True):
    """When the config declared define_py_data_sources2(module=..., obj=...),
    load the @provider-decorated function and bind it as the reader
    (reference PyDataProvider2 path: the C++ trainer pulled batches through
    the provider; here it IS the reader)."""
    src = topo.data_sources or {}
    module, obj = src.get("module"), src.get("obj")
    if not (module and obj):
        return None
    file_list = src.get("train_list" if is_train else "test_list")
    if file_list is None:
        return None
    if isinstance(file_list, str):
        # the reference contract: train_list/test_list name a LIST FILE of
        # data filenames (trainer config_parser); a missing list file is a
        # config error, not a data file
        if not os.path.exists(file_list):
            raise FileNotFoundError(
                f"data source list file not found: {file_list!r}")
        with open(file_list) as f:
            file_list = [ln.strip() for ln in f if ln.strip()]
    provider_cls = getattr(importlib.import_module(module), obj)
    return provider_cls(file_list, input_order=topo.feed_order,
                        is_train=is_train, **(src.get("args") or {}))


def _make_reader(topo, args, batches=None, is_train=True):
    if args.reader:
        mod, _, fn = args.reader.partition(":")
        return getattr(importlib.import_module(mod), fn)()
    from_provider = _provider_reader(topo, is_train=is_train)
    if from_provider is not None:
        # providers yield samples; the CLI reader contract is batch-level
        from ..reader.minibatch import batch
        return batch(from_provider,
                     int(topo.settings.get("batch_size") or 16))
    bs = topo.settings.get("batch_size") or 16
    return _synthetic_reader(topo, int(bs),
                             batches or args.batches_per_pass)


def _make_sgd(topo, main, startup, scope_exe=None):
    import paddle_tpu.fluid as fluid
    import paddle_tpu.v2 as v2

    with fluid.program_guard(main, startup):
        return v2.SGD(cost=topo.cost, optimizer=topo.create_optimizer(),
                      feed_order=topo.feed_order, main_program=main,
                      startup_program=startup) if scope_exe is None \
            else _FeedOnly(topo, main)


class _FeedOnly:
    """Feed-building shim for jobs that drive the executor directly."""

    def __init__(self, topo, main):
        self._feed_order = topo.feed_order
        self._main = main

    def _feed(self, data_batch):
        import paddle_tpu.v2.trainer as t
        return t.SGD._feed(self, data_batch)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="paddle_trainer")
    ap.add_argument("--config", required=True)
    ap.add_argument("--config_args", default="")
    ap.add_argument("--job", default="train",
                    choices=["train", "test", "checkgrad", "time", "merge"])
    ap.add_argument("--model_dir", default=None,
                    help="merge job: output dir for the self-contained "
                         "inference artifact (the reference MergeModel "
                         "capability, paddle/trainer/MergeModel.cpp)")
    ap.add_argument("--num_passes", type=int, default=1)
    ap.add_argument("--batches_per_pass", type=int, default=8)
    ap.add_argument("--reader", default=None,
                    help="module:reader_creator for real data")
    ap.add_argument("--checkgrad_eps", type=float, default=1e-4)
    ap.add_argument("--sequence_inputs", default="",
                    help="comma-separated data-layer names fed as "
                         "sequences (the data-provider knowledge the "
                         "reference supplies at runtime)")
    args = ap.parse_args(argv)

    from .config_helpers import parse_config
    topo, main_prog, startup = parse_config(
        args.config, config_args=_parse_config_args(args.config_args),
        sequence_inputs=tuple(n for n in args.sequence_inputs.split(",")
                              if n))

    if args.job == "checkgrad":
        return job_checkgrad(topo, main_prog, startup, args)

    if args.job == "merge":
        # MergeModel analog: one self-contained deployable artifact
        # (config + trained params) consumable by paddle_tpu/capi —
        # the reference merges ModelConfig + params for its C API
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import aot

        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        out_var = topo.outputs[-1]
        out_name = out_var.var.name if hasattr(out_var, "var") else out_var
        # feeds = only the data layers the pruned output slice reads; an
        # inference config (is_infer outputs(net), the reference MergeModel
        # use) needs no label feed — a cost output honestly still does
        from paddle_tpu.fluid.io import _prune_program
        from paddle_tpu.core.block_walk import free_reads
        declared = [d.name for d in topo.data_layers if not d.is_pending]
        pruned = _prune_program(main_prog, declared, [out_name])
        free = free_reads(pruned, 0)
        feeds = [n for n in declared if n in free]
        if set(declared) - set(feeds):
            print("note: data layers not reachable from the merged output "
                  f"were dropped from the feed list: "
                  f"{sorted(set(declared) - set(feeds))}")
        aot.export_inference_artifact(args.model_dir or "merged_model",
                                      feeds, [out_name], exe,
                                      main_program=main_prog, scope=scope)
        print(f"merged model -> {args.model_dir or 'merged_model'} "
              f"(output {out_name!r}, feeds {feeds})")
        return 0

    import paddle_tpu.fluid as fluid
    import paddle_tpu.v2 as v2

    with fluid.program_guard(main_prog, startup):
        trainer = v2.SGD(cost=topo.cost, optimizer=topo.create_optimizer(),
                         feed_order=topo.feed_order,
                         main_program=main_prog, startup_program=startup)
    reader = _make_reader(topo, args, is_train=args.job != "test")

    if args.job == "train":
        def handler(evt):
            if isinstance(evt, v2.event.EndPass):
                print(f"Pass {evt.pass_id}: cost={evt.metrics['cost']:.6f}")

        trainer.train(reader, num_passes=args.num_passes,
                      event_handler=handler)
        return 0
    if args.job == "test":
        metrics = trainer.test(reader)
        print(f"Test: {metrics}")
        return 0
    if args.job == "time":
        batches = list(reader())
        t0 = time.perf_counter()
        trainer.train(lambda: iter(batches), num_passes=1,
                      event_handler=lambda e: None)
        dt = (time.perf_counter() - t0) / max(len(batches), 1)
        print(f"time: {dt * 1e3:.3f} ms/batch over {len(batches)} batches")
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())
