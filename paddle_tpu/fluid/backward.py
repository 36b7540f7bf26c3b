"""IR-level reverse-mode autodiff: ``append_backward``.

Reference: /root/reference/python/paddle/fluid/backward.py:425
(append_backward) — walk the block's ops in reverse from the loss, ask each
op's grad maker for grad op descs (the C++ GradOpDescMaker contract,
core.get_grad_op_desc there; core/registry.py OpInfo.grad here), de-duplicate
repeated output grads by summation (_addup_repetitive_outputs_ backward.py:117),
and prune branches that don't reach the loss (backward.py:167).

The produced grad ops live in the SAME program block, so under the compiling
Executor forward+backward fuse into one XLA computation. Unreachable grads
(e.g. toward stop_gradient data vars) are appended but dead-code-eliminated by
XLA, mirroring how the reference relies on no-grad pruning.
"""

from __future__ import annotations

import collections

from .framework import (NAMESCOPE_ATTR, Program, Variable, Parameter,
                        grad_var_name, unique_name)
from ..core import registry


def _op_path(block, loss_name, start_idx=None):
    """Indices of ops that contribute to ``loss_name`` (relevance pruning,
    reference backward.py _op_path / no-grad pruning)."""
    needed = {loss_name}
    path = []
    ops = block.ops if start_idx is None else block.ops[:start_idx]
    for i in reversed(range(len(ops))):
        op = ops[i]
        if any(o in needed for o in op.output_arg_names()):
            path.append(i)
            needed.update(op.input_arg_names())
    return set(path), needed


def _create_grad_var(block, fwd_name, grad_name):
    if block.has_var_local(grad_name):
        return block.vars[grad_name]
    if block.has_var(fwd_name):
        fv = block.var(fwd_name)
        return block.create_var(name=grad_name, shape=fv.shape, dtype=fv.dtype,
                                lod_level=fv.lod_level)
    return block.create_var(name=grad_name)


def append_backward(loss, parameter_list=None, no_grad_set=None):
    """Append grad ops for ``loss`` to its program; returns [(param, grad_var)].

    Matches the reference signature (backward.py:425). ``loss`` must be a
    scalar (shape () or (1,)) variable in the root block.
    """
    assert isinstance(loss, Variable)
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())

    # d(loss)/d(loss) = 1
    loss_grad = grad_var_name(loss.name)
    _create_grad_var(block, loss.name, loss_grad)
    block.append_op(
        "fill_constant",
        outputs={"Out": [loss_grad]},
        attrs={"shape": list(loss.shape or ()), "value": 1.0,
               "dtype": loss.dtype or "float32"})

    path, needed = _op_path(block, loss.name, start_idx=len(block.ops) - 1)

    # which forward vars should receive gradients
    stop = {name for name, v in block.vars.items() if v.stop_gradient}
    stop |= no_grad

    produced = {loss_grad}  # grad names already written by appended grad ops

    # var names that (transitively) depend on a trainable parameter — used to
    # detect silent gradient-chain cuts at ops with no grad maker
    derived = {p.name for p in program.global_block().all_parameters()
               if p.trainable} - stop
    for i in sorted(path):
        op = block.ops[i]
        if any(n in derived for n in op.input_arg_names()):
            # stop_gradient vars cut the chain deliberately — don't let the
            # no-grad-maker guard fire past an explicit stop
            derived.update(n for n in op.output_arg_names() if n not in stop)

    for i in reversed(sorted(path)):
        op = block.ops[i]
        info = registry.get_op_info(op.type)
        outs = op.output_arg_names()
        # ops whose EVERY output is an explicit stop_gradient var are
        # pruned outright (the reference's no-grad-set pruning,
        # backward.py _find_no_grad_vars): their upstream chain — e.g. the
        # ssd_loss mining-weight path — must not demand grad makers
        if outs and all(n in stop and not _is_param(block, n)
                        for n in outs):
            continue
        # skip if none of this op's outputs have a live upstream gradient
        out_grads = [grad_var_name(n) for n in outs]
        if not any(g in produced for g in out_grads):
            continue
        if info.grad is None:
            # An op on the needed path with live output grads but no grad
            # maker silently cuts the gradient chain — upstream parameters
            # would be dropped from the (param, grad) list and never train.
            # The reference errors in core.get_grad_op_desc for such ops;
            # fail loudly unless the op genuinely has no trainable inputs.
            if any(n in derived for n in op.input_arg_names()):
                raise RuntimeError(
                    f"op {op.type!r} (#{i} in block {block.idx}) lies on the "
                    f"gradient path of {loss.name!r} but registers no grad "
                    "maker; parameters feeding it would silently stop "
                    "training. Use a differentiable formulation (e.g. "
                    "dynamic_lstm/StaticRNN instead of an inference-only "
                    "While) or mark its inputs stop_gradient=True.")
            continue
        specs = info.grad(op)
        # a tagged op's grad ops belong to its module (fluid.name_scope)
        tag = ({NAMESCOPE_ATTR: op.attrs[NAMESCOPE_ATTR]}
               if NAMESCOPE_ATTR in op.attrs else {})
        # outputs whose grad was never produced (unused forward outputs, e.g.
        # softmax_with_cross_entropy's Softmax when only Loss is used): feed
        # zeros, mirroring the reference's fill_zeros_like insertion
        # (backward.py _append_backward_ops_) — but only for grads some grad
        # spec actually CONSUMES (a zero-fill nothing reads is dead work the
        # PTL101 dead-op lint would rightly flag)
        spec_inputs = {n for spec in specs
                       for names in spec.inputs.values() for n in names}
        for slot, names in op.outputs.items():
            for n in names:
                g = grad_var_name(n)
                if g not in produced and g in spec_inputs:
                    _create_grad_var(block, n, g)
                    block.append_op("fill_zeros_like",
                                    inputs={"X": [n]}, outputs={"Out": [g]},
                                    attrs=dict(tag))
                    produced.add(g)

        for spec in specs:
            # rename-and-sum for repeated gradients (backward.py:117);
            # overwrite_outputs specs (in-place loop state) replace instead
            renames = []  # (canonical, tmp) pairs, possibly repeated names
            spec_seen = set()  # duplicate grad names WITHIN one spec (the
            # x*x pattern: X@GRAD and Y@GRAD are the same var) must also
            # rename-and-sum, else the later slot overwrites the earlier
            for slot, names in spec.outputs.items():
                new_names = []
                for n in names:
                    fwd = n[: -len("@GRAD")] if n.endswith("@GRAD") else n
                    if fwd in stop and not _is_param(block, fwd):
                        # still produce it (XLA DCEs it); cheaper than
                        # rewriting the grad op's outputs
                        pass
                    if ((n in produced or n in spec_seen)
                            and slot not in spec.overwrite_slots):
                        tmp = unique_name(n + "@RENAME")
                        _create_grad_var(block, fwd, tmp)
                        renames.append((n, tmp))
                        new_names.append(tmp)
                    else:
                        _create_grad_var(block, fwd, n)
                        new_names.append(n)
                    spec_seen.add(n)
                spec.outputs[slot] = new_names
            block.append_op(spec.type, spec.inputs, spec.outputs,
                            {**spec.attrs, **tag})
            for slot, names in spec.outputs.items():
                for n in names:
                    produced.add(n)
            # accumulate renamed grads into the canonical name
            for canonical, tmp in renames:
                block.append_op("sum", inputs={"X": [canonical, tmp]},
                                outputs={"Out": [canonical]},
                                attrs=dict(tag))

    # collect (param, grad) pairs
    if parameter_list is not None:
        params = [block.var(p) if isinstance(p, str) else p
                  for p in parameter_list]
    else:
        params = [p for p in program.global_block().all_parameters()
                  if p.trainable]
    result = []
    for p in params:
        g = grad_var_name(p.name)
        if g in produced:
            result.append((p, block.var(g)))

    # verify_passes: the appended-grad program must still be structurally
    # valid (fluid/analysis; raises ProgramVerifyError naming this pass)
    from .analysis import verify_pass_output
    verify_pass_output(program, "append_backward")
    return result


def _is_param(block, name):
    try:
        return isinstance(block.var(name), Parameter)
    except KeyError:
        return False
