"""Neural-network layer functions.

Reference: /root/reference/python/paddle/fluid/layers/nn.py (~80 layer
functions, each appending ops via LayerHelper.append_op — layer_helper.py:44).
This module follows the same calling conventions (input, size, act, param_attr,
bias_attr, ...) so reference model scripts port line-for-line, but the appended
ops lower to fused XLA rather than per-kernel dispatch.
"""

from __future__ import annotations

import copy

import numpy as np

from ..framework import Variable, unique_name
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from ..initializer import Constant, Mapped, Normal, Uniform, Xavier
from . import ops, tensor


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully connected layer (reference nn.py fc): mul per input + sum +
    bias + activation. MXU path: each mul is one big jnp.dot."""
    helper = LayerHelper("fc", name=name, act=act, bias_attr=bias_attr)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_results = []
    for inp in inputs:
        in_shape = inp.shape
        flat_dim = int(np.prod(in_shape[num_flatten_dims:]))
        w = helper.create_parameter(param_attr, shape=(flat_dim, size),
                                    dtype=inp.dtype)
        out = helper.create_tmp_variable(
            inp.dtype, shape=tuple(in_shape[:num_flatten_dims]) + (size,),
            lod_level=inp.lod_level)
        helper.append_op("mul", inputs={"X": [inp.name], "Y": [w.name]},
                         outputs={"Out": [out.name]},
                         attrs={"x_num_col_dims": num_flatten_dims,
                                "y_num_col_dims": 1})
        mul_results.append(out)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(
            mul_results[0].dtype, shape=mul_results[0].shape,
            lod_level=mul_results[0].lod_level)
        helper.append_op("sum", inputs={"X": [m.name for m in mul_results]},
                         outputs={"Out": [pre_bias.name]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, padding_idx=None, param_attr=None,
              dtype="float32"):
    """Embedding lookup (reference nn.py embedding -> lookup_table op)."""
    helper = LayerHelper("embedding")
    w = helper.create_parameter(param_attr, shape=tuple(size), dtype=dtype,
                                default_initializer=Xavier())
    out_shape = None
    if input.shape is not None:
        out_shape = tuple(input.shape[:-1] or input.shape) + (size[1],)
    out = helper.create_tmp_variable(dtype, shape=out_shape,
                                     lod_level=input.lod_level)
    helper.append_op("lookup_table",
                     inputs={"W": [w.name], "Ids": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"is_sparse": is_sparse,
                            "padding_idx": padding_idx})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_tmp_variable(x.dtype, shape=x.shape,
                                     lod_level=x.lod_level)
    mask = helper.create_tmp_variable(x.dtype, shape=x.shape,
                                      lod_level=x.lod_level,
                                      stop_gradient=True)
    helper.append_op("dropout", inputs={"X": [x.name]},
                     outputs={"Out": [out.name], "Mask": [mask.name]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "seed": seed or 0})
    return out


def softmax(input, name=None):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_tmp_variable(input.dtype, shape=input.shape,
                                     lod_level=input.lod_level)
    helper.append_op("softmax", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]})
    return out


def cross_entropy(input, label, soft_label=False):
    """reference nn.py cross_entropy -> cross_entropy op."""
    helper = LayerHelper("cross_entropy")
    out = helper.create_tmp_variable(
        input.dtype, shape=tuple(input.shape[:-1]) + (1,))
    helper.append_op("cross_entropy",
                     inputs={"X": [input.name], "Label": [label.name]},
                     outputs={"Y": [out.name]},
                     attrs={"soft_label": soft_label})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_tmp_variable(logits.dtype, shape=logits.shape)
    loss = helper.create_tmp_variable(
        logits.dtype, shape=tuple(logits.shape[:-1]) + (1,))
    helper.append_op("softmax_with_cross_entropy",
                     inputs={"Logits": [logits.name], "Label": [label.name]},
                     outputs={"Softmax": [softmax_out.name],
                              "Loss": [loss.name]},
                     attrs={"soft_label": soft_label})
    return loss


def square_error_cost(input, label):
    """(input - label)^2 via sub + square ops (reference layers/nn.py
    square_error_cost builds exactly these two ops)."""
    helper = LayerHelper("square_error_cost")
    minus_out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("elementwise_sub",
                     inputs={"X": [input.name], "Y": [label.name]},
                     outputs={"Out": [minus_out.name]})
    sq = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("square", inputs={"X": [minus_out.name]},
                     outputs={"Out": [sq.name]})
    return sq


def sigmoid_cross_entropy_with_logits(x, label):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits")
    out = helper.create_tmp_variable(x.dtype, shape=x.shape)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x.name], "Label": [label.name]},
                     outputs={"Out": [out.name]})
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_tmp_variable(x.dtype, shape=())
    helper.append_op("mean", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]})
    return out


def accuracy(input, label, k=1, correct=None, total=None):
    """reference layers/nn.py accuracy: top_k + accuracy ops."""
    helper = LayerHelper("accuracy")
    topk_out = helper.create_tmp_variable(input.dtype,
                                          shape=tuple(input.shape[:-1]) + (k,),
                                          stop_gradient=True)
    topk_indices = helper.create_tmp_variable(
        "int64", shape=tuple(input.shape[:-1]) + (k,), stop_gradient=True)
    helper.append_op("top_k", inputs={"X": [input.name]},
                     outputs={"Out": [topk_out.name],
                              "Indices": [topk_indices.name]},
                     attrs={"k": k})
    acc_out = helper.create_tmp_variable("float32", shape=(),
                                         stop_gradient=True)
    correct = correct or helper.create_tmp_variable("int32", shape=(),
                                                    stop_gradient=True)
    total = total or helper.create_tmp_variable("int32", shape=(),
                                                stop_gradient=True)
    helper.append_op("accuracy",
                     inputs={"Out": [topk_out.name],
                             "Indices": [topk_indices.name],
                             "Label": [label.name]},
                     outputs={"Accuracy": [acc_out.name],
                              "Correct": [correct.name],
                              "Total": [total.name]})
    return acc_out


def topk(input, k):
    helper = LayerHelper("top_k")
    values = helper.create_tmp_variable(input.dtype,
                                        shape=tuple(input.shape[:-1]) + (k,))
    indices = helper.create_tmp_variable(
        "int64", shape=tuple(input.shape[:-1]) + (k,))
    helper.append_op("top_k", inputs={"X": [input.name]},
                     outputs={"Out": [values.name],
                              "Indices": [indices.name]},
                     attrs={"k": k})
    return values, indices


def _elementwise_binary(x, other, op_type, reverse=False):
    """Implements Variable operator sugar (+-*/) like the reference's
    math_op_patch.py: scalars become scale ops / fill_constant."""
    helper = LayerHelper(op_type)
    if isinstance(other, (int, float)):
        if op_type == "elementwise_add":
            out = helper.create_tmp_variable(x.dtype, shape=x.shape,
                                             lod_level=x.lod_level)
            helper.append_op("scale", inputs={"X": [x.name]},
                             outputs={"Out": [out.name]},
                             attrs={"scale": 1.0, "bias": float(other)})
            return out
        if op_type == "elementwise_mul":
            out = helper.create_tmp_variable(x.dtype, shape=x.shape,
                                             lod_level=x.lod_level)
            helper.append_op("scale", inputs={"X": [x.name]},
                             outputs={"Out": [out.name]},
                             attrs={"scale": float(other)})
            return out
        const = helper.create_tmp_variable(x.dtype, shape=x.shape)
        helper.append_op("fill_constant_batch_size_like",
                         inputs={"Input": [x.name]},
                         outputs={"Out": [const.name]},
                         attrs={"shape": list(x.shape or (1,)),
                                "value": float(other), "dtype": x.dtype})
        other = const
    a, b = (other, x) if reverse else (x, other)
    out = helper.create_tmp_variable(a.dtype, shape=a.shape,
                                     lod_level=a.lod_level)
    helper.append_op(op_type, inputs={"X": [a.name], "Y": [b.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": -1})
    return out


def elementwise_add(x, y, axis=-1, act=None):
    return _elementwise_generic("elementwise_add", x, y, axis, act)


def elementwise_sub(x, y, axis=-1, act=None):
    return _elementwise_generic("elementwise_sub", x, y, axis, act)


def elementwise_mul(x, y, axis=-1, act=None):
    return _elementwise_generic("elementwise_mul", x, y, axis, act)


def elementwise_div(x, y, axis=-1, act=None):
    return _elementwise_generic("elementwise_div", x, y, axis, act)


def elementwise_max(x, y, axis=-1, act=None):
    return _elementwise_generic("elementwise_max", x, y, axis, act)


def elementwise_min(x, y, axis=-1, act=None):
    return _elementwise_generic("elementwise_min", x, y, axis, act)


def elementwise_pow(x, y, axis=-1, act=None):
    return _elementwise_generic("elementwise_pow", x, y, axis, act)


def _elementwise_generic(op_type, x, y, axis, act):
    helper = LayerHelper(op_type, act=act)
    out = helper.create_tmp_variable(x.dtype, shape=x.shape,
                                     lod_level=x.lod_level)
    helper.append_op(op_type, inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": axis})
    return helper.append_activation(out)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1):
    helper = LayerHelper("mul")
    out_shape = tuple(x.shape[:x_num_col_dims]) + tuple(y.shape[y_num_col_dims:])
    out = helper.create_tmp_variable(x.dtype, shape=out_shape)
    helper.append_op("mul", inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [int(v), int(v)]


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    """Conv layer (reference nn.py conv2d → conv2d op, NCHW/MCHW). The
    use_cudnn flag is accepted for source compatibility and ignored — there is
    one XLA lowering. ``data_format="NHWC"`` is a TPU-native extension:
    channels land in the TPU lane dimension so BN reductions and elementwise
    tiles align (the filter stays MCHW for checkpoint parity)."""
    helper = LayerHelper("conv2d", name=name, act=act, bias_attr=bias_attr)
    c_in = input.shape[-1] if data_format == "NHWC" else input.shape[1]
    groups = groups or 1
    fs = _pair(filter_size)
    w = helper.create_parameter(
        param_attr, shape=(num_filters, c_in // groups, fs[0], fs[1]),
        dtype=input.dtype,
        default_initializer=Normal(0.0, (2.0 / (fs[0] * fs[1] * c_in)) ** 0.5))
    attrs = {"strides": _pair(stride), "paddings": _pair(padding),
             "dilations": _pair(dilation), "groups": groups,
             "data_format": data_format}
    pre_bias = helper.create_tmp_variable(input.dtype)
    helper.append_op("conv2d",
                     inputs={"Input": [input.name], "Filter": [w.name]},
                     outputs={"Output": [pre_bias.name]}, attrs=attrs)
    pre_act = _append_channel_bias(helper, pre_bias, num_filters, bias_attr,
                                   data_format)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, use_cudnn=True, act=None, name=None):
    """reference nn.py conv2d_transpose → conv2d_transpose op; filter layout
    [C_in, num_filters, kh, kw] (conv_transpose_op.cc)."""
    helper = LayerHelper("conv2d_transpose", name=name, act=act,
                         bias_attr=bias_attr)
    c_in = input.shape[1]
    stride, padding, dilation = _pair(stride), _pair(padding), _pair(dilation)
    if filter_size is None:
        # derive from requested output size (reference nn.py:…)
        h, w_ = input.shape[2], input.shape[3]
        oh, ow = _pair(output_size)
        filter_size = [oh - (h - 1) * stride[0] + 2 * padding[0],
                       ow - (w_ - 1) * stride[1] + 2 * padding[1]]
    fs = _pair(filter_size)
    w = helper.create_parameter(param_attr,
                                shape=(c_in, num_filters, fs[0], fs[1]),
                                dtype=input.dtype)
    pre_bias = helper.create_tmp_variable(input.dtype)
    helper.append_op("conv2d_transpose",
                     inputs={"Input": [input.name], "Filter": [w.name]},
                     outputs={"Output": [pre_bias.name]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation})
    pre_act = _append_channel_bias(helper, pre_bias, num_filters, bias_attr)
    return helper.append_activation(pre_act)


def _append_channel_bias(helper, pre_bias, num_channels, bias_attr,
                         data_format="NCHW"):
    """Per-output-channel bias broadcast along the channel dim (the reference
    conv layers' append_bias_op(dim_start=1, dim_end=2); channel dim is last
    under the NHWC extension)."""
    if bias_attr is False:
        return pre_bias
    axis = -1 if data_format == "NHWC" else 1
    b = helper.create_parameter(ParamAttr.to_attr(bias_attr),
                                shape=(num_channels,),
                                dtype=pre_bias.dtype, is_bias=True)
    out = helper.create_tmp_variable(pre_bias.dtype, shape=pre_bias.shape)
    helper.append_op("elementwise_add",
                     inputs={"X": [pre_bias.name], "Y": [b.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": axis})
    return out


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False, name=None,
           data_format="NCHW"):
    if pool_type not in ("max", "avg"):
        raise ValueError(f"pool_type must be max|avg, got {pool_type!r}")
    if not global_pooling and (pool_size == -1 or pool_size is None):
        raise ValueError(
            "pool_size must be set when global_pooling is False")
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("pool2d", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"pooling_type": pool_type,
                            "ksize": _pair(pool_size),
                            "strides": _pair(pool_stride),
                            "paddings": _pair(pool_padding),
                            "global_pooling": global_pooling,
                            "ceil_mode": ceil_mode,
                            "data_format": data_format})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None):
    """reference nn.py batch_norm → batch_norm op. Running mean/variance are
    non-trainable parameters so they checkpoint with the model; MeanOut /
    VarianceOut write back in place (batch_norm_op.cc reuses the Mean /
    Variance vars) which under the compiling executor is a state rebind."""
    helper = LayerHelper("batch_norm", name=name, act=act)
    c = input.shape[-1] if data_layout == "NHWC" else input.shape[1]

    scale = helper.create_parameter(ParamAttr.to_attr(param_attr), shape=(c,),
                                    dtype=input.dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(ParamAttr.to_attr(bias_attr), shape=(c,),
                                   dtype=input.dtype, is_bias=True)
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, trainable=False), shape=(c,),
        dtype=input.dtype, default_initializer=Constant(0.0))
    variance = helper.create_parameter(
        ParamAttr(name=moving_variance_name, trainable=False), shape=(c,),
        dtype=input.dtype, default_initializer=Constant(1.0))

    saved_mean = helper.create_tmp_variable(input.dtype, shape=(c,),
                                            stop_gradient=True)
    saved_var = helper.create_tmp_variable(input.dtype, shape=(c,),
                                           stop_gradient=True)
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("batch_norm",
                     inputs={"X": [input.name], "Scale": [scale.name],
                             "Bias": [bias.name], "Mean": [mean.name],
                             "Variance": [variance.name]},
                     outputs={"Y": [out.name], "MeanOut": [mean.name],
                              "VarianceOut": [variance.name],
                              "SavedMean": [saved_mean.name],
                              "SavedVariance": [saved_var.name]},
                     attrs={"momentum": momentum, "epsilon": epsilon,
                            "is_test": is_test, "data_layout": data_layout})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    """reference nn.py layer_norm → layer_norm op."""
    helper = LayerHelper("layer_norm", name=name, act=act)
    norm_dim = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": [input.name]}
    if scale:
        s = helper.create_parameter(ParamAttr.to_attr(param_attr),
                                    shape=(norm_dim,), dtype=input.dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s.name]
    if shift:
        b = helper.create_parameter(ParamAttr.to_attr(bias_attr),
                                    shape=(norm_dim,), dtype=input.dtype,
                                    is_bias=True)
        inputs["Bias"] = [b.name]
    mean = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    var = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("layer_norm", inputs=inputs,
                     outputs={"Y": [out.name], "Mean": [mean.name],
                              "Variance": [var.name]},
                     attrs={"begin_norm_axis": begin_norm_axis,
                            "epsilon": epsilon})
    return helper.append_activation(out)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    mid = helper.create_tmp_variable(input.dtype, shape=input.shape,
                                     stop_gradient=True)
    helper.append_op("lrn", inputs={"X": [input.name]},
                     outputs={"Out": [out.name], "MidOut": [mid.name]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def linear_chain_crf(input, label, param_attr=None):
    """CRF negative log-likelihood cost (reference nn.py linear_chain_crf).
    The transition parameter is [num_tags + 2, num_tags] (row 0 start, row 1
    end scores, linear_chain_crf_op.cc)."""
    helper = LayerHelper("linear_chain_crf")
    size = input.shape[-1]
    transition = helper.create_parameter(param_attr, shape=(size + 2, size),
                                         dtype=input.dtype)
    log_likelihood = helper.create_tmp_variable(input.dtype)
    helper.append_op("linear_chain_crf",
                     inputs={"Emission": [input.name],
                             "Transition": [transition.name],
                             "Label": [label.name]},
                     outputs={"LogLikelihood": [log_likelihood.name]})
    return log_likelihood


def crf_decoding(input, param_attr, label=None):
    helper = LayerHelper("crf_decoding")
    transition = helper.create_parameter(
        ParamAttr.to_attr(param_attr), shape=(input.shape[-1] + 2,
                                              input.shape[-1]),
        dtype=input.dtype)
    path = helper.create_tmp_variable("int64", lod_level=1)
    inputs = {"Emission": [input.name], "Transition": [transition.name]}
    if label is not None:
        inputs["Label"] = [label.name]
    helper.append_op("crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [path.name]})
    return path


def warpctc(input, label, blank=0, norm_by_times=False):
    """CTC loss over ragged logits/labels (reference nn.py warpctc →
    warpctc_op dynloading warp-ctc; here a native XLA scan)."""
    helper = LayerHelper("warpctc")
    loss = helper.create_tmp_variable(input.dtype)
    helper.append_op("warpctc",
                     inputs={"Logits": [input.name], "Label": [label.name]},
                     outputs={"Loss": [loss.name]},
                     attrs={"blank": blank, "norm_by_times": norm_by_times})
    return loss


def ctc_greedy_decoder(input, blank):
    """argmax per step then merge/strip (reference nn.py ctc_greedy_decoder =
    top_k + ctc_align)."""
    helper = LayerHelper("ctc_greedy_decoder")
    _, indices = topk(input, k=1)
    out = helper.create_tmp_variable("int64", lod_level=1)
    helper.append_op("ctc_align", inputs={"Input": [indices.name]},
                     outputs={"Output": [out.name]},
                     attrs={"blank": blank, "merge_repeated": True})
    return out


def edit_distance(input, label, normalized=False, ignored_tokens=None):
    helper = LayerHelper("edit_distance")
    if ignored_tokens:
        erased = helper.create_tmp_variable(input.dtype, lod_level=1)
        helper.append_op("sequence_erase", inputs={"X": [input.name]},
                         outputs={"Out": [erased.name]},
                         attrs={"tokens": list(ignored_tokens)})
        input = erased
        erased_l = helper.create_tmp_variable(label.dtype, lod_level=1)
        helper.append_op("sequence_erase", inputs={"X": [label.name]},
                         outputs={"Out": [erased_l.name]},
                         attrs={"tokens": list(ignored_tokens)})
        label = erased_l
    out = helper.create_tmp_variable("float32")
    seq_num = helper.create_tmp_variable("int64")
    helper.append_op("edit_distance",
                     inputs={"Hyps": [input.name], "Refs": [label.name]},
                     outputs={"Out": [out.name],
                              "SequenceNum": [seq_num.name]},
                     attrs={"normalized": normalized})
    return out, seq_num


def cos_sim(X, Y):
    """Row-wise cosine similarity (reference nn.py cos_sim → cos_sim op)."""
    helper = LayerHelper("cos_sim")
    out_shape = tuple(X.shape[:-1]) + (1,) if X.shape is not None else None
    out = helper.create_tmp_variable(X.dtype, shape=out_shape)
    helper.append_op("cos_sim", inputs={"X": [X.name], "Y": [Y.name]},
                     outputs={"Out": [out.name]})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, name=None):
    helper = LayerHelper("matmul", name=name)
    xs = list(x.shape)
    ys = list(y.shape)
    if transpose_x:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    out_shape = tuple(xs[:-1] + ys[-1:])
    out = helper.create_tmp_variable(x.dtype, shape=out_shape)
    helper.append_op("matmul", inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]},
                     attrs={"transpose_X": transpose_x,
                            "transpose_Y": transpose_y})
    return out


# ---------------------------------------------------------------------------
# op-breadth layers (reference layers/nn.py + layers/ops.py wrappers)
# ---------------------------------------------------------------------------

def cumsum(x, axis=-1, exclusive=False, reverse=False, name=None):
    helper = LayerHelper("cumsum", name=name)
    out = helper.create_tmp_variable(x.dtype, shape=x.shape,
                                     lod_level=x.lod_level)
    helper.append_op("cumsum", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]},
                     attrs={"axis": axis, "exclusive": exclusive,
                            "reverse": reverse})
    return out


def prelu(x, param_attr=None, name=None):
    """Scalar-alpha PReLU (reference prelu_op.cc requires numel(Alpha)==1)."""
    helper = LayerHelper("prelu", name=name)
    alpha = helper.create_parameter(ParamAttr.to_attr(param_attr),
                                    shape=(1,), dtype=x.dtype,
                                    default_initializer=Constant(0.25))
    out = helper.create_tmp_variable(x.dtype, shape=x.shape)
    helper.append_op("prelu", inputs={"X": [x.name], "Alpha": [alpha.name]},
                     outputs={"Out": [out.name]})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("maxout", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={"groups": groups})
    return out


def spp(input, pyramid_height, pool_type="max", name=None):
    helper = LayerHelper("spp", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("spp", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"pyramid_height": pyramid_height,
                            "pooling_type": pool_type})
    return out


def max_pool2d_with_index(input, pool_size, pool_stride=None, name=None):
    helper = LayerHelper("max_pool2d_with_index", name=name)
    ks = [pool_size, pool_size] if isinstance(pool_size, int) else pool_size
    st = pool_stride or ks
    st = [st, st] if isinstance(st, int) else st
    out = helper.create_tmp_variable(input.dtype)
    mask = helper.create_tmp_variable("int32", stop_gradient=True)
    helper.append_op("max_pool2d_with_index", inputs={"X": [input.name]},
                     outputs={"Out": [out.name], "Mask": [mask.name]},
                     attrs={"ksize": list(ks), "strides": list(st)})
    return out, mask


def unpool(input, indices, unpooled_size, name=None):
    helper = LayerHelper("unpool", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("unpool",
                     inputs={"X": [input.name], "Indices": [indices.name]},
                     outputs={"Out": [out.name]},
                     attrs={"unpooled_size": list(unpooled_size)})
    return out


def norm(input, param_attr=None, epsilon=1e-10, name=None):
    """Cross-channel L2 normalization with a learned per-channel scale
    (reference norm_op.h, the SSD conv4_3 normalize layer)."""
    helper = LayerHelper("norm", name=name)
    channels = input.shape[1]
    scale = helper.create_parameter(ParamAttr.to_attr(param_attr),
                                    shape=(channels,), dtype=input.dtype,
                                    default_initializer=Constant(1.0))
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("norm",
                     inputs={"X": [input.name], "Scale": [scale.name]},
                     outputs={"Out": [out.name]},
                     attrs={"epsilon": epsilon})
    return out


def im2sequence(input, filter_size, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence", name=name)
    ks = [filter_size, filter_size] if isinstance(filter_size, int) \
        else list(filter_size)
    st = [stride, stride] if isinstance(stride, int) else list(stride)
    pd = [padding] * 4 if isinstance(padding, int) else list(padding)
    if len(pd) == 2:
        pd = [pd[0], pd[1], pd[0], pd[1]]
    # flat-rows LoD shape [-1, c*kh*kw] so downstream fc sees the feature dim
    shape = None
    if input.shape is not None:
        shape = (-1, input.shape[1] * ks[0] * ks[1])
    out = helper.create_tmp_variable(input.dtype, shape=shape, lod_level=1)
    helper.append_op("im2sequence", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"kernels": ks, "strides": st, "paddings": pd})
    return out


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_tmp_variable(left.dtype, shape=left.shape)
    helper.append_op("rank_loss",
                     inputs={"Label": [label.name], "Left": [left.name],
                             "Right": [right.name]},
                     outputs={"Out": [out.name]})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_tmp_variable(left.dtype, shape=left.shape)
    helper.append_op("margin_rank_loss",
                     inputs={"Label": [label.name], "X1": [left.name],
                             "X2": [right.name]},
                     outputs={"Out": [out.name]}, attrs={"margin": margin})
    return out


def bilinear_tensor_product(x, y, size, param_attr=None, bias_attr=None,
                            name=None):
    helper = LayerHelper("bilinear_tensor_product", name=name,
                         bias_attr=bias_attr)
    w = helper.create_parameter(
        ParamAttr.to_attr(param_attr),
        shape=(size, x.shape[-1], y.shape[-1]), dtype=x.dtype,
        default_initializer=Xavier())
    out = helper.create_tmp_variable(x.dtype, shape=(x.shape[0], size))
    inputs = {"X": [x.name], "Y": [y.name], "Weight": [w.name]}
    if bias_attr is not False:
        b = helper.create_parameter(ParamAttr.to_attr(bias_attr),
                                    shape=(size,), dtype=x.dtype,
                                    default_initializer=Constant(0.0))
        inputs["Bias"] = [b.name]
    helper.append_op("bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out.name]})
    return out


def is_empty(x, name=None):
    helper = LayerHelper("is_empty", name=name)
    out = helper.create_tmp_variable("bool", shape=(1,), stop_gradient=True)
    helper.append_op("is_empty", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]})
    return out


def nce(input, label, num_total_classes, num_neg_samples=10,
        sample_weight=None, param_attr=None, bias_attr=None,
        custom_neg_classes=None, name=None):
    """Noise-contrastive estimation loss (reference layers/nn.py nce ->
    nce_op.h): per-sample cost over [true | sampled negative] classes."""
    helper = LayerHelper("nce", name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(ParamAttr.to_attr(param_attr),
                                shape=(num_total_classes, dim),
                                dtype=input.dtype,
                                default_initializer=Xavier())
    b = helper.create_parameter(ParamAttr.to_attr(bias_attr),
                                shape=(num_total_classes,),
                                dtype=input.dtype,
                                default_initializer=Constant(0.0))
    cost = helper.create_tmp_variable(input.dtype)
    sample_labels = helper.create_tmp_variable("int32", stop_gradient=True)
    inputs = {"Input": [input.name], "Label": [label.name],
              "Weight": [w.name], "Bias": [b.name]}
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight.name]
    helper.append_op(
        "nce", inputs=inputs,
        outputs={"Cost": [cost.name],
                 "SampleLabels": [sample_labels.name]},
        attrs={"num_total_classes": num_total_classes,
               "num_neg_samples": num_neg_samples,
               "custom_neg_classes": list(custom_neg_classes or [])})
    return cost


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv3d", name=name, act=act, bias_attr=bias_attr)
    ks = [filter_size] * 3 if isinstance(filter_size, int) \
        else list(filter_size)
    c_in = input.shape[1]
    w = helper.create_parameter(
        ParamAttr.to_attr(param_attr),
        shape=(num_filters, c_in // groups, ks[0], ks[1], ks[2]),
        dtype=input.dtype, default_initializer=Xavier())
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        "conv3d", inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Output": [out.name]},
        attrs={"strides": [stride] * 3 if isinstance(stride, int)
               else list(stride),
               "paddings": [padding] * 3 if isinstance(padding, int)
               else list(padding),
               "dilations": [dilation] * 3 if isinstance(dilation, int)
               else list(dilation),
               "groups": groups})
    out = _append_channel_bias(helper, out, num_filters, bias_attr)
    return helper.append_activation(out)


def pool3d(input, pool_size=2, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, name=None):
    helper = LayerHelper("pool3d", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        "pool3d", inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={"ksize": [pool_size] * 3 if isinstance(pool_size, int)
               else list(pool_size),
               "strides": [pool_stride] * 3 if isinstance(pool_stride, int)
               else list(pool_stride),
               "paddings": [pool_padding] * 3
               if isinstance(pool_padding, int) else list(pool_padding),
               "pooling_type": pool_type,
               "global_pooling": global_pooling})
    return out


# ---------------------------------------------------------------------------
# round-4 breadth: the remaining reference nn.py surface
# ---------------------------------------------------------------------------

def l2_normalize(x, axis, epsilon=1e-12, name=None):
    """x / sqrt(max(sum(x**2, axis), epsilon)) (reference nn.py l2_normalize;
    the reference's op chain drops the sqrt — an acknowledged bug in its
    TODO — so this follows the documented L2 semantics)."""
    helper = LayerHelper("l2_normalize", name=name)
    if len(x.shape) == 1:
        axis = 0
    square = helper.create_tmp_variable(x.dtype, shape=x.shape)
    helper.append_op("square", inputs={"X": [x.name]},
                     outputs={"Out": [square.name]})
    rshape = tuple(1 if i == (axis % len(x.shape)) else s
                   for i, s in enumerate(x.shape))
    reduced = helper.create_tmp_variable(x.dtype, shape=rshape)
    helper.append_op("reduce_sum", inputs={"X": [square.name]},
                     outputs={"Out": [reduced.name]},
                     attrs={"dim": axis, "keep_dim": True,
                            "reduce_all": False})
    clipped = helper.create_tmp_variable(x.dtype, shape=rshape)
    helper.append_op("clip", inputs={"X": [reduced.name]},
                     outputs={"Out": [clipped.name]},
                     attrs={"min": float(epsilon), "max": 3.4e38})
    root = helper.create_tmp_variable(x.dtype, shape=rshape)
    helper.append_op("sqrt", inputs={"X": [clipped.name]},
                     outputs={"Out": [root.name]})
    rsq = helper.create_tmp_variable(x.dtype, shape=rshape)
    helper.append_op("reciprocal", inputs={"X": [root.name]},
                     outputs={"Out": [rsq.name]})
    out = helper.create_tmp_variable(x.dtype, shape=x.shape)
    helper.append_op("elementwise_mul",
                     inputs={"X": [x.name], "Y": [rsq.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": -1})
    return out


def multiplex(inputs, index):
    """Row-wise select among candidate tensors by index column
    (reference nn.py multiplex -> multiplex_op.cc)."""
    helper = LayerHelper("multiplex")
    if not isinstance(inputs, (list, tuple)) or len(inputs) < 2:
        raise ValueError("multiplex needs at least 2 input tensors")
    out = helper.create_tmp_variable(inputs[0].dtype, shape=inputs[0].shape)
    helper.append_op("multiplex",
                     inputs={"X": [i.name for i in inputs],
                             "Ids": [index.name]},
                     outputs={"Out": [out.name]})
    return out


def one_hot(input, depth):
    """Int ids -> one-hot float rows (reference nn.py one_hot)."""
    helper = LayerHelper("one_hot")
    shape = tuple(input.shape[:-1]) + (depth,) if input.shape else None
    out = helper.create_tmp_variable("float32", shape=shape)
    helper.append_op("one_hot", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs={"depth": depth})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    """Smooth-L1 (Huber) loss rows (reference nn.py smooth_l1 ->
    smooth_l1_loss_op.cc); weights gate the diff inside / the loss outside."""
    helper = LayerHelper("smooth_l1_loss")
    diff = helper.create_tmp_variable(x.dtype, shape=x.shape)
    loss = helper.create_tmp_variable(x.dtype, shape=(x.shape[0], 1))
    inputs = {"X": [x.name], "Y": [y.name]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight.name]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight.name]
    helper.append_op("smooth_l1_loss", inputs=inputs,
                     outputs={"Diff": [diff.name], "Out": [loss.name]},
                     attrs={"sigma": 1.0 if sigma is None else float(sigma)})
    return loss


def expand(x, expand_times, name=None):
    """Tile x by expand_times per dim (reference nn.py expand op chain)."""
    helper = LayerHelper("expand", name=name)
    shape = tuple(int(s * t) for s, t in zip(x.shape, expand_times)) \
        if x.shape else None
    out = helper.create_tmp_variable(x.dtype, shape=shape)
    helper.append_op("expand", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]},
                     attrs={"expand_times": list(expand_times)})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    """Zero-extend each dim by (before, after) pairs (reference layers pad ->
    pad_op.cc)."""
    helper = LayerHelper("pad", name=name)
    shape = tuple(int(s + paddings[2 * i] + paddings[2 * i + 1])
                  for i, s in enumerate(x.shape)) if x.shape else None
    out = helper.create_tmp_variable(x.dtype, shape=shape)
    helper.append_op("pad", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]},
                     attrs={"paddings": list(paddings),
                            "pad_value": float(pad_value)})
    return out


def crop(x, shape=None, offsets=None, name=None):
    """Slice a static-shape window out of x (reference crop_op.cc; shape may
    come from a reference Variable)."""
    helper = LayerHelper("crop", name=name)
    inputs = {"X": [x.name]}
    attrs = {}
    if isinstance(shape, Variable):
        inputs["Y"] = [shape.name]
        out_shape = shape.shape
    else:
        attrs["shape"] = list(shape)
        out_shape = tuple(shape)
    attrs["offsets"] = list(offsets) if offsets is not None \
        else [0] * len(x.shape)
    out = helper.create_tmp_variable(x.dtype, shape=out_shape)
    helper.append_op("crop", inputs=inputs, outputs={"Out": [out.name]},
                     attrs=attrs)
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    """(1-eps)*label + eps*prior (reference label_smooth_op.h)."""
    helper = LayerHelper("label_smooth", name=name)
    inputs = {"X": [label.name]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist.name]
    out = helper.create_tmp_variable(label.dtype, shape=label.shape)
    helper.append_op("label_smooth", inputs=inputs,
                     outputs={"Out": [out.name]},
                     attrs={"epsilon": float(epsilon)})
    return out


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, act=None, name=None):
    """3-D transposed convolution (reference conv_transpose_op.cc 3-D maker,
    filter layout [C_in, C_out, kd, kh, kw])."""
    helper = LayerHelper("conv3d_transpose", name=name, act=act)
    c_in = input.shape[1]
    st = [stride] * 3 if isinstance(stride, int) else list(stride)
    pd = [padding] * 3 if isinstance(padding, int) else list(padding)
    dl = [dilation] * 3 if isinstance(dilation, int) else list(dilation)
    if filter_size is None:
        if output_size is None:
            raise ValueError("output_size must be set when filter_size is None")
        osize = [output_size] * 3 if isinstance(output_size, int) \
            else list(output_size)
        ks = [osize[i] - (input.shape[2 + i] - 1) * st[i] + 2 * pd[i]
              for i in range(3)]
    else:
        ks = [filter_size] * 3 if isinstance(filter_size, int) \
            else list(filter_size)
    w = helper.create_parameter(
        ParamAttr.to_attr(param_attr),
        shape=(c_in, num_filters, ks[0], ks[1], ks[2]), dtype=input.dtype,
        default_initializer=Xavier())
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        "conv3d_transpose",
        inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Output": [out.name]},
        attrs={"strides": st, "paddings": pd, "dilations": dl})
    out = _append_channel_bias(helper, out, num_filters, bias_attr)
    return helper.append_activation(out)


def max_pool3d_with_index(input, pool_size, pool_stride=None, name=None):
    helper = LayerHelper("max_pool3d_with_index", name=name)
    ks = [pool_size] * 3 if isinstance(pool_size, int) else list(pool_size)
    st = pool_stride or ks
    st = [st] * 3 if isinstance(st, int) else list(st)
    out = helper.create_tmp_variable(input.dtype)
    mask = helper.create_tmp_variable("int32", stop_gradient=True)
    helper.append_op("max_pool3d_with_index", inputs={"X": [input.name]},
                     outputs={"Out": [out.name], "Mask": [mask.name]},
                     attrs={"ksize": ks, "strides": st})
    return out, mask


def causal_self_attention(q, k, v, num_heads, num_kv_heads=None, window=0,
                          name=None):
    """Causal self-attention over dense [batch, seq, heads * head_dim]
    Q/K/V (already projected, e.g. by ``fc(num_flatten_dims=2)``). One op
    per transformer layer — the attention site the generation serving
    engine (serving/generate) recognizes and rewrites into its
    prefill/paged-decode phase ops over the KV arena. ``num_kv_heads``
    (default ``num_heads``) key/value heads serve the query heads in
    blocked groups (K and V are then [batch, seq, num_kv_heads *
    head_dim]); ``window`` > 0 lets position i see j only where
    0 <= i - j < window (0: every j <= i). V's heads may be of another size
    than Q's and K's; the result has V's."""
    num_kv_heads = int(num_kv_heads or num_heads)
    if q.shape and q.shape[-1] is not None and q.shape[-1] % num_heads:
        raise ValueError(
            f"hidden size {q.shape[-1]} must divide num_heads {num_heads}")
    if num_heads % num_kv_heads:
        raise ValueError(f"num_heads {num_heads} must be a multiple of "
                         f"num_kv_heads {num_kv_heads}")
    helper = LayerHelper("causal_self_attention", name=name)
    shape = q.shape
    if shape and v.shape and v.shape[-1] is not None:
        shape = tuple(shape[:-1]) + (
            int(v.shape[-1]) // num_kv_heads * int(num_heads),)
    out = helper.create_tmp_variable(q.dtype, shape=shape)
    lse = helper.create_tmp_variable("float32", stop_gradient=True)
    attrs = {"num_heads": int(num_heads)}
    if num_kv_heads != num_heads:
        attrs["num_kv_heads"] = num_kv_heads
    if window:
        attrs["window"] = int(window)
    helper.append_op("causal_self_attention",
                     inputs={"Q": [q.name], "K": [k.name], "V": [v.name]},
                     outputs={"Out": [out.name], "LogSumExp": [lse.name]},
                     attrs=attrs)
    return out


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """Root-mean-square norm over the last axis with a learned scale
    (initialised to 1): ``scale * x * rsqrt(mean(x^2) + epsilon)``."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(ParamAttr.to_attr(param_attr),
                                    shape=(int(input.shape[-1]),),
                                    dtype=input.dtype,
                                    default_initializer=Constant(1.0))
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("rms_norm",
                     inputs={"X": [input.name], "Scale": [scale.name]},
                     outputs={"Y": [out.name]}, attrs={"epsilon": epsilon})
    return out


def rotary_embedding(q, k, head_dim, theta=10000.0, rope_type="default",
                     factor=1.0, original_max_position=0, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.0, name=None):
    """Rotary positions (the ``rotate_half`` convention) on projected Q and
    K, [batch, seq, heads * head_dim], positions 0..seq-1. ``rope_type``
    ``yarn`` blends each frequency with itself over ``factor`` by YaRN's
    linear ramp (``original_max_position``, ``beta_fast``, ``beta_slow``)
    and scales cos and sin by ``attention_factor``. Returns (q, k)."""
    helper = LayerHelper("rotary_embedding", name=name)
    q_out = helper.create_tmp_variable(q.dtype, shape=q.shape)
    k_out = helper.create_tmp_variable(k.dtype, shape=k.shape)
    helper.append_op(
        "rotary_embedding", inputs={"Q": [q.name], "K": [k.name]},
        outputs={"QOut": [q_out.name], "KOut": [k_out.name]},
        attrs={"head_dim": int(head_dim), "theta": float(theta),
               "rope_type": rope_type, "factor": float(factor),
               "original_max_position": int(original_max_position),
               "beta_fast": float(beta_fast), "beta_slow": float(beta_slow),
               "attention_factor": float(attention_factor)})
    return q_out, k_out


def _project(x, size, param_attr):
    """``fc`` over the last axis of [batch, seq, width], no bias, with a
    copy of ``param_attr`` (one attr may serve several projections)."""
    return fc(x, size, num_flatten_dims=2, bias_attr=False,
              param_attr=copy.deepcopy(ParamAttr.to_attr(param_attr)))


def latent_kv_heads(kv, k_rope, num_heads, nope_dim, name=None):
    """Per-head keys and values of latent (MLA) attention: ``kv`` [batch,
    seq, num_heads * (nope_dim + v)], each head [k_nope | v], and the one
    rope key ``k_rope`` [batch, seq, rope] that all heads share -> (k
    [batch, seq, num_heads * (nope_dim + rope)], v [batch, seq, num_heads *
    v])."""
    helper = LayerHelper("latent_kv_heads", name=name)
    k = helper.create_tmp_variable(kv.dtype)
    v = helper.create_tmp_variable(kv.dtype)
    helper.append_op(
        "latent_kv_heads", inputs={"KV": [kv.name], "KRope": [k_rope.name]},
        outputs={"K": [k.name], "V": [v.name]},
        attrs={"num_heads": int(num_heads), "nope_dim": int(nope_dim)})
    return k, v


def latent_attention(input, num_heads, kv_lora_rank, qk_nope_head_dim,
                     qk_rope_head_dim, v_head_dim, q_lora_rank=None,
                     rope_theta=None, epsilon=1e-6, param_attr=None,
                     down_attr=None, up_attr=None, name=None):
    """Causal multi-head latent attention (MLA) in its expanded, training
    form, over an already normed ``input`` [batch, seq, hidden]; no bias
    anywhere. Queries: ``num_heads`` heads of [nope | rope], projected whole
    (``q_lora_rank`` None) or through ``RMSNorm(x W_qa)`` of that rank. Keys
    and values: ``x W_kva`` is [latent (``kv_lora_rank``) | ONE rope key];
    the normed latent goes up to ``num_heads`` heads of [k_nope | v], and
    every head's key is [k_nope | the shared rope key]
    (``latent_kv_heads``). With ``rope_theta`` the rope slices of the
    queries and the rope key are rotated by their positions; with None
    nothing is rotated (NoPE: the slice is 64 more coordinates of a key
    that all heads share). Scores are scaled by (nope + rope)^-0.5 and the
    values' heads may be of another size than the keys'
    (``causal_self_attention``); the heads' outputs go back to ``hidden``
    through ``W_o``. Parameters in the order W_q (or W_qa, the query
    latent's norm, W_qb), W_kva, the key/value latent's norm, W_kvb, W_o.
    ``down_attr`` (W_qa, W_kva) and ``up_attr`` (W_q / W_qb, W_kvb) default
    to ``param_attr``."""
    if rope_theta is not None:
        raise NotImplementedError(
            "latent_attention: a rotated slice needs rotary_embedding over "
            "the last qk_rope_head_dim coordinates of a head, which the op "
            "does not have; pass rope_theta=None (NoPE)")
    head = int(qk_nope_head_dim) + int(qk_rope_head_dim)

    def proj(x, size, attr=None):
        return _project(x, size, attr or param_attr)

    if q_lora_rank:
        q = proj(rms_norm(proj(input, q_lora_rank, down_attr),
                          epsilon=epsilon), num_heads * head, up_attr)
    else:
        q = proj(input, num_heads * head, up_attr)
    c_kv, k_rope = tensor.split(
        proj(input, kv_lora_rank + qk_rope_head_dim, down_attr),
        [int(kv_lora_rank), int(qk_rope_head_dim)], dim=-1)
    kv = proj(rms_norm(c_kv, epsilon=epsilon),
              num_heads * (qk_nope_head_dim + v_head_dim), up_attr)
    k, v = latent_kv_heads(kv, k_rope, num_heads, qk_nope_head_dim)
    out = causal_self_attention(q, k, v, num_heads=num_heads, name=name)
    return proj(out, int(input.shape[-1]))


def gated_mlp(input, width, param_attr=None):
    """The gated-SiLU MLP ``W_down(silu(W_gate x) * W_up x)`` of ``width``
    over [batch, seq, hidden], no bias: three ``fc``, ``swish`` and a
    product. Parameters in the order gate, up, down."""
    gate = _project(input, width, param_attr)
    up = _project(input, width, param_attr)
    return _project(elementwise_mul(ops.swish(gate), up),
                    int(input.shape[-1]), param_attr)


def causal_conv1d(input, taps, param_attr=None, bias_attr=False, name=None):
    """A causal depthwise convolution over time on [batch, seq, channels],
    then SiLU: each channel's own filter of ``taps`` over the current and
    the ``taps - 1`` earlier tokens (zeros before the first). The filter
    parameter is [taps, channels], the last tap on the current token. With
    ``bias_attr`` (True, or a ParamAttr) a bias [channels], zeros by
    default, is added before the SiLU."""
    helper = LayerHelper("causal_conv1d", name=name)
    w = helper.create_parameter(
        ParamAttr.to_attr(param_attr), shape=(int(taps),
                                              int(input.shape[-1])),
        dtype="float32")
    inputs = {"X": [input.name], "Filter": [w.name]}
    if bias_attr:
        bias = helper.create_parameter(
            ParamAttr.to_attr(None if bias_attr is True
                              else copy.deepcopy(bias_attr)),
            shape=(int(input.shape[-1]),), dtype="float32",
            default_initializer=Constant(0.0))
        inputs["Bias"] = [bias.name]
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("causal_conv1d", inputs=inputs,
                     outputs={"Out": [out.name]})
    return out


def kda_decay_gate(input, num_heads, name=None):
    """The gated delta rule's log-decay, one per key channel, from a
    projection ``input`` [batch, seq, num_heads * head_dim]: ``-exp(A_log
    [head]) * softplus(input + dt_bias)``, float32. Parameters ``A_log``
    [num_heads], initialised log U(1, 16), and ``dt_bias`` [num_heads *
    head_dim], the inverse softplus of a step drawn log-uniformly in [0.001,
    0.1] (the family's convention)."""
    helper = LayerHelper("kda_decay_gate", name=name)
    a_log = helper.create_parameter(
        ParamAttr(initializer=Mapped(Uniform(1.0, 16.0), "log")),
        shape=(int(num_heads),), dtype="float32")
    lo, hi = float(np.log(0.001)), float(np.log(0.1))
    dt_bias = helper.create_parameter(
        ParamAttr(initializer=Mapped(       # softplus^-1(e^u) = log(e^e^u - 1)
            Uniform(lo, hi), "exp", "exp", ("scale", {"bias": -1.0}),
            "log")),
        shape=(int(input.shape[-1]),), dtype="float32")
    out = helper.create_tmp_variable("float32", shape=input.shape)
    helper.append_op("kda_decay_gate",
                     inputs={"X": [input.name], "ALog": [a_log.name],
                             "DtBias": [dt_bias.name]},
                     outputs={"Out": [out.name]})
    return out


def gated_delta_rule(q, k, v, g, beta, num_heads, chunk_size=64, name=None):
    """Linear attention by the gated delta rule (ops/
    linear_attention_ops.py): per head a state ``S`` [key, value] from
    zero, ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t
    k_t v_t^T``, ``o_t = S_t^T q_t``, computed in chunks of ``chunk_size``
    tokens. ``q``, ``k`` [batch, seq, num_heads * dk] are L2-normalised per
    head inside the op (``q`` times dk^-0.5); ``v``
    [batch, seq, num_heads * dv]; ``g`` the log-decay per key channel (<=
    0); ``beta`` [batch, seq, num_heads]. Returns o, of v's shape."""
    helper = LayerHelper("gated_delta_rule", name=name)
    out = helper.create_tmp_variable(v.dtype, shape=v.shape)
    states = helper.create_tmp_variable("float32", stop_gradient=True)
    helper.append_op(
        "gated_delta_rule",
        inputs={"Q": [q.name], "K": [k.name], "V": [v.name], "G": [g.name],
                "Beta": [beta.name]},
        outputs={"Out": [out.name], "States": [states.name]},
        attrs={"num_heads": int(num_heads), "chunk_size": int(chunk_size)})
    return out


def gated_rms_norm(input, gate, head_dim, epsilon=1e-6, param_attr=None,
                   gate_first=False, name=None):
    """RMSNorm over each ``head_dim`` slice of [batch, seq, heads *
    head_dim] with one learned scale [head_dim] (initialised to 1), times
    ``sigmoid(gate)``. With ``gate_first`` (the Mamba form) the input is
    multiplied by ``silu(gate)`` BEFORE the norm, the norm is over groups
    of ``head_dim`` channels and the scale is one a channel."""
    helper = LayerHelper("gated_rms_norm", name=name)
    attrs = {"epsilon": float(epsilon)}
    width = int(head_dim)
    if gate_first:      # the default form's op stays as it was built before
        attrs.update(gate_first=True, group_size=int(head_dim))
        width = int(input.shape[-1])
    scale = helper.create_parameter(
        ParamAttr.to_attr(param_attr), shape=(width,),
        dtype="float32", default_initializer=Constant(1.0))
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op("gated_rms_norm",
                     inputs={"X": [input.name], "Gate": [gate.name],
                             "Scale": [scale.name]},
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def kda_attention(input, num_heads, head_dim, conv_size=4, gate_rank=None,
                  chunk_size=64, epsilon=1e-6, param_attr=None,
                  conv_attr=None, name=None):
    """Kimi Delta Attention over an already normed ``input`` [batch, seq,
    hidden]; no bias but the gate's. ``q, k, v = silu(conv(x W))`` (causal
    depthwise convolutions of ``conv_size`` taps), ``num_heads`` heads of
    ``head_dim`` each; the log-decay ``kda_decay_gate(x W_fa W_fb)`` through
    a rank of ``gate_rank`` (default ``head_dim``); the step size
    ``sigmoid(x W_b)`` per head; ``gated_delta_rule``; the output
    ``gated_rms_norm`` per head with the gate ``x W_ga W_gb``; then ``W_o``
    back to hidden. Parameters in the order W_q, W_k, W_v, the three
    filters (q, k, v), W_fa, W_fb, A_log, dt_bias, W_b, W_ga, W_gb, the
    output norm's scale, W_o."""
    width = int(num_heads) * int(head_dim)
    rank = int(gate_rank or head_dim)
    q, k, v = (_project(input, width, param_attr) for _ in range(3))
    q, k, v = (causal_conv1d(x, conv_size, param_attr=copy.deepcopy(
        ParamAttr.to_attr(conv_attr or param_attr))) for x in (q, k, v))
    g = kda_decay_gate(
        _project(_project(input, rank, param_attr), width, param_attr),
        num_heads)
    beta = ops.sigmoid(_project(input, num_heads, param_attr))
    o = gated_delta_rule(q, k, v, g, beta, num_heads, chunk_size, name=name)
    gate = _project(_project(input, rank, param_attr), width, param_attr)
    o = gated_rms_norm(o, gate, head_dim, epsilon=epsilon)
    return _project(o, int(input.shape[-1]), param_attr)


def ssd_scan(x, dt, b, c, num_heads, n_groups=1, chunk_size=128,
             time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
             name=None):
    """The Mamba-2 state-space core (ops/state_space_ops.py): per head a
    state ``S`` [head_dim, state] from zero, ``S_t = a_t S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t + D x_t`` with ``dt_t = softplus(dt + dt_bias)``
    and ``a_t = exp(-exp(A_log) dt_t)`` one scalar a head, computed in chunks
    of ``chunk_size`` tokens. ``x`` [batch, seq, num_heads * head_dim]; the
    raw step ``dt`` [batch, seq, num_heads]; ``b``, ``c`` [batch, seq,
    n_groups * state], head h reading group ``h // (num_heads / n_groups)``.
    Parameters, each [num_heads]: ``A_log`` (log U(1, 16)), ``dt_bias`` (the
    inverse softplus of a step drawn log-uniformly in [``time_step_min``,
    ``time_step_max``] and floored at ``time_step_floor``: the family's
    convention) and the skip ``D`` (ones). Returns y, of x's shape."""
    helper = LayerHelper("ssd_scan", name=name)
    heads = (int(num_heads),)
    a_log = helper.create_parameter(
        ParamAttr(initializer=Mapped(Uniform(1.0, 16.0), "log")),
        shape=heads, dtype="float32")
    lo, hi = float(np.log(time_step_min)), float(np.log(time_step_max))
    dt_bias = helper.create_parameter(
        ParamAttr(initializer=Mapped(       # softplus^-1(s) = log(e^s - 1)
            Uniform(lo, hi), "exp",
            ("clip", {"min": float(time_step_floor), "max": 3.4e38}),
            "exp", ("scale", {"bias": -1.0}), "log")),
        shape=heads, dtype="float32")
    skip = helper.create_parameter(
        ParamAttr(initializer=Constant(1.0)), shape=heads, dtype="float32")
    out = helper.create_tmp_variable(x.dtype, shape=x.shape)
    states = helper.create_tmp_variable("float32", stop_gradient=True)
    helper.append_op(
        "ssd_scan",
        inputs={"X": [x.name], "Dt": [dt.name], "B": [b.name],
                "C": [c.name], "ALog": [a_log.name],
                "DtBias": [dt_bias.name], "D": [skip.name]},
        outputs={"Out": [out.name], "States": [states.name]},
        attrs={"num_heads": int(num_heads), "n_groups": int(n_groups),
               "chunk_size": int(chunk_size)})
    return out


def mamba2_mixer(input, num_heads, head_dim, n_groups, state_size,
                 conv_size=4, chunk_size=128, epsilon=1e-5, conv_bias=True,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, param_attr=None, conv_attr=None,
                 conv_bias_attr=None, out_attr=None, name=None):
    """A Mamba-2 mixer over an already normed ``input`` [batch, seq,
    hidden]; no bias but the convolution's. ``[z | xBC | dt] = x W_in``
    (widths inner | inner + 2 n_groups state_size | num_heads, inner =
    num_heads * head_dim); ``xBC = silu(conv(xBC) + b)`` (ONE causal
    depthwise convolution of ``conv_size`` taps over x, B and C together);
    ``ssd_scan`` over the split x, B, C and dt; ``gated_rms_norm`` in its
    Mamba form (``y * silu(z)``, then RMSNorm over each of ``n_groups``
    groups of channels, a scale a channel); then ``W_out`` back to hidden.
    Parameters in the order W_in, the filter (``conv_attr``), its bias
    (``conv_bias_attr``, zeros by default), A_log, dt_bias, D, the norm's
    scale, W_out (``out_attr``, default ``param_attr``)."""
    inner = int(num_heads) * int(head_dim)
    bc = int(n_groups) * int(state_size)
    proj = _project(input, 2 * inner + 2 * bc + int(num_heads), param_attr)
    z, xbc, dt = tensor.split(proj, [inner, inner + 2 * bc, int(num_heads)],
                              dim=-1)
    xbc = causal_conv1d(
        xbc, conv_size,
        bias_attr=conv_bias and (conv_bias_attr or True),
        param_attr=copy.deepcopy(ParamAttr.to_attr(conv_attr or param_attr)))
    x, b, c = tensor.split(xbc, [inner, bc, bc], dim=-1)
    y = ssd_scan(x, dt, b, c, num_heads, n_groups, chunk_size, time_step_min,
                 time_step_max, time_step_floor, name=name)
    y = gated_rms_norm(y, z, inner // int(n_groups), epsilon=epsilon,
                       gate_first=True)
    return _project(y, int(input.shape[-1]), out_attr or param_attr)


def relu2_mlp(input, width, param_attr=None):
    """The un-gated MLP ``W_down relu(W_up x)^2`` of ``width`` over [batch,
    seq, hidden], no bias: two ``fc``, ``relu`` and ``square``. Parameters
    in the order up, down."""
    up = _project(input, width, param_attr)
    return _project(ops.square(ops.relu(up)), int(input.shape[-1]),
                    param_attr)


def routed_experts(input, num_experts, top_k, expert_width,
                   held_experts=None, expert_offset=0, norm_topk_prob=True,
                   row_buffer_factor=2.0, router_task_gradient=True,
                   scoring_func="softmax", routed_scaling_factor=1.0,
                   selection_bias=False, bias_update_rate=0.0,
                   bias_attr=None, param_attr=None, expert_form="gated_silu",
                   name=None):
    """A mixture-of-experts MLP that is told which experts it holds
    (ops/moe_ops.py): the router scores all ``num_experts``
    (``scoring_func``: ``softmax`` over all of them, or ``sigmoid`` of each)
    and keeps the ``top_k``, whose weights are the scores' own, renormalised
    where ``norm_topk_prob``, times ``routed_scaling_factor``; the layer
    holds the gated-SiLU experts ``expert_offset .. expert_offset +
    held_experts - 1`` (default: all) of width ``expert_width`` and returns
    their part of the result, dropping no row. With ``selection_bias`` a
    non-trainable parameter [num_experts] (zeros, or ``bias_attr``'s
    initialiser) is added to the scores for the SELECTION only, and with
    ``bias_update_rate`` > 0 each step moves it by that much against the
    step's loads (``expert_bias_update``: no gradient, no optimizer). With
    ``expert_form`` ``relu2`` an expert is ``W_down relu(W_up x)^2`` and has
    no gate matrix. With ``router_task_gradient`` off the task loss does not
    reach the router through the top k's weights (it learns from
    ``aux_loss`` alone: for a layer that holds a share of the experts).
    Returns (out, expert_load [held] int32, aux_loss [1]: the load-balancing
    term over all router outputs)."""
    helper = LayerHelper("routed_experts", name=name)
    hidden = int(input.shape[-1])
    held = int(held_experts or num_experts)
    if not 0 <= expert_offset <= num_experts - held:
        raise ValueError(
            f"experts {expert_offset}..{expert_offset + held - 1} are not "
            f"among {num_experts}")

    def weight(shape):
        return helper.create_parameter(
            copy.deepcopy(ParamAttr.to_attr(param_attr)), shape=shape,
            dtype=input.dtype)

    router = weight((hidden, num_experts))
    inputs = {"X": [input.name], "RouterW": [router.name]}
    attrs = {"num_experts": int(num_experts), "top_k": int(top_k),
             "norm_topk_prob": bool(norm_topk_prob),
             "expert_offset": int(expert_offset),
             "row_buffer_factor": float(row_buffer_factor),
             "router_task_gradient": bool(router_task_gradient)}
    # the defaults stay out of the op, which is then the one every program
    # built before them holds
    if scoring_func != "softmax":
        attrs["scoring_func"] = str(scoring_func)
    if routed_scaling_factor != 1.0:
        attrs["routed_scaling_factor"] = float(routed_scaling_factor)
    bias = None
    if selection_bias:
        attr = copy.deepcopy(ParamAttr.to_attr(bias_attr))
        attr.trainable = False
        bias = helper.create_parameter(
            attr, shape=(int(num_experts),), dtype="float32",
            default_initializer=Constant(0.0))
        inputs["SelectBias"] = [bias.name]
    elif bias_update_rate:
        raise ValueError("bias_update_rate without selection_bias")
    relu2 = expert_form == "relu2"
    if relu2:
        attrs["expert_form"] = "relu2"
    elif expert_form != "gated_silu":
        raise ValueError(f"routed_experts: unknown expert_form "
                         f"{expert_form!r}")
    else:
        inputs["WGate"] = [weight((held, hidden, expert_width)).name]
    w_up = weight((held, hidden, expert_width))
    w_down = weight((held, expert_width, hidden))
    inputs.update({"WUp": [w_up.name], "WDown": [w_down.name]})
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    aux = helper.create_tmp_variable("float32", shape=(1,))
    kept = {slot: helper.create_tmp_variable(dtype, stop_gradient=True)
            for slot, dtype in (
                ("ExpertLoad", "int32"), ("Gate", input.dtype),
                ("Up", input.dtype), ("RowAssign", "int32"),
                ("RowWeight", "float32"), ("TopIdx", "int32"),
                ("Probs", "float32")) if not (relu2 and slot == "Gate")}
    kept["ExpertLoad"].shape = (held,)
    helper.append_op(
        "routed_experts", inputs=inputs,
        outputs={"Out": [out.name], "AuxLoss": [aux.name],
                 **{slot: [v.name] for slot, v in kept.items()}},
        attrs=attrs)
    if bias is not None and bias_update_rate:
        helper.append_op(
            "expert_bias_update",
            inputs={"Bias": [bias.name], "TopIdx": [kept["TopIdx"].name]},
            outputs={"BiasOut": [bias.name]},
            attrs={"rate": float(bias_update_rate)})
    return out, kept["ExpertLoad"], aux
