"""Op library: importing this package registers every op lowering.

The registry split (core/registry.py) mirrors the reference's
REGISTER_OPERATOR/REGISTER_OP_*_KERNEL machinery
(/root/reference/paddle/fluid/framework/op_registry.h); modules here correspond
to the op families in SURVEY.md §2.2.
"""

from . import (  # noqa: F401
    elementwise,
    activation,
    tensor_ops,
    matmul,
    reduce,
    loss,
    nn_ops,
    conv_ops,
    norm_ops,
    sequence_ops,
    rnn_ops,
    attention_ops,
    linear_attention_ops,
    state_space_ops,
    moe_ops,
    control_flow_ops,
    crf_ops,
    ctc_ops,
    fused_ops,
    optimizer_ops,
    metrics,
    detection_ops,
    misc_ops,
    breadth_ops,
    io_ops,
)
