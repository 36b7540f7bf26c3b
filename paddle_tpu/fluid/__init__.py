"""paddle_tpu.fluid — the primary user API, mirroring the reference's
``paddle.fluid`` surface (/root/reference/python/paddle/fluid/__init__.py):
layers, Program/program_guard, Executor, optimizer, initializer, io,
backward, regularizer, ParamAttr, places, Scope.
"""

from .framework import (Program, Block, Operator, Variable, Parameter,
                        program_guard, default_main_program,
                        default_startup_program, switch_main_program,
                        switch_startup_program, grad_var_name, unique_name,
                        name_scope)
from ..core.executor import Executor, CPUPlace, TPUPlace
from ..core.amp import amp_guard
from ..core.flags import set_flags, get_flag, flags, init_flags
from ..core.scope import Scope, global_scope
from ..core.lod import LoDArray, pack_sequences, flat_to_lodarray, \
    lodarray_to_flat
from .. import ops as _ops  # registers all op lowerings

from . import analysis  # static analysis (also installs SlotSpec catalogue)
from .analysis import (ProgramVerifyError, lint_program, verify_program)
from . import layers
from . import nets
from . import optimizer
from . import profiler
from . import initializer
from . import regularizer
from . import clip
from . import backward
from . import io
from . import evaluator
from . import concurrency
from .concurrency import (Go, Select, make_channel, channel_send,
                          channel_recv, channel_close)
from .backward import append_backward
from .param_attr import ParamAttr
from .data_feeder import DataFeeder
from .memory_optimization_transpiler import memory_optimize, release_memory
from .fusion import fuse_conv_bn
from .distribute_transpiler import (DistributeTranspiler,
                                    SimpleDistributeTranspiler)
from .param_attr import WeightNormParamAttr
from . import average
from . import recordio_writer
from ..core import executor
from ..core.lod import LoDArray as LoDTensor  # reference core.LoDTensor

# CUDAPlace alias: reference scripts say CUDAPlace(0); on this framework that
# means "the accelerator", i.e. the TPU chip.
CUDAPlace = TPUPlace

__all__ = [
    "Program", "Block", "Operator", "Variable", "Parameter", "program_guard",
    "default_main_program", "default_startup_program", "Executor", "CPUPlace",
    "TPUPlace", "CUDAPlace", "Scope", "global_scope", "layers", "optimizer",
    "initializer", "regularizer", "backward", "io", "nets", "append_backward",
    "ParamAttr", "DataFeeder", "LoDArray", "profiler", "amp_guard", "clip",
    "set_flags", "get_flag", "flags", "init_flags", "evaluator",
    "concurrency", "Go", "Select", "make_channel", "channel_send",
    "channel_recv", "channel_close", "memory_optimize", "release_memory",
    "fuse_conv_bn",
    "DistributeTranspiler", "SimpleDistributeTranspiler",
    "WeightNormParamAttr", "average", "recordio_writer", "executor",
    "LoDTensor", "analysis", "ProgramVerifyError", "lint_program",
    "verify_program", "name_scope",
]
