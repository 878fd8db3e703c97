"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
2018-era PaddlePaddle (Fluid + v2), re-designed for JAX/XLA/Pallas/pjit.

Public API mirrors ``python/paddle/fluid/__init__.py`` of the reference:
Program/Block IR built by a layers DSL, IR-level autodiff and graph-op
optimizers, an Executor that compiles whole blocks to single XLA
computations, and mesh-sharded data/model parallelism in place of
NCCL/pserver distribution.
"""

from paddle_tpu import framework
from paddle_tpu.framework import (
    Program, Block, Operator, Variable, Parameter,
    default_main_program, default_startup_program, program_guard,
    name_scope, switch_main_program, switch_startup_program, unique_name,
)
from paddle_tpu.place import CPUPlace, TPUPlace, CUDAPlace, is_tpu_available
from paddle_tpu.scope import Scope, global_scope, scope_guard
from paddle_tpu import ops  # registers all op lowerings
from paddle_tpu.executor import Executor, fetch_var
from paddle_tpu.ops.reader_ops import EOFException
from paddle_tpu import memory_optimization_transpiler
from paddle_tpu.memory_optimization_transpiler import (memory_optimize,
                                                       release_memory)
from paddle_tpu import v2
from paddle_tpu import pydataprovider2
from paddle_tpu import concurrency
from paddle_tpu.concurrency import (Go, Select, make_channel, channel_send,
                                    channel_recv, channel_close)
from paddle_tpu.channel import Channel as CSPChannel, ChannelClosedError
from paddle_tpu.backward import append_backward, calc_gradient
from paddle_tpu import initializer
from paddle_tpu.param_attr import ParamAttr, WeightNormParamAttr
from paddle_tpu import layers
from paddle_tpu import nets
from paddle_tpu import optimizer
from paddle_tpu.optimizer import (
    SGD, Momentum, Adagrad, Adam, Adamax, DecayedAdagrad, Adadelta, RMSProp,
    Ftrl, SGDOptimizer, MomentumOptimizer, AdagradOptimizer, AdamOptimizer,
    AdamaxOptimizer, DecayedAdagradOptimizer, AdadeltaOptimizer,
    RMSPropOptimizer, FtrlOptimizer, ModelAverage,
)
from paddle_tpu import regularizer
from paddle_tpu import clip
from paddle_tpu import metrics
from paddle_tpu import evaluator
from paddle_tpu import profiler
from paddle_tpu.data_feeder import DataFeeder
from paddle_tpu import io
from paddle_tpu.io import (
    save_vars, save_params, save_persistables, load_vars, load_params,
    load_persistables, save_inference_model, load_inference_model,
)
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu import parallel
from paddle_tpu import reader
from paddle_tpu import dataset
from paddle_tpu import fault
from paddle_tpu import datapipe
from paddle_tpu import obs
from paddle_tpu import analysis

__version__ = "0.1.0"

Tensor = Variable  # convenience alias


def __getattr__(name):
    # deprecated modules import (and warn) only on first touch, so a
    # plain `import paddle_tpu` stays warning-free
    if name == "debuger":
        import importlib
        return importlib.import_module("paddle_tpu.debuger")
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
