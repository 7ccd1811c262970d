"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle Fluid's
capabilities (reference: zhangting2020/Paddle, see SURVEY.md).

Public surface mirrors ``paddle.fluid``: a Program/Block/Op IR built by a layers DSL,
program-level autodiff, optimizers, executors -- but Programs lower whole to XLA,
parallelism is SPMD sharding over device meshes, and custom kernels are Pallas.
"""

import time as _time

_IMPORT_START = _time.perf_counter()   # process_uptime_seconds{at="import_start"}

from . import unique_name  # noqa: F401,E402
from .framework import (Program, Block, Variable, Parameter, Operator,  # noqa
                        program_guard, device_guard, default_main_program,
                        default_startup_program, switch_main_program,
                        grad_var_name, convert_dtype)
from . import ops  # noqa: F401  (registers the op library)
from .core.executor import Executor, Scope, global_scope, scope_guard  # noqa
from .core.backward import append_backward, gradients, calc_gradient  # noqa
from .core import registry  # noqa: F401
from . import layers  # noqa: F401
from . import nets  # noqa: F401
from . import dataset  # noqa: F401
from . import fleet  # noqa: F401
from . import transpiler  # noqa: F401
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa
from .transpiler import memory_optimize, release_memory  # noqa: F401
from . import inference  # noqa: F401
from .dataset_factory import (DatasetFactory, InMemoryDataset,  # noqa
                              QueueDataset)
from . import initializer  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from .layer_helper import LayerHelper, ParamAttr, WeightNormParamAttr  # noqa
from .layers.io import data  # noqa: F401
from .compiler import (CompiledProgram, BuildStrategy, ExecutionStrategy,  # noqa
                       DistributedStrategy)
from . import io  # noqa: F401
from . import contrib  # noqa: F401
from . import flags  # noqa: F401
from . import observability  # noqa: F401
from . import analysis  # noqa: F401  (static program verifier)
from . import resilience  # noqa: F401  (fault injection + step recovery)
from . import profiler  # noqa: F401
from . import debugger  # noqa: F401
from . import comm  # noqa: F401  (quantized collectives + reshard planner)
from . import average  # noqa: F401
from . import install_check  # noqa: F401
from . import net_drawer  # noqa: F401
from . import incubate  # noqa: F401
from .flags import get_flag, set_flags  # noqa: F401
from . import dygraph  # noqa: F401
from . import reader  # noqa: F401
from . import metrics  # noqa: F401
from .reader import DataLoader, PyReader, DataFeeder  # noqa: F401

__version__ = "0.1.0"

observability.timeline.mark_uptime("import_start", _IMPORT_START)
observability.timeline.mark_uptime("import_end")


class CPUPlace:
    """Place tags kept for fluid API parity; device selection is JAX's."""


class CUDAPlace:
    def __init__(self, id=0):
        self.id = id


class TPUPlace:
    def __init__(self, id=0):
        self.id = id


def cpu_places(device_count=None):
    return [CPUPlace()]


def cuda_places(device_ids=None):
    return [CUDAPlace(0)]
