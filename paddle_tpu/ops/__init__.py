"""Operator library: JAX/XLA lowerings for the Fluid op surface.

Reference analog: paddle/fluid/operators/ (~471 registered op types, ~195k LoC of
C++/CUDA kernels). Here each op is one registered lowering (see core/registry.py); the
heavy lifting (fusion, scheduling, memory) is XLA's job, and gradients are derived via
jax.vjp, so the per-op code is the *math*, not kernels.

Importing this package registers all ops.
"""
from . import basic          # noqa: F401
from . import elementwise    # noqa: F401
from . import math_ops       # noqa: F401
from . import activations    # noqa: F401
from . import reduce_ops     # noqa: F401
from . import tensor_ops     # noqa: F401
from . import nn_ops         # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import control_flow   # noqa: F401
from . import metrics_ops    # noqa: F401
from . import sequence_ops   # noqa: F401
from . import collective     # noqa: F401
from . import detection_ops  # noqa: F401
from . import beam_ops       # noqa: F401
from . import pallas_attention  # noqa: F401
from . import pallas_conv_bn  # noqa: F401
from . import tail_ops  # noqa: F401
from . import extra_ops      # noqa: F401
from . import ctc_crf_ops    # noqa: F401
from . import sampled_ops    # noqa: F401
from . import host_table     # noqa: F401
from . import pipeline_op    # noqa: F401
from . import decoder_ops   # noqa: F401
