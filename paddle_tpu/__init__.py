"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle-Fluid
capabilities (reference: /root/reference, see SURVEY.md), built on JAX/XLA.

Architecture (TPU-first, not a port):
  * static graph: Program/Block/Op IR -> whole-block XLA compilation
    (framework/executor.py) instead of per-op kernel dispatch;
  * autodiff: graph-transform append_backward whose grad ops replay forward
    emitters under jax.vjp (framework/backward.py);
  * eager "dygraph" mode with taped autograd (dygraph/);
  * distributed: GSPMD sharding + shard_map collectives over a device Mesh
    (parallel/), replacing NCCL rings and the SSA-graph ParallelExecutor.
"""

from . import core  # noqa: F401  (places, dtypes)
from . import errors  # noqa: F401  (typed error taxonomy, platform/error_codes.proto)
from .core.place import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    TPUPlace,
    cpu_places,
    is_compiled_with_tpu,
    tpu_places,
)
from .framework import (  # noqa: F401
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    global_scope,
    in_dygraph_mode,
    program_guard,
    device_guard,
    name_scope,
    scope_guard,
)
from . import ops  # noqa: F401  (registers all op emitters)
from .framework.executor import Executor  # noqa: F401
from .framework.backward import append_backward, gradients  # noqa: F401
from . import layers  # noqa: F401
from . import initializer  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import io  # noqa: F401
from . import contrib  # noqa: F401
from . import incubate  # noqa: F401
from . import dygraph  # noqa: F401
from .param_attr import ParamAttr  # noqa: F401
from . import dataloader  # noqa: F401
from . import profiler  # noqa: F401
from . import observability  # noqa: F401  (metrics/histograms/spans/exporters)
from . import analysis  # noqa: F401  (pre-compile static verifier + collective lint)
from . import resilience  # noqa: F401  (retry/backoff, fault injection)
from . import monitor  # noqa: F401  (back-compat facade over observability)
from . import debugger  # noqa: F401  (draw_block_graphviz)
from . import install_check  # noqa: F401  (run_check)
from .flags import get_flags, set_flags  # noqa: F401
from . import metrics  # noqa: F401
from . import nets  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from .compiler import BuildStrategy, CompiledProgram, ExecutionStrategy  # noqa: F401
from . import reader  # noqa: F401  (DataLoader + paddle.reader decorators)
from .reader_decorators import batch  # noqa: F401
from . import dataset  # noqa: F401
from .dataset import DatasetFactory  # noqa: F401
from . import native  # noqa: F401
from . import crypto  # noqa: F401  (model-file encryption, framework/io/crypto)
from . import inference  # noqa: F401
from . import serving  # noqa: F401  (freeze/router/KV-decode serving path)
from . import embedding  # noqa: F401  (fused/cached/sharded sparse tables)
from . import distributed  # noqa: F401
from . import nn  # noqa: F401
from . import tensor  # noqa: F401
from . import tools  # noqa: F401
from .reader import DataLoader  # noqa: F401

# `fluid`-compatible alias so code written against the reference API reads
# naturally: `import paddle_tpu as fluid; fluid.layers.fc(...)`.
fluid = None  # replaced below to avoid circular import confusion
import sys as _sys

fluid = _sys.modules[__name__]

__version__ = "0.1.0"


def data(name, shape, dtype="float32", lod_level=0):
    """fluid.data parity: full-shape feed declaration."""
    return layers.data(name, shape, dtype, lod_level=lod_level)
