"""Operator library: importing this package registers every op emitter.

The registry (framework.registry) is the TPU-native analogue of the
reference's OpRegistry (op_registry.h); modules here cover the kernel surface
of paddle/fluid/operators/ that the BASELINE workloads need.
"""

from . import (  # noqa: F401
    _helpers,
    activation,
    amp_ops,
    beam_search,
    collective,
    control_flow,
    crf,
    ctr_ops,
    detection,
    detection_ext,
    eva,
    fused,
    kv_cache,
    llm,
    loss_ext,
    math,
    math_ext,
    metrics,
    nn,
    nn_ext,
    optimizer_ops,
    quant_ops,
    random,
    rnn,
    sparse,
    ssm,
    tensor_ext,
    tensor_ops,
)

# parallelism ops live beside their collectives implementation
from ..parallel import moe as _moe_ops  # noqa: F401,E402
from ..parallel import ring_attention as _ring_ops  # noqa: F401,E402

from ..framework.registry import registered_ops  # noqa: F401
