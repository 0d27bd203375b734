"""Device time of the residual / layer-norm kernels in one step: the
events of the first device whose instruction carries a `fused_residual*`
or `layer_norm*` kernel name, per step."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.pallas_ms_per_step(run, "residual_ln")
