"""Device time of the attention kernels in one step: the events of the
first device whose instruction carries a `flash_attention*` /
`flash_tiled*` / `ring_block*` kernel name, per step."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.pallas_ms_per_step(run, "attention")
