"""Device time of the EVA decode attention in one decode step: the
`eva_decode_attention` kernel's events (the `name=` the program gave its
Pallas call) on the first device inside the window's
`serving.decode_loop` spans, summed, over the `executor.step` spans
inside those loops (as `moe_expert_ms_per_token` reads `moe_gmm`)."""

from . import moe_expert_ms_per_token

KERNEL = "eva_decode_attention"


def read(run):
    found = moe_expert_ms_per_token.in_decode_loops(run, KERNEL)
    return None if found is None else found[0] / found[1]
