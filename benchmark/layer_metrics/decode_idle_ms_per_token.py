"""Device idle per decode step: the idle time of the first device inside
the window's `serving.decode_loop` spans over the `executor.step` spans
inside them. `decode_idle_fetch_ms`, `decode_idle_prologue_ms` and
`decode_idle_sample_ms` are its parts by owner; what they leave is under
`executor.step` or `serving.decode_loop` themselves (between children)."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.decode_idle_ms(run)
