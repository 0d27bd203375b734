"""The part of `decode_idle_ms_per_token` under the executor's own host
work: `executor.prologue`, `executor.dispatch`, `executor.writeback` and
the spans nested in them (`executor.compile`, `executor.rng_key`,
`spmd.dispatch`, `spmd.stage`)."""

from benchmark.harness import program_trace

OWNERS = ("executor.prologue", "executor.compile", "executor.rng_key",
          "executor.dispatch", "spmd.dispatch", "spmd.stage",
          "executor.writeback")


def read(run):
    return program_trace.decode_idle_ms(run, OWNERS)
