"""Host work of one `Executor.run` that no device wait can hide in: the
`executor.prologue` span (entry to just before the compiled function is
called: feed and key preparation, cache lookup, state gather, PRNG key)
plus `executor.writeback` (new state into the scope), summed per call,
MEDIAN over all calls of the traced window. The `executor.dispatch`
between them holds the back-pressure that made `executor_host_ms` read the
shortest call only; this reads every call."""

from benchmark.harness import program_trace, stats


def read(run):
    return stats.median(program_trace.per_call_ms(
        run.spans, "executor.step",
        ("executor.prologue", "executor.writeback"),
    ))
