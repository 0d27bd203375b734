"""Share of the window's busy self time that the section metrics cannot
name: events under no module of a phase (the cache reset's fills, a
counter read) or whose instruction has no scope of the vocabulary. The
instrument's own health: the `prefill_*_ms` and `decode_*_ms_per_token`
mean little where it is large. Prints the phases' tables."""

from benchmark.harness import sections


def read(run):
    return sections.unscoped_pct(run)
