"""Device idle inside a batch's `serving.prefill` span (the gaps between
its blocking dispatches): what one dispatch for the whole batch could win
at most."""

from benchmark.harness import sections


def read(run):
    return sections.idle_inside_ms(run, "serving.prefill")
