"""Share of the window's device idle that no span of the program names:
the pieces under no program span, or under `serving.batch` /
`executor.step` themselves (a whole batch, a whole call: owners that say
nothing). Prints the owners of all idle time on a line of its own."""

import json

from benchmark.harness import program_trace

SAYS_NOTHING = (program_trace.UNOWNED, "serving.batch", "executor.step")


def read(run):
    pieces = program_trace.idle_pieces(run)
    if not pieces:
        return None
    owners = program_trace.by_owner(pieces)
    total = sum(owners.values())
    print(json.dumps({"idle_owners_ms": {
        name: ns / 1e6 for name, ns in owners.items()
    }, "decode_idle": program_trace.decode_idle(run)}), flush=True)
    return 100.0 * sum(owners.get(n, 0.0) for n in SAYS_NOTHING) / total
