"""Device time of the Pallas kernels in one step: the trace events that
carry the names of the compiled step's `tpu_custom_call` instructions,
summed on the first device, per step."""

from benchmark.harness import xplane


def seconds(run):
    names = set(run.facts.get("custom_call_names", ()))
    ops = run.device_ops()
    if not names or not ops:
        return None
    hit = [e for e in ops if xplane.op_name(e[0]) in names]
    if not hit:
        return None
    return sum(e[2] for e in hit) / 1e9


def read(run):
    total = seconds(run)
    return None if total is None else 1e3 * total / run.facts["steps"]
