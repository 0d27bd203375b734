"""Device time of one training step: the union of the "XLA Ops"
intervals on the first device over the traced window, per step."""

from benchmark.harness import xplane


def read(run):
    ops = run.device_ops()
    if not ops:
        return None
    return xplane.total(xplane.union(ops)) / 1e6 / run.facts["steps"]
