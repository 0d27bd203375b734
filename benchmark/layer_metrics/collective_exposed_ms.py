"""The part of the collectives' time during which no other op ran on
that device, per step, on the device where it is largest."""

from . import collective_ms_per_step


def read(run):
    pairs = collective_ms_per_step.per_device(run)
    if not any(c for c, _e in pairs):
        return None
    return 1e3 * max(e for _c, e in pairs) / run.facts["steps"]
