"""Facts read from a compiled step's optimized HLO text."""

from __future__ import annotations

import re

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
_PALLAS = 'custom_call_target="tpu_custom_call"'


def custom_calls(hlo_text):
    """Pallas (Mosaic) kernels in an optimized HLO module."""
    return hlo_text.count(_PALLAS)


def custom_call_names(hlo_text):
    """Instruction names of the Pallas kernels: the names their events
    carry on the device trace's "XLA Ops" line."""
    names = []
    for line in hlo_text.splitlines():
        if _PALLAS in line:
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
            if m:
                names.append(m.group(1))
    return names


def collectives(hlo_text):
    """Collective instructions by kind (`-start` counts the async form
    once; `-done` is not counted). An operand is written `%all-reduce.3`,
    so the kind followed by "(" is the instruction itself; the shape
    before it may hold "=" (`/*index=5*/` in a tuple), which the pattern
    chip_smoke.py uses stops at (PR 22: it counted 3 of 4 all-reduces)."""
    out = {}
    for kind in COLLECTIVE_KINDS:
        n = len(re.findall(rf"(?<![\w%.\-]){kind}(?:-start)?\(", hlo_text))
        if n:
            out[kind] = n
    return out
