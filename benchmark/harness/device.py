"""The device as JAX reports it, and the refusal to measure without it."""

from __future__ import annotations

import sys


def record():
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu(chips):
    """Exit (code 2, no result line) unless JAX holds `chips` TPU chips."""
    rec = record()
    if rec["platform"] != "tpu" or rec["count"] < chips:
        print(f"benchmark: the cell needs {chips} TPU chip(s), JAX reports "
              f"{rec}", file=sys.stderr)
        raise SystemExit(2)
    return rec


def peak_bytes(devices):
    """Peak bytes held on the fullest of `devices`, as the device reports
    them; None where the backend keeps no such statistics (the CPU).

    The TPU runtime keeps two books: `peak_bytes_in_use` is the
    allocator's high-water mark (parameters, optimizer state, feeds,
    fetches, caches), and `peak_bytes_reserved` the most it set aside for
    the temporaries of compiled programs, which the first does not
    include (PR 22: BERT-base at batch 64 showed 2.4 GB in use beside
    8.0 GB reserved; free = limit - in use - reserved). The peak is their
    sum: what could not have been given to anything else."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def memory_record(devices):
    """Every device's `memory_stats()` as the backend gives it (for the
    record line; None on the CPU)."""
    return [d.memory_stats() for d in devices]
