"""`device_idle_pct` of a generate cell (a per-layer metric names the one
end-to-end metric it moves, so the serving cells have their own)."""

from .device_idle_pct import read  # noqa: F401
