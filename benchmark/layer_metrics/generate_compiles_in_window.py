"""`compiles_in_window` of a generate cell (a per-layer metric names the
one end-to-end metric it moves, so the serving cells have their own)."""

from .compiles_in_window import read  # noqa: F401
