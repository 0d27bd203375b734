"""What `run.py` hands a traffic driver, and what a driver hands back."""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import time


@dataclasses.dataclass
class Context:
    chips: int
    config: dict            # benchmark/configs/<config>.json
    traffic: dict           # the cell file's "traffic" (rehearse: overlaid)
    expect: dict            # the cell file's "expect"
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list           # the jax devices the cell uses
    meter: object           # harness.meter.CompileMeter
    trace_dir: str


@dataclasses.dataclass
class Run:
    """One run's outcome. `facts` is what the per-layer readers may read
    besides the trace and the spans; its keys are the drivers' own."""

    attempted: int
    failed: int
    checks: dict            # name -> {"ok": bool, ...}; all must hold
    end_to_end: dict        # metric name -> value
    facts: dict
    spans: list = dataclasses.field(default_factory=list)
    trace: object = None    # harness.xplane.Trace, traced runs only
    window_ns: tuple = None  # the window on the trace's clock
    _ops: dict = dataclasses.field(default_factory=dict, repr=False)

    def device_ops(self, index=0):
        """The op events of the `index`-th device inside the window; None
        where the trace holds no such device (a CPU rehearsal)."""
        from . import xplane

        if index not in self._ops:
            planes = self.trace.device_planes()
            self._ops[index] = None if index >= len(planes) else \
                xplane.device_ops(self.trace, planes[index], self.window_ns)
        return self._ops[index]

    @property
    def correct(self):
        return self.failed == 0 and all(
            c.get("ok") for c in self.checks.values()
        )


class Window:
    """The measured window: host clock, wall clock (the program's spans
    carry wall-clock stamps) and the compile meter at both ends; with
    `trace` on, a profiler capture around it."""

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        import jax

        ctx = self.ctx
        self._stack = contextlib.ExitStack()
        if ctx.trace:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
            os.makedirs(ctx.trace_dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
            self._stack.callback(jax.profiler.stop_trace)
            self._stack.enter_context(
                jax.profiler.TraceAnnotation("bench.window")
            )
        self.meter_before = ctx.meter.snapshot()
        # what the process spent compiling before the window: set-up
        self.setup_meter = ctx.meter.since()
        self.wall0 = time.time()
        self.t0 = time.perf_counter()
        return self

    def close(self):
        """The window's last instant (call when the last result is in)."""
        self.t1 = time.perf_counter()
        self.wall1 = time.time()
        self.compiles = self.ctx.meter.since(self.meter_before)["compiles"]

    def __exit__(self, *exc):
        if not hasattr(self, "t1"):
            self.close()
        self._stack.close()
        return False

    @property
    def seconds(self):
        return self.t1 - self.t0

    def facts(self):
        """What every driver's Run carries about its window."""
        from . import device

        ctx = self.ctx
        return {
            "window_s": self.seconds, "t_window_start": self.t0,
            "setup_meter": self.setup_meter,
            "compiles_in_window": self.compiles,
            "peak_bytes": device.peak_bytes(ctx.devices),
            "chips": ctx.chips, "device_kind": ctx.devices[0].device_kind,
        }

    def compile_check(self):
        return {"ok": self.compiles == 0, "count": self.compiles}

    def attach(self, run):
        """Traced runs: the program's spans of the window and the
        captured trace, onto `run`."""
        if self.ctx.trace:
            from . import spans

            run.spans = spans.collect(self.wall0, self.wall1)
            run.trace, run.window_ns = self.load_trace()
        return run

    def load_trace(self):
        """(Trace, window on its clock) of the capture."""
        from . import xplane

        trace = xplane.load(xplane.newest_xplane(self.ctx.trace_dir))
        window = xplane.window_of(trace)
        if window is None:
            raise RuntimeError("bench.window is not in the captured trace")
        return trace, window
