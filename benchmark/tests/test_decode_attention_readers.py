"""The two readers of the `decode_attention` kernel on hand-made events:
the time a decode step from the trace alone, the roofline share from
that and the program's two host-side counters, and nothing to read where
a parent's program has neither events nor counters."""

import pytest

from .test_afmoe_cell import _run


def test_the_decode_attention_readers_on_hand_made_events():
    from benchmark.layer_metrics import (
        decode_attention_ms_per_token, decode_attention_roofline_pct,
    )
    from paddle_tpu import observability as obs

    events = [
        ("%decode_attention.5 = bf16[64,6,1024] custom-call(...)", 1e6,
         0.4e6),
        ("%decode_attention.6 = bf16[64,6,1024] custom-call(...)", 2e6,
         0.4e6),
        ("%fusion.3 = ...", 3e6, 1e6),
        ("%decode_attention.5 = bf16[64,6,1024] custom-call(...)", 11e6,
         0.4e6),
        ("%decode_attention.6 = bf16[64,6,1024] custom-call(...)", 12e6,
         0.4e6),
        # outside every decode loop: not a decode step's
        ("%decode_attention.5 = bf16[64,6,1024] custom-call(...)", 30e6,
         9e6),
    ]
    program = [("serving.prefill", 0.1e6, 0.3e6),
               ("serving.decode_loop", 0.5e6, 16e6),
               ("executor.step", 0.6e6, 5e6),
               ("executor.step", 10.6e6, 5e6)]
    run = _run(events, program, [])
    obs.reset()
    try:
        # two steps of two calls of 0.4 ms
        assert decode_attention_ms_per_token.read(run) == pytest.approx(0.8)
        # a parent's program counts nothing: no need, no share
        assert decode_attention_roofline_pct.read(run) is None
        obs.add("kv_cache.decode_steps", 127)
        obs.add("kv_cache.decode_bytes_needed", 127 * 500_000_000)
        want = 100.0 * (500e6 / 819e9) / 0.8e-3
        assert decode_attention_roofline_pct.read(run) == pytest.approx(want)
        # no kernel events (the `jnp` path, a parent): nothing to read
        bare = _run(events[2:3], program, [])
        assert decode_attention_ms_per_token.read(bare) is None
        assert decode_attention_roofline_pct.read(bare) is None
    finally:
        obs.reset()
