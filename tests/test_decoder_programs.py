"""The serving programs of every decoder family, held op for op.

Each case builds one `GPTGenerator` at a tiny size (no executor run) and
reduces what it built to canonical parts: the startup program's ops (the
order seeds the weights), the prefill and decode programs' ops with their
inputs, outputs and attributes (`op_namescope` included: the benchmark's
section metrics read it), every persistable, the state the generator
zeroes, the KV slots it counts, its fetch lists, the `kv_cache.bytes.*`
gauges and the `serving.generate.model` table. A part's sha256 must match
`tests/data/decoder_programs.json`: a change to the model code that is
meant to move nothing moves none of them.

`python tests/test_decoder_programs.py` writes the fixture from the tree
it runs on.
"""

import hashlib
import json
import os
import sys

import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "decoder_programs.json")


def _gpt():
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig.tiny(), dict(batch=2, context_len=8, max_len=16)


def _afmoe(**kw):
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeDecoder

    # context 24 = 3 x the window of 8: the window layers' rings bind
    return AfmoeDecoder(AfmoeConfig.tiny(**kw)), dict(
        batch=2, context_len=24, max_len=32)


def _nemotron_h(**kw):
    from paddle_tpu.models.nemotron_h import NemotronHConfig, NemotronHDecoder

    return NemotronHDecoder(NemotronHConfig.tiny(**kw)), dict(
        batch=2, context_len=11, max_len=19)


def _dots_vlm():
    from paddle_tpu.models.dots_vlm import DotsVlmConfig, DotsVlmDecoder

    return DotsVlmDecoder(DotsVlmConfig.tiny()), dict(
        batch=2, context_len=24, max_len=34)


def _qwen3_next(**kw):
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextDecoder

    return Qwen3NextDecoder(Qwen3NextConfig.tiny(**kw)), dict(
        batch=2, context_len=11, max_len=19)


def _minicpm_sala(context, max_len, **kw):
    from paddle_tpu.models.minicpm_sala import (
        MiniCPMSalaConfig, MiniCPMSalaDecoder,
    )

    # the tiny config's dense_len is 16
    return MiniCPMSalaDecoder(MiniCPMSalaConfig.tiny(**kw)), dict(
        batch=2, context_len=context, max_len=max_len)


def _evabyte(**kw):
    from paddle_tpu.models.evabyte import EvaByteConfig, EvaByteDecoder

    # context 40 = 2.5 x the window of 16: a decode step sees 8 summaries
    return EvaByteDecoder(EvaByteConfig.tiny(**kw)), dict(
        batch=2, context_len=40, max_len=50)


CASES = {
    "gpt": _gpt,
    "afmoe": _afmoe,
    "afmoe_rows": lambda: _afmoe(prefill_rows=1),
    "nemotron_h": _nemotron_h,
    "nemotron_h_rows": lambda: _nemotron_h(prefill_rows=1),
    "dots_vlm": _dots_vlm,
    "qwen3_next": _qwen3_next,
    "qwen3_next_rows": lambda: _qwen3_next(prefill_rows=1),
    "minicpm_sala_dense": lambda: _minicpm_sala(8, 16),
    "minicpm_sala_sparse": lambda: _minicpm_sala(24, 32),
    "minicpm_sala_sparse_rows": lambda: _minicpm_sala(24, 32, prefill_rows=1),
    "evabyte": _evabyte,
    "evabyte_rows": lambda: _evabyte(prefill_rows=1),
}


def _ops(program, inputs=True):
    out = []
    for op in program.global_block.ops:
        attrs = {k: v for k, v in op.attrs.items() if k != "__loc__"}
        if not inputs:
            out.append([op.type, op.outputs, attrs])
        else:
            out.append([op.type, op.inputs, op.outputs, attrs])
    return out


def _persistables(program):
    return [[v.name, list(v.shape or ()), str(v.dtype)]
            for v in program.global_block.vars.values() if v.persistable]


def _parts(case):
    """The canonical parts of one case's build, as JSON-able data."""
    from paddle_tpu import observability as obs
    from paddle_tpu.framework import unique_name
    from paddle_tpu.serving import GPTGenerator

    obs.reset()
    decoder, sizes = CASES[case]()
    with unique_name.guard():
        gen = GPTGenerator(decoder, **sizes)
    gauges = {k: v for k, v in obs.get_gauges().items()
              if k.startswith("kv_cache.bytes.")}
    return {
        "startup": _ops(gen.startup_prog, inputs=False),
        "prefill": _ops(gen.prefill_prog),
        "decode": _ops(gen.decode_prog),
        "persistables": [_persistables(p) for p in (
            gen.startup_prog, gen.prefill_prog, gen.decode_prog)],
        "state": sorted([n, list(s), str(d)] for n, s, d in gen._state_specs),
        "kv_slots": sorted([int(s), int(b)] for s, b in gen._kv_slots),
        "fetch": [gen._prefill_fetch, gen._decode_fetch],
        "gauges": gauges,
        "model": obs.get_tables()["serving.generate.model"],
    }


def _digests(case):
    return {
        part: hashlib.sha256(json.dumps(
            value, sort_keys=True, default=repr).encode()).hexdigest()
        for part, value in _parts(case).items()
    }


@pytest.mark.parametrize("case", sorted(CASES))
def test_programs_match_the_fixture(case):
    with open(FIXTURE) as f:
        want = json.load(f)[case]
    got = _digests(case)
    assert got.keys() == want.keys()
    moved = sorted(part for part in want if got[part] != want[part])
    assert not moved, f"{case}: {moved} differ from the fixture"


def test_a_state_declared_twice_must_be_declared_alike():
    """The generator reads state back from both programs: a name the two
    declare differently is refused, not zeroed by one of the two."""
    import paddle_tpu as fluid
    from paddle_tpu.errors import InvalidArgumentError
    from paddle_tpu.models.decoder import declared_state, state

    programs = []
    for slots in (8, 16):
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            state("l0_cache_k", (2, slots, 32), "float32", "full")
            state("counters", (2,), "int32")
        programs.append(prog)
    assert declared_state(programs[0]) == {
        "l0_cache_k": ((2, 8, 32), "float32", "full", None),
        "counters": ((2,), "int32", None, None)}
    with pytest.raises(InvalidArgumentError, match="l0_cache_k"):
        declared_state(*programs)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
    table = {case: _digests(case) for case in sorted(CASES)}
    with open(FIXTURE, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(table)} cases to {FIXTURE}")
