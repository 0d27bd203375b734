"""fluid.name_scope, carried to the compiled step; programs named on the
capture's lines; the scope table read from the capture's own HLO
(ISSUE 35)."""

import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, profiler
from paddle_tpu.framework import unique_name

SECTIONS = {"embed", "attn", "mlp", "moe", "ssm", "head"}


@pytest.fixture(autouse=True)
def fresh():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.framework.scope.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        yield


def _scopes(program):
    return [(op.type, op.attr("op_namescope"))
            for op in program.global_block.ops]


# ---------------------------------------------------------------------------
# the program side
# ---------------------------------------------------------------------------


def test_scopes_nest_with_a_slash_and_end_with_their_block():
    x = fluid.data("x", [4, 8], "float32")
    with fluid.name_scope("attn"):
        a = layers.scale(x, 2.0)
        with fluid.name_scope("proj"):
            b = layers.fc(a, 8)
        c = layers.relu(b)
    d = layers.scale(c, 3.0)
    ops = _scopes(fluid.default_main_program())
    assert ops[0] == ("scale", "attn")
    assert {s for t, s in ops[1:-2]} == {"attn/proj"}      # fc's ops
    assert ops[-2] == ("relu", "attn") and ops[-1] == ("scale", None)
    assert "op_namescope" not in d.block.ops[-1].attrs


def test_a_scope_ends_when_its_body_raises():
    with pytest.raises(RuntimeError):
        with fluid.name_scope("attn"):
            raise RuntimeError("boom")
    x = fluid.data("x", [2, 2], "float32")
    layers.scale(x, 2.0)
    assert _scopes(fluid.default_main_program()) == [("scale", None)]


def _two_section_loss():
    x = fluid.data("x", [4, 8], "float32")
    y = fluid.data("y", [4, 1], "float32")
    with fluid.name_scope("attn"):
        h = layers.fc(x, 8, act="relu")
        h = layers.layer_norm(h, begin_norm_axis=1)     # a grad maker
    with fluid.name_scope("head"):
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
    return loss


def test_a_grad_op_carries_its_forward_ops_scope():
    # layer_norm's maker declines unless its Pallas kernel is on
    fluid.set_flags({"FLAGS_paddle_tpu_pallas_layer_norm": True})
    loss = _two_section_loss()
    n_fwd = len(fluid.default_main_program().global_block.ops)
    fluid.optimizer.SGD(0.1).minimize(loss)
    ops = fluid.default_main_program().global_block.ops
    grads = [op for op in ops[n_fwd:] if op.type not in ("sgd",)]
    vjps = [op for op in grads if op.type == "__vjp__"]
    assert vjps and all(
        op.attr("op_namescope") == op.attr("fwd_attrs")["op_namescope"]
        for op in vjps)
    assert {op.attr("op_namescope") for op in vjps} == {"attn", "head"}
    made = [op for op in grads if op.type == "layer_norm_grad"]
    assert made and made[0].attr("op_namescope") == "attn"
    # only the seed of the loss's gradient, the updates and the renaming
    # of a parameter's finished gradient (no instruction) have no scope
    bare = {op.type for op in ops[n_fwd:] if not op.attr("op_namescope")}
    assert bare <= {"fill_constant", "sgd", "assign"}, bare


def test_the_lowered_step_holds_scope_then_op_type():
    loss = _two_section_loss()
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    feed = {"x": np.ones((4, 8), np.float32),
            "y": np.ones((4, 1), np.float32)}
    text = exe.lower(feed=feed, fetch_list=[loss]).as_text(debug_info=True)
    assert '"jit(train_step)/attn/mul/dot_general"' in text
    assert '"jit(train_step)/head/__vjp__/transpose(jvp(mul))/dot_general"' \
        in text
    assert "jit(train_step)/sgd/" in text


# ---------------------------------------------------------------------------
# programs named
# ---------------------------------------------------------------------------


def _module_name(lowered):
    return lowered.as_text().split("module @", 1)[1].split()[0]


def test_two_programs_of_one_executor_give_two_module_names():
    exe = fluid.Executor()
    startup = fluid.default_startup_program()
    x = fluid.data("x", [-1, 8], "float32")
    out = layers.fc(x, 4)
    exe.run(startup)
    main = fluid.default_main_program()
    small = {"x": np.ones((2, 8), np.float32)}
    large = {"x": np.ones((6, 8), np.float32)}
    exe.run(main, feed=small, fetch_list=[out])
    exe.run(main, feed=large, fetch_list=[out])
    names = [c.name for c in exe._cache.values()]
    assert names[0] == "startup"
    plain = f"program{main._creation_ordinal}"
    assert names[1] == plain
    # the second compile of one program: the name plus a digest
    assert names[2].startswith(plain + "_") and len(names[2]) == \
        len(plain) + 9
    assert len(set(names)) == 3
    assert _module_name(exe.lower(main, feed=small, fetch_list=[out])) \
        == "jit_" + plain
    assert _module_name(exe.lower(main, feed=large, fetch_list=[out])) \
        == "jit_" + names[2]


def test_an_owners_label_names_the_module():
    exe = fluid.Executor()
    x = fluid.data("x", [2, 8], "float32")
    out = layers.fc(x, 4)
    exe.run(fluid.default_startup_program())
    main = fluid.default_main_program()
    main._label = "my_step"
    feed = {"x": np.ones((2, 8), np.float32)}
    assert _module_name(exe.lower(main, feed=feed, fetch_list=[out])) \
        == "jit_my_step"


def test_a_mesh_program_is_named_as_any_other():
    from paddle_tpu.parallel import make_mesh, shard_program

    exe = fluid.Executor()
    x = fluid.data("x", [4, 8], "float32")
    out = layers.fc(x, 4)
    exe.run(fluid.default_startup_program())
    main = fluid.default_main_program()
    main._label = "dp_step"
    shard_program(main, make_mesh({"dp": 2}, jax.devices()[:2]),
                  {"x": ("dp",)})
    feed = {"x": np.ones((4, 8), np.float32)}
    assert _module_name(exe.lower(main, feed=feed, fetch_list=[out])) \
        == "jit_dp_step"


# ---------------------------------------------------------------------------
# the models' sections, in the compiled decode step and in a capture
# ---------------------------------------------------------------------------


def _generator(family):
    from paddle_tpu.serving import GPTGenerator

    if family == "gpt":
        from paddle_tpu.models.gpt import GPTConfig

        dec = GPTConfig.tiny()
    elif family == "afmoe":
        from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeDecoder

        dec = AfmoeDecoder(AfmoeConfig.tiny())
    elif family == "nemotron_h":
        from paddle_tpu.models.nemotron_h import (
            NemotronHConfig, NemotronHDecoder,
        )

        dec = NemotronHDecoder(NemotronHConfig.tiny())
    else:
        from paddle_tpu.models.dots_vlm import DotsVlmConfig, DotsVlmDecoder

        dec = DotsVlmDecoder(DotsVlmConfig.tiny())
    gen = GPTGenerator(dec, batch=2, context_len=8, max_len=16)
    gen.init_params(seed=3)
    return gen


def _hlo_proto(lowered):
    """The compiled step's HloProto as a capture's metadata plane files
    it: field 1 = the serialized HloModuleProto."""
    (module,) = lowered.compile().runtime_executable().hlo_modules()
    body = module.as_serialized_hlo_module_proto()
    size, head = len(body), b"\x0a"
    while size >= 0x80:
        head += bytes([size & 0x7F | 0x80])
        size >>= 7
    return head + bytes([size]) + body


FAMILY_SECTIONS = {
    "gpt": {"embed", "attn", "mlp", "head"},
    "afmoe": {"embed", "attn", "mlp", "moe", "head"},
    "nemotron_h": {"embed", "attn", "moe", "ssm", "head"},
    "dots_vlm": {"embed", "attn", "mlp", "moe", "head"},
}


@pytest.mark.parametrize("family", sorted(FAMILY_SECTIONS))
def test_every_op_of_both_programs_sits_in_a_section(family):
    gen = _generator(family)
    assert gen.prefill_prog._label == f"{family}_prefill"
    assert gen.decode_prog._label == f"{family}_decode"
    for prog in (gen.prefill_prog, gen.decode_prog):
        firsts = {(s or "").split("/")[0] for _t, s in _scopes(prog)}
        assert firsts == FAMILY_SECTIONS[family], (prog._label, firsts)


@pytest.mark.parametrize("family", sorted(FAMILY_SECTIONS))
def test_the_compiled_decode_step_holds_every_section(family):
    gen = _generator(family)
    feed = {"token_ids": np.zeros((2, 1), np.int64),
            "pos_ids": np.array([[8]], np.int64)}
    lowered = gen.executor.lower(gen.decode_prog, feed=feed,
                                 fetch_list=gen._decode_fetch,
                                 scope=gen.scope)
    assert _module_name(lowered) == f"jit_{family}_decode"
    scopes = profiler.op_scopes(_hlo_proto(lowered))
    firsts = [s.split("/")[0] for s in scopes.values()]
    assert set(firsts) - {""} == FAMILY_SECTIONS[family]
    # what the compiler put in without metadata takes its operands' scope
    assert firsts.count("") <= 0.05 * len(firsts), \
        [k for k, v in scopes.items() if not v]
    if "moe" in FAMILY_SECTIONS[family]:
        deep = set(scopes.values())
        assert any(s.startswith("moe/experts/moe_local_experts/moe_router")
                   for s in deep), sorted(deep)
        assert any(s.startswith("moe/shared") for s in deep)


@pytest.fixture
def gpt_capture(tmp_path):
    gen = _generator("gpt")
    ids = np.random.RandomState(0).randint(0, 512, (2, 8))
    gen.generate(ids, 4)
    jax.profiler.start_trace(str(tmp_path))
    gen.generate(ids, 4)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    return str(tmp_path), path


def test_the_capture_names_both_programs_and_holds_their_hlo(gpt_capture):
    _dir, path = gpt_capture
    modules = profiler.capture_modules(path)
    labels = {m.split("(")[0] for m in modules}
    assert {"jit_gpt_prefill", "jit_gpt_decode"} <= labels
    assert "jit_traced" not in labels
    scopes = profiler.capture_scopes(path)
    (decode,) = [m for m in scopes if m.startswith("jit_gpt_decode(")]
    assert {s.split("/")[0] for s in scopes[decode].values()} \
        == FAMILY_SECTIONS["gpt"]


def test_summary_by_scope_is_the_per_fluid_op_table(gpt_capture):
    trace_dir, _path = gpt_capture
    table = profiler.summary(trace_dir, by="scope")
    rows = {row[0]: row for row in table}
    firsts = {scope.split("/")[0] for scope in rows}
    assert {"attn", "mlp", "head"} <= firsts
    assert "attn/core/kv_cache_attention" in rows
    assert not any(scope.startswith("fusion") for scope in rows)
    assert all(len(row) == 4 for row in table)
    assert sum(row[3] for row in table) == pytest.approx(1.0)
    assert [row[1] for row in table] == sorted((r[1] for r in table),
                                              reverse=True)
    # by kind, the table it was: XLA's instruction kinds
    kinds = profiler.summary(trace_dir)
    assert kinds and all(len(row) == 3 for row in kinds)
    assert not {k for k, _ms, _n in kinds} & firsts
    assert "attn/proj/mul" in profiler._format_table(table)
    with pytest.raises(ValueError):
        profiler.summary(trace_dir, by="layer")


# ---------------------------------------------------------------------------
# the reader's arithmetic, on hand-made inputs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op_name, scope", [
    ("jit(afmoe_decode)/jit(main)/moe/shared/mul/dot_general",
     "moe/shared/mul"),
    ("jit(train_step)/attn/__vjp__/transpose(jvp(mul))/dot_general",
     "attn/__vjp__/mul"),
    ("jit(gpt_decode)/attn/core/kv_cache_attention/jit(decode_attention)/"
     "pallas_call", "attn/core/kv_cache_attention"),
    ("jit(nemotron_h_prefill)/ssm/scan/ssd_chunk_scan/while/body/add",
     "ssm/scan/ssd_chunk_scan/while/body"),
    ("jit(_threefry_seed)/concatenate", ""),
    ("", ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert profiler.scope_of(op_name) == scope


def _varint(n):
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _field(number, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instruction(iid, name, opcode, op_name="", operands=(), calls=()):
    out = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if op_name:
        out += _field(7, _field(2, op_name))
    # operand ids packed, called computations one varint a field: both
    # forms of a repeated int64 are on the wire
    if operands:
        out += _field(36, b"".join(_varint(i) for i in operands))
    for c in calls:
        out += _field(38, c)
    return out


def _computation(cid, name, instructions, fused=False):
    out = _field(1, name) + _field(5, cid)
    if fused:
        out += _field(7, 1)
    return out + b"".join(_field(2, i) for i in instructions)


def test_op_scopes_of_a_hand_written_module():
    fused = _computation(10, "fused_computation", [
        _instruction(1, "param_0", "parameter"),
        _instruction(2, "dot.1", "dot", "jit(f)/attn/proj/mul/dot_general",
                     operands=[1]),
        _instruction(3, "add.1", "add", "jit(f)/attn/proj/mul/add",
                     operands=[2]),
    ], fused=True)
    reducer = _computation(11, "region_0", [
        _instruction(4, "reduce_sum.1", "add"),
    ])
    body = _computation(12, "while_body", [
        _instruction(5, "arg", "parameter"),
        _instruction(6, "tanh.1", "tanh",
                     "jit(f)/jit(main)/mlp/gelu/while/body/tanh",
                     operands=[5]),
        _instruction(7, "copy.2", "copy", operands=[6]),
    ])
    entry = _computation(13, "main", [
        _instruction(20, "x", "parameter"),
        _instruction(21, "fusion.1", "fusion", operands=[20], calls=[10]),
        _instruction(22, "copy.1", "copy", operands=[21]),
        _instruction(23, "while.1", "while", "jit(f)/mlp/gelu/while",
                     operands=[22], calls=[12]),
        _instruction(24, "reduce.1", "reduce", "jit(f)/head/mean/reduce_sum",
                     operands=[23], calls=[11]),
        _instruction(25, "copy-start.1", "copy-start", operands=[20]),
        _instruction(26, "tuple.1", "tuple", operands=[24]),
        _instruction(27, "iota.1", "iota"),
    ])
    module = _field(1, "jit_f") + _field(6, 13) + b"".join(
        _field(3, c) for c in (fused, reducer, body, entry))
    assert profiler.op_scopes(_field(1, module)) == {
        # no metadata of its own: what most of its fused instructions have
        "fusion.1": "attn/proj/mul",
        # no metadata, no body: what its operand's producer has
        "copy.1": "attn/proj/mul",
        "while.1": "mlp/gelu",
        # a while's body runs as instructions of its own
        "tanh.1": "mlp/gelu/while/body",
        "copy.2": "mlp/gelu/while/body",
        "reduce.1": "head/mean",
        # its one operand is a parameter, nothing reads it: where the
        # parameter's other reader is
        "copy-start.1": "attn/proj/mul",
        # nothing to go by
        "iota.1": "",
        # not listed: parameters, the tuple, the inside of the fusion,
        # the reduction's scalar computation
    }


def test_self_time_takes_nested_events_out_of_their_parent():
    #           0....................100
    # while     [----------------------]
    # body ops     [10-30] [40-90]
    #                        [50-60]        (nested one deeper)
    # after                              [100-120]
    events = [(0, 100), (10, 20), (40, 50), (50, 10), (100, 20)]
    assert profiler.self_times(events) == [30, 20, 40, 10, 20]
    assert sum(profiler.self_times(events)) == 120     # the busy union
    assert profiler.self_times([]) == []
