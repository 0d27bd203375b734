"""`GPTGenerator.generate` chooses on the device (ISSUE 28): both
programs end in `greedy_token`, the chosen token is the next step's feed
as a device array, no step waits for its fetch and the host reads a
batch's ids once. Held here for BOTH decoders, CPU, tiny sizes: the ids
are the host argmax chain of the logits the same two executables fetch,
a request compiles nothing the benchmark's probe has not compiled, and
nothing of one batch survives into the next."""

import numpy as np
import pytest

import jax

from paddle_tpu import observability as obs
from paddle_tpu.framework.registry import OpView
from paddle_tpu.framework.scope import scope_guard
from paddle_tpu.ops.kv_cache import _greedy_token
from paddle_tpu.serving import GPTGenerator
from paddle_tpu.serving.generate import NEXT_TOKEN_VAR, TOKENS_VAR

NEW = 10


def gpt_generator():
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig.tiny()
    cfg.use_fused_attention = False
    gen = GPTGenerator(cfg, batch=4, context_len=12, max_len=12 + NEW)
    gen.init_params(seed=11)
    return gen


def afmoe_generator():
    """Four rows prefilled in two blocks of two (`row_ids` 0 and 2)."""
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeDecoder

    gen = GPTGenerator(AfmoeDecoder(AfmoeConfig.tiny(prefill_rows=2)),
                       batch=4, context_len=24, max_len=24 + NEW)
    gen.init_params(seed=7)
    return gen


DECODERS = pytest.mark.parametrize(
    "make", [gpt_generator, afmoe_generator], ids=["gpt", "afmoe"])


def prompts_for(gen, seed):
    """Rows that differ, so that rows choose different tokens."""
    return np.random.RandomState(seed).randint(
        0, gen.cfg.vocab_size, (gen.batch, gen.context_len)).astype(np.int64)


def host_chain(gen, prompts, steps):
    """What the benchmark's probes do (benchmark/builders/gpt2.py,
    afmoe.py): the two programs with host feeds and the generator's own
    fetch lists, the argmax on the host. [batch, steps + 1] ids."""
    exe, scope = gen.executor, gen.scope
    gen.reset()
    with scope_guard(scope):
        logits = np.concatenate([
            np.asarray(exe.run(gen.prefill_prog, feed=feed, scope=scope,
                               fetch_list=gen._prefill_fetch)[0])[:, -1, :]
            for feed in gen.prefill_feeds(prompts)
        ])
        chain = [np.argmax(logits, axis=-1)]
        for t in range(steps):
            logits = np.asarray(exe.run(
                gen.decode_prog,
                feed={"token_ids": chain[-1][:, None].astype(np.int64),
                      "pos_ids": np.array([[gen.context_len + t]], np.int64)},
                fetch_list=gen._decode_fetch, scope=scope,
            )[0])[:, -1, :]
            chain.append(np.argmax(logits, axis=-1))
    return np.stack(chain, axis=1)


@DECODERS
def test_generate_returns_the_host_argmax_chain_of_the_probed_logits(make):
    gen = make()
    prompts = prompts_for(gen, 3)
    want = host_chain(gen, prompts, NEW - 1)
    assert len({tuple(r) for r in want.tolist()}) == gen.batch
    got = gen.generate(prompts, NEW)
    assert got.dtype == np.int64 and got.shape == (gen.batch, NEW)
    np.testing.assert_array_equal(got, want)


@DECODERS
def test_a_request_compiles_nothing_the_probe_has_not(make):
    """After the probe's sequence (reset, the two programs with host
    feeds) and one `reset()`, a whole `generate()` adds no executable to
    the Executor and no backend compile to `jax.monitoring`, the meter
    of the benchmark's window: the fed device array has the shape and
    dtype of the probe's host feed, and `generate` runs no `jnp`
    computation of its own."""
    from benchmark.harness.meter import CompileMeter

    gen = make()
    prompts = prompts_for(gen, 4)
    host_chain(gen, prompts, 2)
    gen.reset()
    meter = CompileMeter()
    before = obs.get_counters().get("executor.compile_count", 0)
    cached = len(gen.executor._cache)
    gen.generate(prompts_for(gen, 5), NEW)
    assert obs.get_counters().get("executor.compile_count", 0) == before
    assert len(gen.executor._cache) == cached == 3
    assert meter.since()["compiles"] == 0
    # the meter does count: a new shape is one compile
    jax.jit(lambda x: x + 1)(np.zeros(NEW + 1, np.float32))
    assert meter.since()["compiles"] == 1


@DECODERS
def test_the_token_handed_over_is_the_probes_feed_on_the_device(make):
    gen = make()
    prompts = prompts_for(gen, 6)
    got = gen.generate(prompts, NEW)
    nxt, tokens = (gen.scope.find_var(n) for n in (NEXT_TOKEN_VAR, TOKENS_VAR))
    host_feed = jax.numpy.asarray(np.zeros((gen.batch, 1), np.int64))
    assert isinstance(nxt, jax.Array)
    assert (nxt.shape, nxt.dtype) == (host_feed.shape, host_feed.dtype)
    assert tokens.shape == (gen.batch, gen.max_len - gen.context_len)
    np.testing.assert_array_equal(np.asarray(tokens)[:, :NEW], got)
    np.testing.assert_array_equal(np.asarray(nxt)[:, 0], got[:, -1])
    # what `reset()` zeroes includes both
    gen.reset()
    for name in (NEXT_TOKEN_VAR, TOKENS_VAR):
        assert not np.asarray(gen.scope.find_var(name)).any()


@DECODERS
def test_consecutive_batches_share_nothing(make):
    """Two batches on different prompts, each against its own chain;
    the second is shorter, so columns the first wrote lie beyond it."""
    gen = make()
    first, second = prompts_for(gen, 7), prompts_for(gen, 8)
    got_first = gen.generate(first, NEW)
    got_second = gen.generate(second, NEW - 3)
    assert (got_first[:, :NEW - 3] != got_second).any()
    np.testing.assert_array_equal(got_second,
                                  host_chain(gen, second, NEW - 4))
    np.testing.assert_array_equal(got_first, host_chain(gen, first, NEW - 1))
    np.testing.assert_array_equal(gen.generate(first, NEW), got_first)


@pytest.mark.parametrize("block", [False, True], ids=["batch", "row_block"])
def test_greedy_token_op(block):
    """First index on a tie, the last position's logits, the column
    `Pos + column`; a block of rows lands at `Row` of both arrays."""
    logits = np.zeros((2, 3, 7), np.float32)
    logits[0, -1, [2, 5]] = 1.0         # a tie: 2
    logits[1, -1, 6] = 3.0
    logits[:, 0, 0] = 9.0               # not the last position
    tokens = np.full((4, 5), -1, np.int32)
    ins = {"Logits": [logits], "Tokens": [tokens],
           "Pos": [np.array([[9]], np.int32)]}
    if block:
        ins.update(Row=[np.array([2], np.int32)],
                   Next=[np.full((4, 1), -1, np.int32)])
    else:
        ins["Tokens"] = [tokens[:2]]
    out = _greedy_token(None, OpView("greedy_token", {"column": -6}), ins)
    row = 2 if block else 0
    want = np.array(ins["Tokens"][0])
    want[row:row + 2, 3] = [2, 6]
    np.testing.assert_array_equal(out["TokensOut"][0], want)
    nxt = np.asarray(out["NextOut"][0])
    assert nxt.dtype == np.int32
    np.testing.assert_array_equal(nxt[row:row + 2, 0], [2, 6])
    if block:
        assert nxt.shape == (4, 1) and (nxt[:2] == -1).all()
        del ins["Next"]
        with pytest.raises(Exception, match="Next"):
            _greedy_token(None, OpView("greedy_token", {}), ins)
