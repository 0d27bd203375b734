"""The plain references against `models/bert.py` and `models/gpt.py` at
the `tiny` sizes, float32, test mode, on the CPU. The tolerance is
float32 rounding through two layers: a reference that left out a term
(a bias, a (1 - p) factor, the causal mask) misses it by orders of
magnitude."""

import numpy as np
import pytest

from benchmark.builders import bert as bert_builder
from benchmark.builders import common
from benchmark.builders import gpt2 as gpt2_builder
from benchmark.harness import manifest as mf
from benchmark.reference import bert as bert_ref
from benchmark.reference import gpt2 as gpt2_ref

RTOL = 2e-5


def _run(build_graph, feed):
    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        fetch = build_graph(fluid)
    scope, exe = Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    (out,) = exe.run(main, feed=feed, fetch_list=[fetch], scope=scope)
    return np.asarray(out), scope


def test_bert_reference_matches_the_model():
    from paddle_tpu.models import bert_pretrain

    cfg = bert_builder.model_config(mf.config(mf.load(), "bert_base"),
                                    tiny=True)
    rng = np.random.RandomState(0)
    b, s, p = 2, 32, 10
    feed, _ = bert_builder.mlm_batch(rng, cfg, b, s, p)

    def graph(fluid):
        ids = fluid.data("ids", [b, s], "int64")
        types = fluid.data("types", [b, s], "int64")
        mask = fluid.data("mask", [b, s], "float32")
        pos = fluid.data("mask_pos", [p], "int64")
        labels = fluid.data("labels", [p], "int64")
        return bert_pretrain(ids, types, mask, labels, cfg, is_test=True,
                             mask_pos=pos)

    got, scope = _run(graph, feed)
    params = common.scope_params(scope, bert_ref.param_names(cfg.num_layers))
    want, _ = bert_ref.mlm_loss(
        params, feed["ids"], feed["types"], feed["mask_pos"],
        feed["labels"], layers=cfg.num_layers, heads=cfg.num_heads,
        hidden_dropout=cfg.hidden_dropout,
        attention_dropout=cfg.attention_dropout,
    )
    assert float(got.reshape(-1)[0]) == pytest.approx(float(want), rel=RTOL)


def test_gpt2_reference_matches_the_model_logits_and_loss():
    from paddle_tpu.models.gpt import gpt_lm_loss, gpt_logits

    cfg = gpt2_builder.model_config(mf.config(mf.load(), "gpt2_small"),
                                    tiny=True)
    rng = np.random.RandomState(1)
    b, s = 2, 24
    ids = rng.randint(0, cfg.vocab_size, (b, s)).astype("int32")
    kw = dict(layers=cfg.num_layers, heads=cfg.num_heads,
              hidden_dropout=cfg.hidden_dropout,
              attention_dropout=cfg.attention_dropout)

    got, scope = _run(
        lambda fluid: gpt_logits(fluid.data("ids", [b, s], "int64"), cfg,
                                 is_test=True),
        {"ids": ids},
    )
    params = common.scope_params(scope, gpt2_ref.param_names(cfg.num_layers))
    want = np.asarray(gpt2_ref.last_logits(params, ids, **kw))
    assert np.max(np.abs(got[:, -1, :] - want)) <= RTOL * np.max(np.abs(want))

    got, scope = _run(
        lambda fluid: gpt_lm_loss(fluid.data("ids", [b, s], "int64"), cfg,
                                  is_test=True),
        {"ids": ids},
    )
    params = common.scope_params(scope, gpt2_ref.param_names(cfg.num_layers))
    want = gpt2_ref.lm_loss(params, ids, chunk=7, **kw)
    assert float(got.reshape(-1)[0]) == pytest.approx(float(want), rel=RTOL)


def test_a_reference_without_the_test_mode_factor_is_caught():
    from paddle_tpu.models.gpt import gpt_logits

    cfg = gpt2_builder.model_config(mf.config(mf.load(), "gpt2_small"),
                                    tiny=True)
    ids = np.random.RandomState(2).randint(0, cfg.vocab_size, (1, 16))
    got, scope = _run(
        lambda fluid: gpt_logits(fluid.data("ids", [1, 16], "int64"), cfg,
                                 is_test=True),
        {"ids": ids.astype("int32")},
    )
    params = common.scope_params(scope, gpt2_ref.param_names(cfg.num_layers))
    wrong = np.asarray(gpt2_ref.last_logits(
        params, ids, layers=cfg.num_layers, heads=cfg.num_heads,
        hidden_dropout=0.0, attention_dropout=cfg.attention_dropout,
    ))
    err = np.max(np.abs(got[:, -1, :] - wrong)) / np.max(np.abs(wrong))
    assert err > 10 * RTOL
