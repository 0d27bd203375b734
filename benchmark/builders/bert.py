"""BERT-family cells: masked-LM training of `models/bert.py`."""

from __future__ import annotations

import numpy as np

from benchmark.harness import flops
from benchmark.reference import bert as reference

from . import common


def model_config(cfg_json, tiny=False):
    from paddle_tpu.models import BertConfig

    c = dict(cfg_json)
    if tiny:
        c.update(cfg_json["tiny"])
    return BertConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        intermediate_size=c["intermediate_size"],
        max_position=c["max_position_embeddings"],
        type_vocab_size=c["type_vocab_size"],
        hidden_dropout=c["hidden_dropout_prob"],
        attention_dropout=c["attention_probs_dropout_prob"],
        initializer_range=c["initializer_range"],
    )


def mlm_batch(rng, cfg, batch, seq, n_pred, shards=1):
    """One MLM batch (copied from chip_smoke.py::bert_batch). Token ids
    follow a Zipf law and the label of a predicted position is the token
    there, so Adam steps lower the loss measurably. Positions are drawn
    per batch shard: the second value indexes a shard's own flattened
    [batch/shards * seq] rows (what a data-parallel shard_map program
    gathers from), `mask_pos` the whole batch's."""
    ids = np.minimum(rng.zipf(1.3, (batch, seq)), cfg.vocab_size - 1)
    rows = batch // shards * seq
    per = n_pred // shards
    local = np.concatenate([
        rng.choice(rows, per, replace=False) for _ in range(shards)
    ])
    glob = local + np.repeat(np.arange(shards) * rows, per)
    feed = {
        "ids": ids.astype("int32"),
        "types": rng.randint(0, cfg.type_vocab_size,
                             (batch, seq)).astype("int32"),
        "mask": np.ones((batch, seq), "float32"),
        "mask_pos": glob.astype("int32"),
        "labels": ids.reshape(-1)[glob].astype("int32"),
    }
    return feed, local.astype("int32")


def build_train(cfg_json, traffic, chips, tiny, seed):
    """`traffic`: batch (global), seq, mask_share. On several chips the
    program is one shard's (batch / chips rows, its own mask positions)
    and the feed is the global batch."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert_pretrain

    cfg = model_config(cfg_json, tiny)
    batch, seq = traffic["batch"], traffic["seq"]
    n_pred = int(traffic["mask_share"] * batch * seq) // chips * chips
    b_local, p_local = batch // chips, n_pred // chips

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", [b_local, seq], "int64")
        types = fluid.data("types", [b_local, seq], "int64")
        mask = fluid.data("mask", [b_local, seq], "float32")
        mask_pos = fluid.data("mask_pos", [p_local], "int64")
        labels = fluid.data("labels", [p_local], "int64")
        loss = bert_pretrain(ids, types, mask, labels, cfg,
                             mask_pos=mask_pos)
        common.minimize(loss, startup, chips)

    def make_feed(rng):
        feed, local = mlm_batch(rng, cfg, batch, seq, n_pred, shards=chips)
        if chips > 1:
            feed["mask_pos"] = local
        return feed

    def check(exe, scope, rng):
        """Two seeded sequences, repeated to fill the batch, with every
        predicted position drawn from them: each shard then computes the
        logits and the loss the reference computes on the two."""
        import jax

        two = np.minimum(rng.zipf(1.3, (2, seq)), cfg.vocab_size - 1)
        two_types = rng.randint(0, cfg.type_vocab_size, (2, seq))
        pos = rng.randint(0, 2 * seq, p_local)
        lab = two.reshape(-1)[pos]
        reps = batch // 2
        feed = {
            "ids": np.tile(two, (reps, 1)).astype("int32"),
            "types": np.tile(two_types, (reps, 1)).astype("int32"),
            "mask": np.ones((batch, seq), "float32"),
            "mask_pos": np.tile(pos, chips).astype("int32"),
            "labels": np.tile(lab, chips).astype("int32"),
        }
        got, logits = common.test_mode_forward(exe, scope, main, loss, feed)
        params = common.scope_params(
            scope, reference.param_names(cfg.num_layers)
        )
        want, ref_logits = jax.jit(lambda p, *batch: reference.mlm_loss(
            p, *batch, layers=cfg.num_layers, heads=cfg.num_heads,
            hidden_dropout=cfg.hidden_dropout,
            attention_dropout=cfg.attention_dropout,
        ))(params, two, two_types, pos, lab)
        # the first shard's rows: every shard computes the same
        return common.forward_check(got, float(want), logits[:p_local],
                                    ref_logits, traffic)

    return common.TrainBuild(
        main=main, startup=startup, loss=loss, make_feed=make_feed,
        tokens_per_step=batch * seq,
        flops_per_token=flops.encoder_train_flops_per_token(
            cfg.hidden_size, cfg.num_layers, seq, cfg.vocab_size,
            n_pred / (batch * seq),
        ),
        check=check,
    )
