"""What the families' builders share: the AMP recipe, the optimizer (plain
or through `fleet` on several chips), and the test-mode forward of a
training program."""

from __future__ import annotations

import dataclasses
import typing

import numpy as np

LEARNING_RATE = 1e-4


@dataclasses.dataclass
class TrainBuild:
    """A training cell, built: the Programs of one step and what the
    driver needs to feed, count and check them."""

    main: object
    startup: object
    loss: object
    make_feed: typing.Callable      # rng -> {name: host array}
    tokens_per_step: int
    flops_per_token: float          # forward + backward, closed form
    check: typing.Callable          # (exe, scope, rng) -> dict with "ok"
    kernel_cost: typing.Optional[typing.Callable] = None  # -> (flops, bytes)


def amp(opt):
    """bench.py's AMP recipe: bfloat16, static loss scale 1."""
    from paddle_tpu.contrib import mixed_precision as mp

    return mp.decorate(
        opt,
        amp_lists=mp.AutoMixedPrecisionLists(
            custom_white_list={"softmax", "layer_norm"}
        ),
        use_dynamic_loss_scaling=False,
        init_loss_scaling=1.0,
        dest_dtype="bfloat16",
    )


def minimize(loss, startup, chips):
    """AMP Adam on one chip; on several, the same optimizer through
    `fleet.distributed_optimizer` with its default strategy (data
    parallel over every device, shard_map, explicit collectives)."""
    from paddle_tpu.optimizer import Adam

    opt = amp(Adam(LEARNING_RATE))
    if chips == 1:
        opt.minimize(loss, startup)
        return
    from paddle_tpu.fleet import collective as fc
    from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

    fleet = fc.Fleet()
    fleet.init(UserDefinedRoleMaker())
    fleet.distributed_optimizer(
        opt, fc.DistributedStrategy()
    ).minimize(loss, startup)


def test_mode_forward(exe, scope, main, loss, feed):
    """The step's forward in test mode, same scope: the training program
    cloned for test and cut back to the loss and the logits its loss op
    reads (`serving.freeze_program`: `clone(for_test=True)` + prune), so
    the AMP casts stay and the backward and the optimizer go. Returns the
    loss and the logits as the program holds them (rows x vocabulary,
    every shard's rows on several chips), on the host."""
    from paddle_tpu.serving import freeze_program

    (loss_op,) = [op for op in main.global_block.ops
                  if op.type == "softmax_with_cross_entropy"]
    frozen = freeze_program(main, [loss, loss_op.inputs["Logits"][0]],
                            feed_names=tuple(feed))
    if main._mesh is not None:
        # a copied Program drops its mesh: the test-mode forward runs
        # under the layout the step runs under
        from paddle_tpu.parallel import shard_program

        shard_program(frozen.program, main._mesh, main._sharding,
                      mode=main._spmd_mode)
    value, logits = exe.run(frozen.program, feed=feed,
                            fetch_list=list(frozen.fetch_names), scope=scope)
    return float(np.asarray(value).reshape(-1)[0]), np.asarray(logits)


def scope_params(scope, names):
    """{name: host float32 array} of the scope's parameters."""
    return {n: np.asarray(scope.find_var(n), np.float32) for n in names}


def logit_err(got, want):
    """max|diff| / max|reference|, in float32 on the host."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def forward_check(loss, loss_ref, logits, logits_ref, traffic):
    """The test-mode forward against the plain reference: the logits
    within `logits_tol` (max|diff| / max|reference|) and the loss within
    `loss_rtol`."""
    rel = abs(loss - loss_ref) / max(abs(loss_ref), 1e-30)
    err = logit_err(logits, logits_ref)
    rtol, tol = traffic["loss_rtol"], traffic["logits_tol"]
    return {"ok": bool(rel <= rtol and err <= tol),
            "logits_err": err, "logits_tol": tol,
            "logits_compared": list(np.shape(logits_ref)),
            "loss_test_mode": loss, "loss_reference": loss_ref,
            "rel_diff": rel, "rtol": rtol}

