"""Optimizer zoo: op-emitting optimizers, fluid-style.

Reference parity: python/paddle/fluid/optimizer.py (4,304 LoC; SGD :842,
Momentum :936, Adagrad :1600, Adam :1716, Adamax :1982, Dpsgd :2154,
DecayedAdagrad :2249, Adadelta :2359, RMSProp :2478, Ftrl :2666, Lamb :2825,
LarsMomentum :1486). Each optimizer emits one update op per parameter into
the main program; minimize() = append_backward + regularization + clip +
update ops — identical pipeline shape to the reference's
Optimizer.minimize (optimizer.py:796) / apply_gradients (:683).

The meta-optimizers (Recompute/Pipeline/DGC/EMA/ModelAverage/Lookahead) live
in incubate modules and wrap these.
"""

from __future__ import annotations

import numpy as np

from .framework import unique_name
from .framework.backward import append_backward
from .framework.program import (
    Variable,
    default_main_program,
    default_startup_program,
)
from .initializer import Constant


class Optimizer:
    def __init__(
        self,
        learning_rate,
        parameter_list=None,
        regularization=None,
        grad_clip=None,
        name=None,
    ):
        self._learning_rate = learning_rate
        self._parameter_list = parameter_list
        self.regularization = regularization
        self._grad_clip = grad_clip
        self._name = name
        self._lr_var = None
        self._accumulators = {}  # (acc_name, param_name) -> Variable
        self.type = type(self).__name__.lower()

    # -- learning rate ----------------------------------------------------
    def _create_lr(self, block):
        if isinstance(self._learning_rate, Variable):
            return self._learning_rate
        if self._lr_var is not None:
            return self._lr_var
        name = unique_name.generate("learning_rate")
        main = block.program.global_block
        startup = default_startup_program().global_block
        self._lr_var = main.create_parameter(
            name, [1], "float32", trainable=False
        )
        self._lr_var.stop_gradient = True
        startup.create_parameter(name, [1], "float32", trainable=False)
        Constant(float(self._learning_rate))(startup, name, [1], "float32")
        return self._lr_var

    def set_lr(self, value, scope=None):
        """Runtime LR override (dygraph/static parity helper)."""
        from .framework.scope import global_scope
        import jax.numpy as jnp

        self._learning_rate = float(value)
        if self._lr_var is not None:
            (scope or global_scope()).set_var(
                self._lr_var.name, jnp.full([1], float(value), dtype=jnp.float32)
            )

    # -- accumulators ------------------------------------------------------
    def _add_accumulator(self, name, param, fill_value=0.0, shape=None, dtype=None):
        key = (name, param.name)
        if key in self._accumulators:
            return self._accumulators[key]
        param_shaped = shape is None
        shape = list(shape if shape is not None else param.shape)
        dtype = dtype or "float32"
        vname = unique_name.generate(f"{param.name}_{name}")
        main = param.block.program.global_block
        startup = default_startup_program().global_block
        v = main.create_parameter(vname, shape, dtype, trainable=False)
        v.stop_gradient = True
        # tag for sharding bookkeeping: parallel/sparse.shard_sparse_tables
        # row-shards exactly the accumulators of sharded tables
        v._accum_of = param.name
        # elementwise (param-shaped) state shards 1/N under the ZeRO
        # weight-update transpile; explicitly-shaped state (beta-pow
        # scalars) is broadcast into the update and must stay replicated
        v._accum_elementwise = param_shaped
        startup.create_parameter(vname, shape, dtype, trainable=False)
        Constant(fill_value)(startup, vname, shape, dtype)
        self._accumulators[key] = v
        return v

    def _get_accumulator(self, name, param):
        return self._accumulators[(name, param.name)]

    # -- pipeline ----------------------------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        return append_backward(
            loss, parameter_list or self._parameter_list, no_grad_set
        )

    def apply_gradients(self, params_grads):
        if params_grads:
            # anchor to the params' own program, not the ambient default
            block = params_grads[0][0].block.program.global_block
        else:
            block = default_main_program().global_block
        if self._grad_clip is not None:
            params_grads = self._grad_clip.apply(params_grads, block)
        processed = []
        for p, g in params_grads:
            reg = getattr(p, "regularizer", None) or self.regularization
            if reg is not None:
                g = reg.append_regularization_op(p, g, block)
            processed.append((p, g))
        self._create_accumulators(block, [p for p, _ in processed])
        ops = [self._append_optimize_op(block, pg) for pg in processed]
        # a program with update ops is a training step (its XLA module
        # is `jit_train_step` in a capture) unless its owner says more
        if block.program._label is None:
            block.program._label = "train_step"
        return ops

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads)

    def minimize(
        self, loss, startup_program=None, parameter_list=None, no_grad_set=None
    ):
        from .dygraph.varbase import VarBase

        if isinstance(loss, VarBase):
            # eager mode: loss.backward() has populated param._grad; apply
            # updates in place (reference dygraph minimize semantics)
            return self._eager_minimize(parameter_list), []

        # ops must land in the loss's program even if minimize() is called
        # outside its program_guard (fluid wraps minimize the same way)
        from .framework.program import program_guard

        with program_guard(
            loss.block.program, startup_program or default_startup_program()
        ):
            params_grads = self.backward(
                loss, startup_program, parameter_list, no_grad_set
            )
            ops = self.apply_gradients(params_grads)
        return ops, params_grads

    # -- eager (dygraph) path ---------------------------------------------
    def _eager_lr(self):
        # a schedule callable advances its step on every call, so it must be
        # invoked once per minimize (cached below), not once per parameter
        cached = getattr(self, "_eager_lr_value", None)
        if cached is not None:
            return cached
        lr = self._learning_rate
        return float(lr() if callable(lr) else lr)

    def _eager_acc(self, name, p, fill=0.0, shape=None):
        import jax.numpy as jnp

        key = (name, p.name)
        store = self.__dict__.setdefault("_eager_accs", {})
        if key not in store:
            shp = list(shape if shape is not None else p.shape)
            store[key] = jnp.full(shp, fill, dtype=jnp.float32)
        return store[key]

    def _set_eager_acc(self, name, p, value):
        self._eager_accs[(name, p.name)] = value

    def _eager_minimize(self, parameter_list=None):
        params = parameter_list or self._parameter_list or []
        updated = []
        self._eager_lr_value = None
        self._eager_lr_value = self._eager_lr()  # advance schedule ONCE
        try:
            for p in params:
                if not getattr(p, "trainable", True) or p._grad is None:
                    continue
                g = p._grad
                reg = getattr(p, "regularizer", None) or self.regularization
                if reg is not None and getattr(reg, "_coeff", 0.0):
                    g = g + reg._coeff * p.value
                self._eager_update(p, g)
                updated.append(p)
        finally:
            self._eager_lr_value = None
        return updated

    def _eager_update(self, p, g):
        raise NotImplementedError(
            f"{type(self).__name__} has no eager-mode update yet; "
            "use the static-graph path"
        )

    # -- per-optimizer hooks ----------------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError


class SGDOptimizer(Optimizer):
    def _append_optimize_op(self, block, pg):
        p, g = pg
        lr = self._create_lr(block)
        return block.append_op(
            "sgd",
            {"Param": [p.name], "Grad": [g.name], "LearningRate": [lr.name]},
            {"ParamOut": [p.name]},
            {},
        )

    def _eager_update(self, p, g):
        p.set_value(p.value - self._eager_lr() * g)


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum=0.9, use_nesterov=False, **kw):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        v = self._get_accumulator("velocity", p)
        lr = self._create_lr(block)
        return block.append_op(
            "momentum",
            {
                "Param": [p.name],
                "Grad": [g.name],
                "Velocity": [v.name],
                "LearningRate": [lr.name],
            },
            {"ParamOut": [p.name], "VelocityOut": [v.name]},
            {"mu": self._momentum, "use_nesterov": self._use_nesterov},
        )

    def _eager_update(self, p, g):
        lr = self._eager_lr()
        v = self._eager_acc("velocity", p)
        v_new = self._momentum * v + g
        if self._use_nesterov:
            p.set_value(p.value - lr * (g + self._momentum * v_new))
        else:
            p.set_value(p.value - lr * v_new)
        self._set_eager_acc("velocity", p, v_new)


class LarsMomentumOptimizer(Optimizer):
    def __init__(
        self, learning_rate, momentum=0.9, lars_coeff=0.001,
        lars_weight_decay=0.0005, **kw,
    ):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        v = self._get_accumulator("velocity", p)
        lr = self._create_lr(block)
        return block.append_op(
            "lars_momentum",
            {
                "Param": [p.name],
                "Grad": [g.name],
                "Velocity": [v.name],
                "LearningRate": [lr.name],
            },
            {"ParamOut": [p.name], "VelocityOut": [v.name]},
            {
                "mu": self._momentum,
                "lars_coeff": self._lars_coeff,
                "lars_weight_decay": self._lars_weight_decay,
            },
        )


class _AdamBase(Optimizer):
    op_type = "adam"

    def __init__(
        self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kw
    ):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow", p, self._beta1, shape=[1])
            self._add_accumulator("beta2_pow", p, self._beta2, shape=[1])

    def _extra_attrs(self):
        return {}

    def _append_optimize_op(self, block, pg):
        p, g = pg
        lr = self._create_lr(block)
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow", p)
        b2p = self._get_accumulator("beta2_pow", p)
        return block.append_op(
            self.op_type,
            {
                "Param": [p.name],
                "Grad": [g.name],
                "LearningRate": [lr.name],
                "Moment1": [m1.name],
                "Moment2": [m2.name],
                "Beta1Pow": [b1p.name],
                "Beta2Pow": [b2p.name],
            },
            {
                "ParamOut": [p.name],
                "Moment1Out": [m1.name],
                "Moment2Out": [m2.name],
                "Beta1PowOut": [b1p.name],
                "Beta2PowOut": [b2p.name],
            },
            {
                "beta1": self._beta1,
                "beta2": self._beta2,
                "epsilon": self._epsilon,
                **self._extra_attrs(),
            },
        )


class AdamOptimizer(_AdamBase):
    op_type = "adam"


def _adam_eager(opt, p, g, weight_decay=0.0):
    import jax.numpy as jnp

    lr = opt._eager_lr()
    b1, b2, eps = opt._beta1, opt._beta2, opt._epsilon
    m1 = opt._eager_acc("moment1", p)
    m2 = opt._eager_acc("moment2", p)
    b1p = opt._eager_acc("beta1_pow", p, opt._beta1, shape=[1])
    b2p = opt._eager_acc("beta2_pow", p, opt._beta2, shape=[1])
    m1 = b1 * m1 + (1 - b1) * g
    m2 = b2 * m2 + (1 - b2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    upd = lr_t * m1 / (jnp.sqrt(m2) + eps)
    if weight_decay:
        upd = upd + lr * weight_decay * p.value
    p.set_value(p.value - upd.reshape(p.value.shape))
    opt._set_eager_acc("moment1", p, m1)
    opt._set_eager_acc("moment2", p, m2)
    opt._set_eager_acc("beta1_pow", p, b1p * b1)
    opt._set_eager_acc("beta2_pow", p, b2p * b2)


_AdamBase._eager_update = lambda self, p, g: _adam_eager(
    self, p, g, getattr(self, "_weight_decay", 0.0)
)


class AdamWOptimizer(_AdamBase):
    op_type = "adamw"

    def __init__(self, learning_rate=0.001, weight_decay=0.01, **kw):
        super().__init__(learning_rate, **kw)
        self._weight_decay = weight_decay

    def _extra_attrs(self):
        return {"weight_decay": self._weight_decay}


class LambOptimizer(_AdamBase):
    op_type = "lamb"

    def __init__(
        self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
        beta2=0.999, epsilon=1e-6, **kw,
    ):
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self._weight_decay = lamb_weight_decay

    def _extra_attrs(self):
        return {"weight_decay": self._weight_decay}


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, initial_accumulator_value=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, self._init_acc)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m = self._get_accumulator("moment", p)
        lr = self._create_lr(block)
        return block.append_op(
            "adagrad",
            {
                "Param": [p.name], "Grad": [g.name], "Moment": [m.name],
                "LearningRate": [lr.name],
            },
            {"ParamOut": [p.name], "MomentOut": [m.name]},
            {"epsilon": self._epsilon},
        )


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m = self._get_accumulator("moment", p)
        lr = self._create_lr(block)
        return block.append_op(
            "decayed_adagrad",
            {
                "Param": [p.name], "Grad": [g.name], "Moment": [m.name],
                "LearningRate": [lr.name],
            },
            {"ParamOut": [p.name], "MomentOut": [m.name]},
            {"decay": self._decay, "epsilon": self._epsilon},
        )


class RMSPropOptimizer(Optimizer):
    def __init__(
        self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
        centered=False, **kw,
    ):
        super().__init__(learning_rate, **kw)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("momentum_acc", p)
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        lr = self._create_lr(block)
        return block.append_op(
            "rmsprop",
            {
                "Param": [p.name],
                "Grad": [g.name],
                "Moment": [self._get_accumulator("momentum_acc", p).name],
                "MeanSquare": [self._get_accumulator("mean_square", p).name],
                "MeanGrad": [self._get_accumulator("mean_grad", p).name],
                "LearningRate": [lr.name],
            },
            {
                "ParamOut": [p.name],
                "MomentOut": [self._get_accumulator("momentum_acc", p).name],
                "MeanSquareOut": [self._get_accumulator("mean_square", p).name],
                "MeanGradOut": [self._get_accumulator("mean_grad", p).name],
            },
            {
                "decay": self._rho,
                "epsilon": self._epsilon,
                "momentum": self._momentum,
                "centered": self._centered,
            },
        )


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            "adadelta",
            {
                "Param": [p.name],
                "Grad": [g.name],
                "AvgSquaredGrad": [self._get_accumulator("avg_squared_grad", p).name],
                "AvgSquaredUpdate": [
                    self._get_accumulator("avg_squared_update", p).name
                ],
            },
            {
                "ParamOut": [p.name],
                "AvgSquaredGradOut": [
                    self._get_accumulator("avg_squared_grad", p).name
                ],
                "AvgSquaredUpdateOut": [
                    self._get_accumulator("avg_squared_update", p).name
                ],
            },
            {"rho": self._rho, "epsilon": self._epsilon},
        )


class AdamaxOptimizer(Optimizer):
    def __init__(
        self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kw
    ):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow", p, self._beta1, shape=[1])

    def _append_optimize_op(self, block, pg):
        p, g = pg
        lr = self._create_lr(block)
        return block.append_op(
            "adamax",
            {
                "Param": [p.name],
                "Grad": [g.name],
                "LearningRate": [lr.name],
                "Moment": [self._get_accumulator("moment", p).name],
                "InfNorm": [self._get_accumulator("inf_norm", p).name],
                "Beta1Pow": [self._get_accumulator("beta1_pow", p).name],
            },
            {
                "ParamOut": [p.name],
                "MomentOut": [self._get_accumulator("moment", p).name],
                "InfNormOut": [self._get_accumulator("inf_norm", p).name],
                "Beta1PowOut": [self._get_accumulator("beta1_pow", p).name],
            },
            {
                "beta1": self._beta1,
                "beta2": self._beta2,
                "epsilon": self._epsilon,
            },
        )


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        lr = self._create_lr(block)
        return block.append_op(
            "ftrl",
            {
                "Param": [p.name],
                "Grad": [g.name],
                "SquaredAccumulator": [self._get_accumulator("squared", p).name],
                "LinearAccumulator": [self._get_accumulator("linear", p).name],
                "LearningRate": [lr.name],
            },
            {
                "ParamOut": [p.name],
                "SquaredAccumOut": [self._get_accumulator("squared", p).name],
                "LinearAccumOut": [self._get_accumulator("linear", p).name],
            },
            {"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power},
        )


class DpsgdOptimizer(Optimizer):
    def __init__(self, learning_rate, clip=10.0, batch_size=16.0, sigma=1.0, **kw):
        super().__init__(learning_rate, **kw)
        self._clip, self._batch_size, self._sigma = clip, batch_size, sigma

    def _append_optimize_op(self, block, pg):
        p, g = pg
        lr = self._create_lr(block)
        return block.append_op(
            "dpsgd",
            {"Param": [p.name], "Grad": [g.name], "LearningRate": [lr.name]},
            {"ParamOut": [p.name]},
            {
                "clip": self._clip,
                "batch_size": self._batch_size,
                "sigma": self._sigma,
            },
        )


class ProximalGDOptimizer(Optimizer):
    """reference optimizers/proximal_gd_op.cc: SGD step + L1 soft-threshold
    + L2 shrink."""

    def __init__(self, learning_rate, l1_regularization_strength=0.0,
                 l2_regularization_strength=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self._l1 = l1_regularization_strength
        self._l2 = l2_regularization_strength

    def _append_optimize_op(self, block, pg):
        p, g = pg
        lr = self._create_lr(block)
        return block.append_op(
            "proximal_gd",
            {"Param": [p.name], "Grad": [g.name],
             "LearningRate": [lr.name]},
            {"ParamOut": [p.name]},
            {"l1": self._l1, "l2": self._l2},
        )


class ProximalAdagradOptimizer(Optimizer):
    """reference optimizers/proximal_adagrad_op.cc: adagrad-scaled lr into
    the proximal update."""

    def __init__(self, learning_rate, l1_regularization_strength=0.0,
                 l2_regularization_strength=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self._l1 = l1_regularization_strength
        self._l2 = l2_regularization_strength

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m = self._get_accumulator("moment", p)
        lr = self._create_lr(block)
        return block.append_op(
            "proximal_adagrad",
            {"Param": [p.name], "Grad": [g.name], "Moment": [m.name],
             "LearningRate": [lr.name]},
            {"ParamOut": [p.name], "MomentOut": [m.name]},
            {"l1": self._l1, "l2": self._l2},
        )


class DGCMomentumOptimizer(Optimizer):
    """Deep Gradient Compression momentum (reference optimizer.py:1071):
    top-k sparsified gradient exchange with error feedback, momentum
    correction and factor masking (ops/optimizer_ops.py dgc_momentum_step).
    Under a dp mesh the exchange all_gathers (values, indices) pairs —
    2k*nranks words instead of the dense numel. `sparsity` takes the FINAL
    ratio of the reference's schedule (static shapes fix k); steps before
    `rampup_begin_step` run the dense warmup path."""

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 num_trainers=None, **kw):
        super().__init__(learning_rate, **kw)
        if use_nesterov:
            raise NotImplementedError(
                "DGCMomentumOptimizer: use_nesterov is not implemented in "
                "the fused dgc_momentum_step op"
            )
        self._momentum = momentum
        self._rampup_begin = float(rampup_begin_step)
        self._sparsity = float(sparsity[-1] if isinstance(
            sparsity, (list, tuple)) else sparsity)
        self._nranks = num_trainers or 1

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("dgc_u", p)
            self._add_accumulator("dgc_v", p)
            if self._rampup_begin > 0:  # step counter only drives rampup
                self._add_accumulator("dgc_step", p, fill_value=0.0,
                                      shape=[1])

    def _append_optimize_op(self, block, pg):
        p, g = pg
        u = self._get_accumulator("dgc_u", p)
        v = self._get_accumulator("dgc_v", p)
        lr = self._create_lr(block)
        ins_step = {}
        if self._rampup_begin > 0:
            step = self._get_accumulator("dgc_step", p)
            block.append_op(
                "increment", {"X": [step.name]}, {"Out": [step.name]},
                {"step": 1.0},
            )
            ins_step = {"CurrentStep": [step.name]}
        # with no rampup, omitting CurrentStep selects the op's static
        # sparse-only path (no dead dense branch compiled)
        return block.append_op(
            "dgc_momentum_step",
            {"Param": [p.name], "Grad": [g.name], "U": [u.name],
             "V": [v.name], "LearningRate": [lr.name], **ins_step},
            {"ParamOut": [p.name], "UOut": [u.name], "VOut": [v.name],
             "SentRatio": [block.create_var(
                 name=f"{p.name}@DGC_RATIO", shape=[1], dtype="float32"
             ).name]},
            {"momentum": self._momentum, "sparsity": self._sparsity,
             "rampup_begin_step": self._rampup_begin,
             "nranks": self._nranks},
        )


SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adamax = AdamaxOptimizer
Adagrad = AdagradOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer
Dpsgd = DpsgdOptimizer
ProximalGD = ProximalGDOptimizer
ProximalAdagrad = ProximalAdagradOptimizer
DGCMomentum = DGCMomentumOptimizer


# ---------------------------------------------------------------------------
# meta-optimizers: EMA / ModelAverage / Lookahead
# (reference python/paddle/fluid/optimizer.py: ModelAverage :2997, EMA :3306,
# LookaheadOptimizer :4150)
# ---------------------------------------------------------------------------

import contextlib as _contextlib

from .framework.state import create_persistable_var, create_step_counter


def _make_counter(name_hint, init=0.0, dtype="float32"):
    return create_persistable_var(name_hint, [1], dtype, init)


def _make_state_like(param, name_hint, init=0.0, dtype=None, shape=None):
    return create_persistable_var(
        name_hint,
        list(shape if shape is not None else param.shape),
        dtype or param.dtype,
        init,
    )


class _SwappingAverager:
    """Shared apply()/restore() scope-swap machinery for EMA/ModelAverage.

    The swap phases run between train steps, off the hot path, so host-side
    scope mutation (a couple of device round-trips) is the right tool — the
    reference built dedicated apply/restore Programs instead
    (optimizer.py:3306 area)."""

    def __init__(self):
        self._backup = {}

    def _averaged_value(self, scope, pname):
        raise NotImplementedError

    @_contextlib.contextmanager
    def apply(self, executor=None, need_restore=True):
        from .framework.scope import global_scope

        scope = global_scope()
        self._backup = {}
        for pname in self._param_names():
            self._backup[pname] = scope.find_var(pname)
            scope.set_var(pname, self._averaged_value(scope, pname))
        try:
            yield
        finally:
            if need_restore:
                self.restore(executor)

    def restore(self, executor=None):
        from .framework.scope import global_scope

        scope = global_scope()
        for pname, val in self._backup.items():
            scope.set_var(pname, val)
        self._backup = {}


class ExponentialMovingAverage(_SwappingAverager):
    """EMA of trainable parameters, updated in-graph each step.

    update() appends `ema = decay_t * ema + (1-decay_t) * param` ops to the
    main program (they fuse into the train step's XLA computation — the
    reference ran separate kernels, optimizer.py:3306). With thres_steps the
    decay ramps as min(decay, (1+step)/(10+step)). apply() swaps in the
    bias-corrected average ema / (1 - prod(decay_t)); restore() swaps back.
    """

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        super().__init__()
        self._decay = float(decay)
        self._thres_steps = thres_steps
        self._name = name or "ema"
        self._pairs = {}  # param_name -> ema_name
        self._decay_pow_name = None

    def _param_names(self):
        return list(self._pairs)

    def update(self):
        from .framework.program import default_main_program
        from . import layers

        main = default_main_program()
        params = [p for p in main.all_parameters() if p.trainable]
        blk = main.global_block

        if self._thres_steps is not None:
            # reference semantics (optimizer.py:3306): thres_steps is the
            # caller's step Variable and the decay ramps as
            # min(decay, (1+t)/(10+t)); a numeric thres clamps an internal
            # counter (created only for this branch)
            if isinstance(self._thres_steps, Variable):
                t = layers.cast(self._thres_steps, "float32")
            else:
                step_v = create_step_counter(self._name + "_step")
                t = layers.elementwise_min(
                    layers.cast(step_v, "float32"),
                    layers.fill_constant(
                        [1], "float32", float(self._thres_steps)
                    ),
                )
            decay_t = layers.elementwise_min(
                layers.fill_constant([1], "float32", self._decay),
                (t + 1.0) / (t + 10.0),
            )
        else:
            decay_t = layers.fill_constant([1], "float32", self._decay)

        # running product of decay_t, for bias correction at apply()
        pow_v = _make_counter(self._name + "_decay_pow", init=1.0)
        prod = layers.elementwise_mul(pow_v, decay_t)
        blk.append_op("assign", {"X": [prod.name]}, {"Out": [pow_v.name]}, {})
        self._decay_pow_name = pow_v.name

        for p in params:
            ema = _make_state_like(p, p.name + "_" + self._name)
            new = layers.elementwise_add(
                layers.elementwise_mul(ema, decay_t),
                layers.elementwise_mul(p, 1.0 - decay_t),
            )
            blk.append_op("assign", {"X": [new.name]}, {"Out": [ema.name]}, {})
            self._pairs[p.name] = ema.name

    def _averaged_value(self, scope, pname):
        pow_t = np.asarray(scope.find_var(self._decay_pow_name))
        debias = max(1.0 - float(pow_t.reshape(-1)[0]), 1e-12)
        ema_val = scope.find_var(self._pairs[pname])
        return (ema_val / debias).astype(ema_val.dtype)


class ModelAverage(_SwappingAverager):
    """Windowed average of parameters (reference optimizer.py:2997).

    Two-tier accumulation mirroring the reference's rotating partial sums:
    (sum_cur, cnt_cur) accumulate every step; when cnt_cur reaches the
    effective window clip(average_window_rate * num_updates,
    min_average_window, max_average_window) the current tier shifts to
    (sum_old, cnt_old) and restarts — so apply() always averages over at
    least one full window once warm (never a fresh-restart handful of
    samples). All in-graph mask-selects, no host control flow. apply()
    swaps params for (sum_cur+sum_old)/(cnt_cur+cnt_old).
    """

    def __init__(
        self,
        average_window_rate=0.15,
        min_average_window=10000,
        max_average_window=10000,
        name=None,
    ):
        super().__init__()
        self.average_window = float(average_window_rate)
        self.min_average_window = int(min_average_window)
        self.max_average_window = int(max_average_window)
        self._name = name or "model_avg"
        self._state = {}  # param_name -> (sum_cur, cnt_cur, sum_old, cnt_old)
        self._build()

    def _param_names(self):
        return list(self._state)

    def _build(self):
        from .framework.program import default_main_program
        from . import layers

        main = default_main_program()
        blk = main.global_block
        # shared step counter: effective window scales with total updates
        # (reference semantics: window = clip(rate * num_updates, min, max))
        g = create_step_counter(self._name + "_num_updates")
        eff_window = layers.elementwise_min(
            layers.fill_constant([1], "float32", float(self.max_average_window)),
            layers.elementwise_max(
                layers.fill_constant([1], "float32", float(self.min_average_window)),
                layers.cast(g, "float32") * self.average_window,
            ),
        )
        one = layers.fill_constant([1], "int32", 1)
        for p in [q for q in main.all_parameters() if q.trainable]:
            sum_cur = _make_state_like(p, p.name + "_avg_sum", dtype="float32")
            cnt_cur = _make_counter(p.name + "_avg_cnt", dtype="int32")
            sum_old = _make_state_like(p, p.name + "_avg_sum_old", dtype="float32")
            cnt_old = _make_counter(p.name + "_avg_cnt_old", dtype="int32")
            cond = layers.greater_equal(
                layers.cast(cnt_cur, "float32"), eff_window
            )
            shift = layers.cast(cond, "float32")
            keep = 1.0 - shift
            new_sum_old = layers.elementwise_add(
                layers.elementwise_mul(sum_cur, shift, axis=0),
                layers.elementwise_mul(sum_old, keep, axis=0),
            )
            # counters stay int32 end-to-end (float32 math would stall at
            # 2^24); select with `where` instead of mask arithmetic
            new_cnt_old = layers.where(cond, cnt_cur, cnt_old)
            new_sum_cur = layers.elementwise_add(
                layers.elementwise_mul(sum_cur, keep, axis=0),
                layers.cast(p, "float32"),
            )
            zero = layers.fill_constant([1], "int32", 0)
            new_cnt_cur = layers.elementwise_add(
                layers.where(cond, zero, cnt_cur), one
            )
            for new, tgt in (
                (new_sum_old, sum_old), (new_cnt_old, cnt_old),
                (new_sum_cur, sum_cur), (new_cnt_cur, cnt_cur),
            ):
                blk.append_op("assign", {"X": [new.name]}, {"Out": [tgt.name]}, {})
            self._state[p.name] = (
                sum_cur.name, cnt_cur.name, sum_old.name, cnt_old.name
            )

    def _averaged_value(self, scope, pname):
        sc, cc, so, co = self._state[pname]
        s = scope.find_var(sc) + scope.find_var(so)
        c = int(np.asarray(scope.find_var(cc)).reshape(-1)[0]) + int(
            np.asarray(scope.find_var(co)).reshape(-1)[0]
        )
        c = max(c, 1.0)
        orig = self._backup[pname]
        return (s / c).astype(orig.dtype).reshape(orig.shape)


class LookaheadOptimizer:
    """Lookahead (k slow-weight sync, reference optimizer.py:4150): wraps an
    inner optimizer; every k steps slow += alpha*(fast-slow), fast = slow.
    The k-step condition is a mask-select in-graph (no host branch)."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        assert inner_optimizer is not None
        assert 0.0 <= alpha <= 1.0
        assert k >= 1 and isinstance(k, int)
        self.inner_optimizer = inner_optimizer
        self.alpha = float(alpha)
        self.k = int(k)

    def minimize(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        from . import layers
        from .framework.program import program_guard

        ops, params_grads = self.inner_optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set
        )
        main = loss.block.program
        blk = main.global_block
        startup = (startup_program or default_startup_program()).global_block

        with program_guard(main, startup_program or default_startup_program()):
            step_v = create_step_counter("lookahead_step")
            # int mod: a float32 counter would lose exactness past 2^24 steps
            kf = layers.fill_constant([1], "int32", self.k)
            rem = layers.elementwise_mod(step_v, kf)
            sync = layers.cast(
                layers.equal(rem, layers.fill_constant([1], "int32", 0)),
                "float32",
            )
            for p, _ in params_grads:
                slow = blk.create_parameter(
                    unique_name.generate(p.name + "_slow"), p.shape, p.dtype,
                    trainable=False,
                )
                slow.stop_gradient = True
                startup.create_parameter(slow.name, p.shape, p.dtype, trainable=False)
                # slow starts equal to fast: copy the initialized param value
                # (runs after the param's init ops in the startup program)
                startup.append_op("assign", {"X": [p.name]}, {"Out": [slow.name]}, {})
                merged = p * self.alpha + slow * (1.0 - self.alpha)
                new_slow = layers.elementwise_add(
                    layers.elementwise_mul(merged, sync, axis=0),
                    layers.elementwise_mul(slow, 1.0 - sync, axis=0),
                )
                new_fast = layers.elementwise_add(
                    layers.elementwise_mul(new_slow, sync, axis=0),
                    layers.elementwise_mul(p, 1.0 - sync, axis=0),
                )
                blk.append_op("assign", {"X": [new_slow.name]}, {"Out": [slow.name]}, {})
                blk.append_op("assign", {"X": [new_fast.name]}, {"Out": [p.name]}, {})
        return ops, params_grads
