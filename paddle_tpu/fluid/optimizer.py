"""Optimizers: append update ops onto the program.

Parity: reference ``python/paddle/fluid/optimizer.py:54`` — ``minimize`` =
``append_backward`` + ``apply_gradients``; accumulators are persistable scope
vars; LR is a graph var (scheduler output or constant). 13 concrete
optimizers + wrappers (ModelAverage, EMA, Lookahead, Recompute).

All update math executes inside the single compiled train step with donated
buffers — an optimizer step costs zero extra memory traffic beyond the
reads/writes themselves.
"""

import numpy as np

from . import framework, unique_name
from .backward import append_backward
from .framework import Variable, default_main_program, default_startup_program
from .initializer import Constant
from .layer_helper import LayerHelper

__all__ = [
    "SGD", "Momentum", "Adagrad", "Adam", "Adamax", "Dpsgd", "DecayedAdagrad",
    "Adadelta", "RMSProp", "Ftrl", "Lamb",
    "SGDOptimizer", "MomentumOptimizer", "AdagradOptimizer", "AdamOptimizer",
    "AdamaxOptimizer", "DpsgdOptimizer", "DecayedAdagradOptimizer",
    "AdadeltaOptimizer", "RMSPropOptimizer", "FtrlOptimizer", "LambOptimizer",
    "LarsMomentumOptimizer", "DGCMomentumOptimizer",
    "ModelAverage", "ExponentialMovingAverage", "LookaheadOptimizer",
    "RecomputeOptimizer", "PipelineOptimizer", "GradientMergeOptimizer",
]


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None,
                 grad_clip=None):
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._name = name
        self._grad_clip = grad_clip
        self._accumulators = {}  # acc_name -> {param_name: var}
        self._lr_var = None
        self.type = self.__class__.__name__.replace("Optimizer", "").lower()
        # dygraph (eager) optimizer state: per-param accumulators, their
        # names for state_dict keys, and checkpoint state restored by
        # set_dict awaiting first allocation
        self._eager_state = {}
        self._eager_names = {}
        self._loaded_state = {}

    # -- learning rate ------------------------------------------------------
    def _create_global_learning_rate(self):
        if isinstance(self._learning_rate, Variable):
            self._lr_var = self._learning_rate
            return
        if self._lr_var is not None:
            return
        helper = LayerHelper("learning_rate")
        name = unique_name.generate("learning_rate")
        self._lr_var = helper.main_program.global_block().create_var(
            name=name, shape=(1,), dtype="float32", persistable=True,
            stop_gradient=True,
        )
        sb = helper.startup_program.global_block()
        sv = sb.create_var(name=name, shape=(1,), dtype="float32", persistable=True)
        Constant(float(self._learning_rate))(sv, sb)

    def _global_learning_rate(self):
        return self._lr_var

    # -- accumulators -------------------------------------------------------
    def _add_accumulator(self, name, param, fill_value=0.0, shape=None, dtype=None):
        if name in self._accumulators and param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        helper = LayerHelper("accum")
        shape = shape if shape is not None else param.shape
        dtype = dtype or param.dtype
        var_name = unique_name.generate("%s_%s" % (param.name, name))
        var = helper.main_program.global_block().create_var(
            name=var_name, shape=shape, dtype=dtype, persistable=True,
            stop_gradient=True,
        )
        # moments share the param's TP layout so the optimizer update is
        # local to each shard (no resharding per step)
        pspec = getattr(param, "shard_spec", None)
        if pspec is not None and tuple(shape) == tuple(param.shape):
            var.shard_spec = pspec
        sb = helper.startup_program.global_block()
        sv = sb.create_var(name=var_name, shape=shape, dtype=dtype, persistable=True)
        Constant(float(fill_value))(sv, sb)
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- the template -------------------------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, parameters_and_grads):
        pass

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set, callbacks)

    def apply_gradients(self, params_grads):
        block = default_main_program().global_block()
        self._create_global_learning_rate()

        # grad clipping (reference clip.py append_gradient_clip_ops)
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        else:
            from .clip import append_gradient_clip_ops

            params_grads = append_gradient_clip_ops(params_grads)

        # weight decay / regularization (reference regularizer.append_regularization_ops)
        from .regularizer import append_regularization_ops

        params_grads = append_regularization_ops(params_grads, self.regularization)

        self._create_accumulators(block, [p for p, _ in params_grads])
        for pg in params_grads:
            self._append_optimize_op(block, pg)
        self._finish_update(block, params_grads)
        return params_grads

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None):
        if framework.in_dygraph_mode():
            return self._dygraph_minimize(loss, parameter_list,
                                          grad_clip=grad_clip)
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads

    # -- dygraph (eager) path ----------------------------------------------
    def _dygraph_minimize(self, loss, parameter_list, grad_clip=None):
        """Eager update: runs loss.backward() if grads are absent, applies
        the optional ``grad_clip`` strategy (dygraph_grad_clip.py, the
        reference's optimizer.py:680 hook), then the optimizer op eagerly
        per param (reference dygraph minimize)."""
        from .dygraph.base import VarBase

        tracer = framework._dygraph_tracer()
        if parameter_list is None:
            raise ValueError("dygraph minimize needs parameter_list "
                             "(e.g. model.parameters())")
        if tracer._tape:
            loss.backward()
        import jax.numpy as jnp

        if isinstance(self._learning_rate, VarBase):
            lr = float(self._learning_rate.numpy().reshape(-1)[0])
        elif callable(self._learning_rate):
            lr = float(self._learning_rate())
        else:
            lr = float(self._learning_rate)
        params_grads = []
        with tracer._no_grad_guard():
            for p in parameter_list:
                if p is None or p._grad is None or p.stop_gradient:
                    continue
                params_grads.append((p, p._grad))
            # clip RAW grads, then fold regularization in — the static
            # path's apply_gradients order (clip ops before
            # append_regularization_ops), so both modes update identically.
            # The call-site grad_clip wins over the constructor-level one.
            clip = grad_clip if grad_clip is not None else self._grad_clip
            if clip is not None:
                params_grads = clip(params_grads)
            regularized = []
            for p, g in params_grads:
                if getattr(p, "regularizer", None) is not None or \
                        self.regularization is not None:
                    reg = getattr(p, "regularizer", None) or self.regularization
                    from .regularizer import L1DecayRegularizer

                    if isinstance(reg, L1DecayRegularizer):
                        g = g + reg._coeff * jnp.sign(p._ivar)
                    else:
                        g = g + reg._coeff * p._ivar
                regularized.append((p, g))
            params_grads = regularized
            for p, g in params_grads:
                p._ivar = self._eager_update(p, g, lr)
        return None, params_grads

    def _eager_state_for(self, p, names_and_init):
        import jax.numpy as jnp

        st = self._eager_state.get(id(p))
        if st is None:
            st = {}
            pending = self._loaded_state
            for name, init in names_and_init:
                key = "%s@%s" % (p.name, name)
                if key in pending:          # set_dict restore, by name
                    st[name] = jnp.asarray(pending.pop(key))
                elif np.isscalar(init):
                    st[name] = jnp.full((1,), init, dtype=p._ivar.dtype)
                else:
                    st[name] = jnp.full(p._ivar.shape, 0.0, dtype=p._ivar.dtype)
            self._eager_state[id(p)] = st
            self._eager_names[id(p)] = p.name
        return st

    # -- dygraph checkpointing (reference optimizer.py:100 state_dict /
    # :131 set_dict): eager accumulators keyed "<param>@<slot>", plus
    # global_step when the LR is a LearningRateDecay object ------------
    def state_dict(self):
        if not framework.in_dygraph_mode():
            raise RuntimeError(
                "optimizer.state_dict() is dygraph-only; static graph "
                "optimizer state lives in scope persistables "
                "(fluid.io.save)")
        # still-pending restored state (set_dict before any minimize)
        # must survive a re-save — it simply hasn't allocated yet
        out = dict(self._loaded_state)
        names = self._eager_names
        for pid, st in self._eager_state.items():
            for slot, arr in st.items():
                out["%s@%s" % (names[pid], slot)] = np.asarray(arr)
        from .dygraph.learning_rate_scheduler import LearningRateDecay

        if isinstance(self._learning_rate, LearningRateDecay):
            out["global_step"] = np.asarray(
                [self._learning_rate.step_num], np.int64)
        return out

    def set_dict(self, state_dict):
        """Restore from ``state_dict``. Accumulators apply lazily by
        param NAME at first use (eager state allocates on first
        minimize); global_step steps the LR decay object now."""
        state = dict(state_dict)
        gs = state.pop("global_step", None)
        if gs is not None:
            from .dygraph.learning_rate_scheduler import LearningRateDecay

            if isinstance(self._learning_rate, LearningRateDecay):
                self._learning_rate.step_num = int(
                    np.asarray(gs).ravel()[0])
            else:
                import logging

                logging.getLogger(__name__).warning(
                    "set_dict: checkpoint carries global_step=%d but "
                    "this optimizer's learning_rate is not a "
                    "LearningRateDecay object — the schedule position "
                    "is dropped", int(np.asarray(gs).ravel()[0]))
        self._loaded_state = state
        # already-allocated eager state updates in place
        names = self._eager_names
        for pid, st in self._eager_state.items():
            for slot in list(st):
                key = "%s@%s" % (names[pid], slot)
                if key in self._loaded_state:
                    import jax.numpy as jnp

                    st[slot] = jnp.asarray(self._loaded_state.pop(key))

    set_state_dict = set_dict

    def _eager_update(self, p, g, lr):
        raise NotImplementedError(
            "%s has no eager update; use static graph mode" % type(self).__name__)

    def _lr_for(self, param):
        """Per-param LR multiplier (param.optimize_attr['learning_rate'])."""
        mult = 1.0
        if hasattr(param, "optimize_attr"):
            mult = param.optimize_attr.get("learning_rate", 1.0)
        if mult == 1.0:
            return self._lr_var
        from .layers import nn

        return nn.scale(self._lr_var, scale=mult)


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)

    def _eager_update(self, p, g, lr):
        return p._ivar - lr * g

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "sgd",
            inputs={"Param": [param], "Grad": [grad],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param]},
        )


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, use_nesterov=False,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _eager_update(self, p, g, lr):
        st = self._eager_state_for(p, [("velocity", None)])
        v_new = self._momentum * st["velocity"] + g
        st["velocity"] = v_new
        if self._use_nesterov:
            return p._ivar - (g + self._momentum * v_new) * lr
        return p._ivar - lr * v_new

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            "momentum",
            inputs={"Param": [param], "Grad": [grad], "Velocity": [velocity],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param], "VelocityOut": [velocity]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
        )


class LarsMomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            "lars_momentum",
            inputs={"Param": [param], "Grad": [grad], "Velocity": [velocity],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param], "VelocityOut": [velocity]},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay},
        )


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, regularization=None,
                 name=None, initial_accumulator_value=0.0):
        super().__init__(learning_rate, regularization, name)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, fill_value=self._initial)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            "adagrad",
            inputs={"Param": [param], "Grad": [grad], "Moment": [moment],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param], "MomentOut": [moment]},
            attrs={"epsilon": self._epsilon},
        )


class AdamOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, name=None, lazy_mode=False):
        super().__init__(learning_rate, regularization, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _eager_update(self, p, g, lr):
        import jax.numpy as jnp

        st = self._eager_state_for(
            p, [("m", None), ("v", None), ("b1p", self._beta1),
                ("b2p", self._beta2)])
        st["m"] = self._beta1 * st["m"] + (1 - self._beta1) * g
        st["v"] = self._beta2 * st["v"] + (1 - self._beta2) * jnp.square(g)
        b1p, b2p = st["b1p"].reshape(()), st["b2p"].reshape(())
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        new_p = p._ivar - lr_t * st["m"] / (jnp.sqrt(st["v"]) + self._epsilon)
        st["b1p"] = st["b1p"] * self._beta1
        st["b2p"] = st["b2p"] * self._beta2
        return new_p

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=(1,))
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2,
                                  shape=(1,))

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            "adam",
            inputs={"Param": [param], "Grad": [grad], "Moment1": [m1],
                    "Moment2": [m2], "Beta1Pow": [b1p], "Beta2Pow": [b2p],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param], "Moment1Out": [m1], "Moment2Out": [m2],
                     "Beta1PowOut": [b1p], "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon},
        )


class AdamaxOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=(1,))

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "adamax",
            inputs={"Param": [param], "Grad": [grad],
                    "Moment": [self._get_accumulator("moment", param)],
                    "InfNorm": [self._get_accumulator("inf_norm", param)],
                    "Beta1Pow": [self._get_accumulator("beta1_pow_acc", param)],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param],
                     "MomentOut": [self._get_accumulator("moment", param)],
                     "InfNormOut": [self._get_accumulator("inf_norm", param)],
                     "Beta1PowOut": [self._get_accumulator("beta1_pow_acc", param)]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon},
        )


class DpsgdOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16.0,
                 sigma=1.0):
        super().__init__(learning_rate)
        self._clip, self._batch_size, self._sigma = clip, batch_size, sigma

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "dpsgd",
            inputs={"Param": [param], "Grad": [grad],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param]},
            attrs={"clip": self._clip, "batch_size": self._batch_size,
                   "sigma": self._sigma},
        )


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            "decayed_adagrad",
            inputs={"Param": [param], "Grad": [grad], "Moment": [moment],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param], "MomentOut": [moment]},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
        )


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("__avg_squared_grad", p)
            self._add_accumulator("__avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        g = self._get_accumulator("__avg_squared_grad", param)
        u = self._get_accumulator("__avg_squared_update", param)
        return block.append_op(
            "adadelta",
            inputs={"Param": [param], "Grad": [grad], "AvgSquaredGrad": [g],
                    "AvgSquaredUpdate": [u],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param], "AvgSquaredGradOut": [g],
                     "AvgSquaredUpdateOut": [u]},
            attrs={"epsilon": self._epsilon, "rho": self._rho},
        )


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("momentum", p)
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "rmsprop",
            inputs={"Param": [param], "Grad": [grad],
                    "Moment": [self._get_accumulator("momentum", param)],
                    "MeanSquare": [self._get_accumulator("mean_square", param)],
                    "MeanGrad": [self._get_accumulator("mean_grad", param)],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param],
                     "MomentOut": [self._get_accumulator("momentum", param)],
                     "MeanSquareOut": [self._get_accumulator("mean_square", param)],
                     "MeanGradOut": [self._get_accumulator("mean_grad", param)]},
            attrs={"decay": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum, "centered": self._centered},
        )


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        sq = self._get_accumulator("squared", param)
        lin = self._get_accumulator("linear", param)
        return block.append_op(
            "ftrl",
            inputs={"Param": [param], "Grad": [grad],
                    "SquaredAccumulator": [sq], "LinearAccumulator": [lin],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param], "SquaredAccumOut": [sq],
                     "LinearAccumOut": [lin]},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power},
        )


class LambOptimizer(AdamOptimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, regularization=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, regularization,
                         name)
        self._weight_decay = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        wd = self._weight_decay
        if self._exclude_fn is not None and self._exclude_fn(param):
            wd = 0.0
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            "lamb",
            inputs={"Param": [param], "Grad": [grad], "Moment1": [m1],
                    "Moment2": [m2], "Beta1Pow": [b1p], "Beta2Pow": [b2p],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param], "Moment1Out": [m1], "Moment2Out": [m2],
                     "Beta1PowOut": [b1p], "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "weight_decay": wd},
        )


class DGCMomentumOptimizer(MomentumOptimizer):
    """Momentum with deep-gradient-compression top-k sparsification
    (reference ``optimizer.py:870``, ``operators/dgc_op.cc``): each step the
    ``dgc`` op applies momentum correction + error-feedback accumulation and
    emits a masked-dense gradient with only the top ``1-sparsity`` fraction
    of entries non-zero (paddle_tpu/parallel/dgc.py); the param update is a
    plain SGD step on that compressed gradient. Under ``GradAllReduce`` the
    allreduce moves onto the compressed gradient (the reference's
    sparse_all_reduce_op_handle). Steps before ``rampup_begin_step`` behave
    as plain momentum, gated in-graph on a step counter."""

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None,
                 regularization=None, name=None):
        super().__init__(learning_rate, momentum, use_nesterov, regularization,
                         name)
        self._rampup_begin_step = int(rampup_begin_step)
        self._rampup_step = int(rampup_step)
        self._sparsity = list(sparsity)
        self._dgc_step_var = None

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("dgc_u", p)
            self._add_accumulator("dgc_v", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        u = self._get_accumulator("dgc_u", param)
        v = self._get_accumulator("dgc_v", param)
        inputs = {"U": [u], "V": [v], "Grad": [grad]}
        if self._rampup_begin_step > 0 or len(self._sparsity) > 1:
            if self._dgc_step_var is None:
                from .layers import nn

                self._dgc_step_var = nn.autoincreased_step_counter(
                    counter_name="@DGC_STEP@", begin=0)
            inputs["CurrentStep"] = [self._dgc_step_var]
        compressed = block.create_var(
            name=unique_name.generate(grad.name + ".dgc"), shape=grad.shape,
            dtype=grad.dtype, stop_gradient=True)
        block.append_op(
            "dgc", inputs=inputs,
            outputs={"UOut": [u], "VOut": [v], "GradOut": [compressed]},
            attrs={"m": self._momentum,
                   "sparsity": [float(s) for s in self._sparsity],
                   "rampup_begin_step": self._rampup_begin_step,
                   "rampup_step": self._rampup_step})
        return block.append_op(
            "sgd",
            inputs={"Param": [param], "Grad": [compressed],
                    "LearningRate": [self._lr_for(param)]},
            outputs={"ParamOut": [param]})


# -- wrappers ----------------------------------------------------------------


class ModelAverage(Optimizer):
    """Maintains WINDOWED running averages of params; ``apply()`` swaps them
    in for eval (reference ``optimizer.py:2512`` +
    ``operators/average_accumulates_op.cc``).

    Window semantics: accumulation restarts whenever the in-window count
    reaches ``clip(average_window_rate * num_updates, min_average_window,
    max_average_window)``; the just-closed window is kept so the served
    average always covers (current + previous) windows — a bounded window,
    not an unbounded running sum. The restart is gated in-graph (no
    divergent control flow under jit):

        r        = (num_acc + 1 >= W)            # restart gate, 0/1
        sum_prev' = r * (sum + p) + (1-r) * sum_prev
        old_num'  = r * (num_acc + 1) + (1-r) * old_num
        sum'      = (1-r) * (sum + p)
        num_acc'  = (1-r) * (num_acc + 1)
        average   = (sum + sum_prev) / (num_acc + old_num)
    """

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, regularization=None, name=None):
        super().__init__(0.0, regularization, name)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self.params_sums = {}
        program = default_main_program()
        block = program.global_block()
        from .layers import nn, tensor

        steps = nn.autoincreased_step_counter(
            counter_name="@MODEL_AVERAGE_STEP@", begin=1)
        stepsf = tensor.cast(steps, "float32")
        # W = clip(rate * num_updates, min_window, max_window)
        w = nn.clip(nn.scale(stepsf, scale=float(average_window_rate)),
                    float(min_average_window), float(max_average_window))
        for param in program.all_parameters():
            if not param.trainable:
                continue
            s = self._add_accumulator("sum", param)
            sp = self._add_accumulator("sum_prev", param)
            n = self._add_accumulator("num_acc", param, shape=(1,))
            on = self._add_accumulator("old_num_acc", param, shape=(1,))
            n1 = nn.scale(n, scale=1.0, bias=1.0)          # num_acc + 1
            s1 = nn.elementwise_add(s, param)              # sum + p
            rb = n1 >= w
            r = tensor.cast(rb, "float32")                 # restart gate
            keep = nn.scale(r, scale=-1.0, bias=1.0)       # 1 - r
            new_sp = nn.elementwise_add(
                nn.elementwise_mul(s1, r, axis=-1),
                nn.elementwise_mul(sp, keep, axis=-1))
            new_on = nn.elementwise_add(
                nn.elementwise_mul(n1, r), nn.elementwise_mul(on, keep))
            new_s = nn.elementwise_mul(s1, keep, axis=-1)
            new_n = nn.elementwise_mul(n1, keep)
            for src, dst in ((new_sp, sp), (new_on, on), (new_s, s),
                             (new_n, n)):
                block.append_op("assign", inputs={"X": [src]},
                                outputs={"Out": [dst]})
            self.params_sums[param.name] = (s, sp, n, on)

    import contextlib

    @contextlib.contextmanager
    def apply(self, executor, need_restore=True):
        from .executor import global_scope

        scope = global_scope()
        backups = {}
        for pname, (s, sp, n, on) in self.params_sums.items():
            backups[pname] = scope.find_var(pname)
            ssum = (np.asarray(scope.find_var(s.name))
                    + np.asarray(scope.find_var(sp.name)))
            num = float(np.asarray(scope.find_var(n.name)).reshape(-1)[0]
                        + np.asarray(scope.find_var(on.name)).reshape(-1)[0])
            if num > 0:
                scope.set_var(pname, (ssum / num).astype(backups[pname].dtype))
        try:
            yield
        finally:
            if need_restore:
                for pname, val in backups.items():
                    scope.set_var(pname, val)

    def restore(self, executor):
        pass


class ExponentialMovingAverage:
    """EMA of params updated in-graph (reference ``optimizer.py:2814``)."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._name = name or ""
        self._ema_vars = {}
        program = default_main_program()
        block = program.global_block()
        helper = LayerHelper("ema")
        for param in program.all_parameters():
            if not param.trainable:
                continue
            name = unique_name.generate(param.name + ".ema")
            ema = block.create_var(name=name, shape=param.shape, dtype=param.dtype,
                                   persistable=True, stop_gradient=True)
            sb = default_startup_program().global_block()
            sv = sb.create_var(name=name, shape=param.shape, dtype=param.dtype,
                               persistable=True)
            Constant(0.0)(sv, sb)
            self._ema_vars[param.name] = ema
            # ema = decay*ema + (1-decay)*param, written each step
            from .layers import nn

            tmp = nn.elementwise_add(
                nn.scale(ema, scale=self._decay),
                nn.scale(param, scale=1.0 - self._decay),
            )
            block.append_op("assign", inputs={"X": [tmp]}, outputs={"Out": [ema]})

    import contextlib

    @contextlib.contextmanager
    def apply(self, executor, need_restore=True):
        from .executor import global_scope

        scope = global_scope()
        backups = {}
        for pname, ema in self._ema_vars.items():
            backups[pname] = scope.find_var(pname)
            v = scope.find_var(ema.name)
            if v is not None:
                scope.set_var(pname, v)
        try:
            yield
        finally:
            if need_restore:
                for pname, val in backups.items():
                    scope.set_var(pname, val)

    def update(self):
        pass  # updates happen in-graph

    def restore(self, executor):
        pass


class LookaheadOptimizer:
    """Reference ``optimizer.py:3634``: slow/fast weights; every k steps slow
    += alpha*(fast-slow), fast = slow."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        self.inner_optimizer = inner_optimizer
        self.alpha = alpha
        self.k = k

    def minimize(self, loss, startup_program=None):
        opt_ops, params_grads = self.inner_optimizer.minimize(loss,
                                                              startup_program)
        program = loss.block.program
        block = program.global_block()
        helper = LayerHelper("lookahead")
        from .layers import nn, tensor

        step = nn.autoincreased_step_counter(counter_name="@LOOKAHEAD_STEP@")
        stepf = tensor.cast(step, "float32")
        kf = float(self.k)
        # sync_flag = 1.0 when step % k == 0
        mod = nn.elementwise_sub(
            stepf, nn.scale(nn.elementwise_floordiv(
                tensor.cast(step, "int64"),
                tensor.fill_constant([1], "int64", self.k)).astype("float32"),
                scale=kf))
        is_sync = tensor.cast(mod < 0.5, "float32")
        for param, _ in params_grads:
            name = unique_name.generate(param.name + ".slow")
            slow = block.create_var(name=name, shape=param.shape,
                                    dtype=param.dtype, persistable=True,
                                    stop_gradient=True)
            sb = default_startup_program().global_block()
            sv = sb.create_var(name=name, shape=param.shape, dtype=param.dtype,
                               persistable=True)
            Constant(0.0)(sv, sb)
            new_slow = nn.elementwise_add(
                slow, nn.scale(nn.elementwise_sub(param, slow),
                               scale=self.alpha))
            merged_slow = nn.elementwise_add(
                nn.elementwise_mul(is_sync, new_slow),
                nn.elementwise_mul(nn.scale(is_sync, scale=-1.0, bias=1.0), slow),
            )
            merged_fast = nn.elementwise_add(
                nn.elementwise_mul(is_sync, merged_slow),
                nn.elementwise_mul(nn.scale(is_sync, scale=-1.0, bias=1.0), param),
            )
            block.append_op("assign", inputs={"X": [merged_slow]},
                            outputs={"Out": [slow]})
            block.append_op("assign", inputs={"X": [merged_fast]},
                            outputs={"Out": [param]})
        return opt_ops, params_grads


class RecomputeOptimizer:
    """Activation recomputation (reference ``optimizer.py:3341``). Under the
    functional-autodiff design the checkpoint list is carried on the autodiff
    op; its lowering wraps forward segments in ``jax.checkpoint`` so XLA
    rematerializes instead of saving activations. What survives a segment's
    boundary: the checkpoint vars, and the values a producer inside the
    segment marked as dear to make for what they hold
    (``kernels.common.keep_across_recompute``: the flash and select
    attention tiers' output and row logsumexp, ``sparse_index``'s mask,
    the expert layer's dispatch plan and the router's chosen scores and
    ids, the delta rule kernel's output, entering states and inverses) -
    the producer decides, there is nothing to set here."""

    def __init__(self, optimizer):
        self._optimizer = optimizer
        self._checkpoints = None

    def _set_checkpoints(self, checkpoints):
        self._checkpoints = checkpoints

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None, checkpoints=None):
        return append_backward(loss, parameter_list, no_grad_set,
                               checkpoints=self._checkpoints or checkpoints)

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def apply_optimize(self, loss, startup_program, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        return self.apply_gradients(params_grads), params_grads


class PipelineOptimizer:
    """Pipeline parallelism (reference ``optimizer.py:3048``). Records the
    cut points on the program; ``CompiledProgram.with_pipeline`` consumes
    them to run the forward as a GPipe schedule over the 'pp' mesh axis
    (stages dispatched by lax.switch, activations via ppermute — see
    ``compiler.py:_wrap_step_pipeline`` and paddle_tpu/parallel/pipeline.py).
    ``place_list``/``concurrency_list``/``queue_size`` are the reference's
    host-thread knobs and are meaningless in the single-SPMD-program design;
    accepted for API parity, ignored."""

    def __init__(self, optimizer, cut_list=None, place_list=None,
                 concurrency_list=None, queue_size=30, sync_steps=1,
                 start_cpu_core_id=0):
        self._optimizer = optimizer
        self._cut_list = cut_list or []

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        result = self._optimizer.minimize(loss, startup_program,
                                          parameter_list, no_grad_set)
        program = loss.block.program
        program._pipeline_cut_vars = [
            [v.name for v in cut] if isinstance(cut, (list, tuple)) else [cut.name]
            for cut in self._cut_list
        ]
        return result


class GradientMergeOptimizer:
    """Gradient accumulation / batch merge (capability of the reference's
    ``ir/multi_batch_merge_pass.cc``: replicate forward/backward, merge
    grads, apply once per k micro-batches).

    TPU-first redesign: instead of cloning the graph k times, grads
    accumulate into persistable buffers every step and the inner
    optimizer's *entire* update subgraph is gated arithmetically —
    its writes to persistable state (params, moments, LR counters) are
    SSA-renamed to shadows and committed via
    ``state' = state + sync * (shadow - state)`` where
    ``sync = (step % k == 0)``. One static XLA graph, no divergent
    control flow, momentum/Adam state advances exactly once per merge —
    bit-matching a plain optimizer fed the k-step mean gradient.
    """

    def __init__(self, inner_optimizer, k_steps=1, avg=True):
        self.inner_optimizer = inner_optimizer
        self.k_steps = int(k_steps)
        self.avg = avg

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return self.inner_optimizer.backward(
            loss, startup_program, parameter_list, no_grad_set, callbacks)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        if self.k_steps <= 1:
            return self.inner_optimizer.minimize(
                loss, startup_program, parameter_list, no_grad_set)
        from .framework import program_guard
        from .layers import nn, tensor

        main = loss.block.program
        startup = startup_program or default_startup_program()
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        block = main.global_block()
        with program_guard(main, startup):
            # per-instance counter: two merge-wrapped optimizers in one
            # program (e.g. GAN G/D) must not share or double-increment it
            step = nn.autoincreased_step_counter(
                counter_name=unique_name.generate("@GRADMERGE_STEP@"),
                begin=1)
            from .layers.control_flow import equal

            k = tensor.fill_constant([1], "int64", self.k_steps)
            # sync == 1.0 on steps k, 2k, ...
            sync = tensor.cast(
                equal(nn.elementwise_mod(step, k),
                      tensor.zeros([1], "int64")), "float32")

            # accumulate: acc_new = acc + g; merged grad = acc_new / k
            acc_pairs = []  # (acc var, acc_new var)
            merged = []
            for p, g in params_grads:
                # unique per instance: a param shared by two merge-wrapped
                # optimizers must not alias one accumulator
                acc = tensor.create_global_var(
                    shape=list(p.shape), value=0.0, dtype=p.dtype,
                    persistable=True,
                    name=unique_name.generate(p.name + "@GRAD@MERGE"))
                acc_new = nn.elementwise_add(acc, g)
                gm = (nn.scale(acc_new, scale=1.0 / self.k_steps)
                      if self.avg else acc_new)
                acc_pairs.append((acc, acc_new))
                merged.append((p, gm))

            # inner optimizer appends its update ops; record the range
            start_idx = len(block.ops)
            optimize_ops = self.inner_optimizer.apply_gradients(merged)
            end_idx = len(block.ops)
            shadows = self._shadow_persistable_writes(block, start_idx,
                                                      end_idx)
            # commit gated state: state' = state + sync * (shadow - state)
            for orig_name, shadow_name in shadows.items():
                orig = block.var(orig_name)
                shadow = block.var(shadow_name)
                gate = tensor.cast(sync, orig.dtype)
                delta = nn.elementwise_mul(
                    nn.elementwise_sub(shadow, orig), gate, axis=-1)
                tensor.assign(nn.elementwise_add(orig, delta), output=orig)
            # reset accumulators on sync: acc = acc_new * (1 - sync)
            keep = nn.elementwise_sub(tensor.ones([1], "float32"), sync)
            for acc, acc_new in acc_pairs:
                gate = tensor.cast(keep, acc.dtype)
                tensor.assign(nn.elementwise_mul(acc_new, gate, axis=-1),
                              output=acc)
        return optimize_ops, params_grads

    @staticmethod
    def _shadow_persistable_writes(block, start_idx, end_idx):
        """SSA-rename persistable outputs of ops[start:end] to fresh
        non-persistable shadow vars; later reads inside the range follow
        the rename. Returns {original_name: final_shadow_name}."""
        latest = {}
        n_shadow = 0
        for op in block.ops[start_idx:end_idx]:
            for slot, names in op.inputs.items():
                op.inputs[slot] = [latest.get(n, n) for n in names]
            for slot, names in op.outputs.items():
                new_names = []
                for n in names:
                    v = block._find_var_recursive(n)
                    if v is not None and getattr(v, "persistable", False):
                        shadow = "%s@GM_SHADOW_%d" % (n, n_shadow)
                        n_shadow += 1
                        block.create_var(name=shadow, shape=v.shape,
                                         dtype=v.dtype, stop_gradient=True)
                        latest[n] = shadow
                        new_names.append(shadow)
                    else:
                        new_names.append(n)
                op.outputs[slot] = new_names
        return latest


SGD = SGDOptimizer
Momentum = MomentumOptimizer
LarsMomentum = LarsMomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
Dpsgd = DpsgdOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
