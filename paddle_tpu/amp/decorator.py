"""Static-graph AMP optimizer decorator with dynamic loss scaling.

Reference: /root/reference/python/paddle/fluid/contrib/mixed_precision/
decorator.py — `decorate` (:215) wraps an optimizer in
OptimizerWithMixedPrecision: rewrite_program casts the forward, the loss is
scaled before backward, `check_finite_and_unscale` + `update_loss_scaling`
ops guard the optimizer step.

TPU note: with bfloat16 the exponent range matches fp32, so dynamic loss
scaling is rarely required — `use_dynamic_loss_scaling=False` +
init_loss_scaling=1.0 is the recommended TPU configuration; the full fp16
machinery is kept for parity.
"""
from __future__ import annotations

from ..core.program import OpRole, default_startup_program, unique_name
from ..static import layers
from ..static.layer_helper import LayerHelper
from .fp16_lists import AutoMixedPrecisionLists
from .fp16_utils import rewrite_program
from ..profiler import Phase

__all__ = ["decorate", "OptimizerWithMixedPrecision"]


class OptimizerWithMixedPrecision:
    """decorator.py:37 parity."""

    def __init__(self, optimizer, amp_lists, init_loss_scaling,
                 use_dynamic_loss_scaling, incr_every_n_steps,
                 decr_every_n_nan_or_inf, incr_ratio, decr_ratio,
                 dest_dtype="bfloat16"):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._init_loss_scaling = float(init_loss_scaling)
        self._use_dynamic_loss_scaling = use_dynamic_loss_scaling
        self._incr_every_n_steps = incr_every_n_steps
        self._decr_every_n_nan_or_inf = decr_every_n_nan_or_inf
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._dest_dtype = dest_dtype
        self._loss_scaling = None
        self._scaled_loss = None

    def get_loss_scaling(self):
        return self._loss_scaling

    def get_scaled_loss(self):
        return self._scaled_loss

    def _create_scale_vars(self):
        self._loss_scaling = layers.create_global_var(
            [1], self._init_loss_scaling, "float32", persistable=True,
            name=unique_name("loss_scaling"))
        if self._use_dynamic_loss_scaling:
            self._good_steps = layers.create_global_var(
                [1], 0, "int32", persistable=True,
                name=unique_name("good_steps"))
            self._bad_steps = layers.create_global_var(
                [1], 0, "int32", persistable=True,
                name=unique_name("bad_steps"))

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        """decorator.py:142 — rewrite program, scale loss, backward."""
        program = loss.block.program
        from ..core.program import program_guard
        with program_guard(program, startup_program
                           or default_startup_program()):
            with Phase("amp/rewrite"):
                rewrite_program(program, self._amp_lists, self._dest_dtype)
            # loss may now be low precision; bring it to fp32 for scaling
            if loss.dtype != "float32":
                loss = layers.cast(loss, "float32")
            self._create_scale_vars()
            with program._op_role_guard(OpRole.Forward):
                self._scaled_loss = layers.elementwise_mul(
                    loss, self._loss_scaling)
            params_grads = self._optimizer.backward(
                self._scaled_loss, startup_program, parameter_list,
                no_grad_set, callbacks)
        return params_grads

    def apply_gradients(self, params_grads):
        """decorator.py:167 — unscale & inf-check before the real step."""
        program = params_grads[0][0].block.program
        from ..core.program import program_guard
        with program_guard(program), \
                program._op_role_guard(OpRole.Optimize):
            grads = [g for _, g in params_grads]
            helper = LayerHelper("check_finite_and_unscale")
            found_inf = helper.create_variable_for_type_inference("bool")
            outs = [helper.block.create_var(
                name=unique_name(g.name + "@UNSCALED"), shape=g.shape,
                dtype=g.dtype, stop_gradient=True) for g in grads]
            helper.append_op(
                "check_finite_and_unscale",
                inputs={"X": grads, "Scale": [self._loss_scaling]},
                outputs={"Out": outs, "FoundInfinite": [found_inf]})
            if self._use_dynamic_loss_scaling:
                outs2 = [helper.block.create_var(
                    name=unique_name(g.name + "@GUARDED"), shape=g.shape,
                    dtype=g.dtype, stop_gradient=True) for g in grads]
                helper.append_op(
                    "update_loss_scaling",
                    inputs={"X": outs, "FoundInfinite": [found_inf],
                            "PrevLossScaling": [self._loss_scaling],
                            "InGoodSteps": [self._good_steps],
                            "InBadSteps": [self._bad_steps]},
                    outputs={"Out": outs2,
                             "LossScaling": [self._loss_scaling],
                             "OutGoodSteps": [self._good_steps],
                             "OutBadSteps": [self._bad_steps]},
                    attrs={"incr_every_n_steps": self._incr_every_n_steps,
                           "decr_every_n_nan_or_inf":
                               self._decr_every_n_nan_or_inf,
                           "incr_ratio": self._incr_ratio,
                           "decr_ratio": self._decr_ratio})
                outs = outs2
            new_pg = [(p, g) for (p, _), g in zip(params_grads, outs)]
        return self._optimizer.apply_gradients(new_pg)

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        ops = self.apply_gradients(params_grads)
        # recorded like Optimizer.minimize does: the PS transpiler and
        # static.gradient_merge read the pairing off the program
        loss.block.program._ps_params_grads = params_grads
        return ops, params_grads

    def __getattr__(self, item):
        return getattr(self.__dict__["_optimizer"], item)


def decorate(optimizer, amp_lists=None, init_loss_scaling=2 ** 15,
             incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
             incr_ratio=2.0, decr_ratio=0.8,
             use_dynamic_loss_scaling=True, dest_dtype="bfloat16"):
    """contrib/mixed_precision/decorator.py:215 parity."""
    return OptimizerWithMixedPrecision(
        optimizer, amp_lists, init_loss_scaling, use_dynamic_loss_scaling,
        incr_every_n_steps, decr_every_n_nan_or_inf, incr_ratio, decr_ratio,
        dest_dtype)
