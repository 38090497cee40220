"""Optimizers (``paddle.optimizer`` parity), as pure pytree transforms.

Reference: python/paddle/optimizer/{optimizer,adamw,momentum,lamb}.py and the
fused CUDA kernels paddle/phi/kernels/gpu/{adamw,fused_adam,lamb}_kernel.cu.
On TPU a "fused multi-tensor optimizer kernel" is simply the XLA-fused update
over the whole parameter pytree inside the compiled step — no hand fusion
needed.  Design:

- ``opt.init(params) -> state`` and ``opt.apply(grads, state, params) ->
  (new_params, new_state)`` are the pure core (used by jit.TrainStep).
- ``multi_precision`` master weights (fp32 copies of low-precision params)
  follow the reference's MPType pattern: update in fp32, cast back to the
  param dtype, keep the fp32 master in optimizer state.
- The paddle-style stateful surface (``opt.step()``/``clear_grad``) works
  eagerly for small-model/debug use via the owning Layer captured from
  ``parameters=model.parameters()``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp

from ..nn.clip import ClipGradBase, ClipGradByGlobalNorm
from ..nn.layer import Layer, ParameterList, raw_params
from ..observability.regions import region
from . import lr as lr_mod
from .lr import LRScheduler

PyTree = Any


def _lr_value(lr, step):
    if isinstance(lr, LRScheduler):
        return lr.lr_at(step)
    return jnp.asarray(lr, jnp.float32)


class Optimizer:
    """Base optimizer: pure functional core + paddle-style surface."""

    def __init__(self, learning_rate=0.001, parameters: Optional[ParameterList] = None,
                 weight_decay=0.0, grad_clip: Optional[ClipGradBase] = None,
                 multi_precision=False, apply_decay_param_fun: Optional[Callable] = None):
        self._lr = learning_rate
        self.weight_decay = weight_decay or 0.0
        # paddle.regularizer objects are accepted wherever a scalar is
        # (reference: optimizer.py regularization= / weight_decay=)
        from ..regularizer import L1Decay, L2Decay
        self._l1_coeff = 0.0
        if isinstance(self.weight_decay, L1Decay):
            self._l1_coeff = self.weight_decay.coeff
            self._wd_coeff = 0.0
        elif isinstance(self.weight_decay, L2Decay):
            self._wd_coeff = self.weight_decay.coeff
        else:
            self._wd_coeff = float(self.weight_decay)
        self.grad_clip = grad_clip
        self.multi_precision = multi_precision
        self.master_grad = False  # set by amp.decorate(master_grad=True)
        self.apply_decay_param_fun = apply_decay_param_fun
        self._owner: Optional[Layer] = None
        self._names = None
        if isinstance(parameters, ParameterList):
            self._owner = parameters.owner
            self._names = parameters.names
        self._eager_state = None

    # ---- functional core --------------------------------------------------

    def init(self, params: PyTree) -> PyTree:
        state = {"step": jnp.zeros((), jnp.int32)}
        if self.multi_precision:
            state["master"] = jax.tree.map(
                lambda p: p.astype(jnp.float32)
                if jnp.issubdtype(p.dtype, jnp.floating) and p.dtype != jnp.float32
                else None, params)
        state.update(self._init_slots(params))
        return state

    def _init_slots(self, params: PyTree) -> Dict[str, PyTree]:
        return {}

    def _update_one(self, name, p, g, lr, state_slots, step):
        raise NotImplementedError

    def _update_leaf(self, name, p, g, lr, state_slots, step, wd, dtype):
        """One dense leaf as ``apply`` holds it: ``p`` the float32
        parameter or master, ``g`` the gradient in the dtype it arrived
        in, ``dtype`` the dtype the model keeps the parameter in.  Returns
        (new float32 ``p``, the same in ``dtype``, new slots).  An
        optimizer whose kernel reads the gradient and writes the
        low-precision copy itself overrides this; the rule is
        ``_update_one``, in float32."""
        new_p, new_slots = self._update_one(
            name, p, g.astype(jnp.float32), lr, state_slots, step, wd)
        return new_p, new_p.astype(dtype), new_slots

    def _decay_mask(self, params: Dict[str, jax.Array]) -> Dict[str, bool]:
        if self.apply_decay_param_fun is None:
            return {k: True for k in params}
        return {k: bool(self.apply_decay_param_fun(k)) for k in params}

    def _update_rows(self, name, p, rg, lr, slots, step, wd):
        """Rows-sparse update (grad is a RowsGrad).  Default: densify and
        run the dense rule; SGD/Adam override with true sparse updates
        (reference: phi selected_rows kernels)."""
        return self._update_one(name, p, rg.to_dense().astype(jnp.float32),
                                lr, slots, step, wd)

    def apply(self, grads: Dict[str, jax.Array], state: PyTree,
              params: Dict[str, jax.Array]):
        """Pure update. grads may cover a subset of params (frozen ones
        skipped).  A grad leaf may be a ``sparse.RowsGrad`` — it bypasses
        grad_clip/master_grad promotion (reference: SelectedRows grads are
        exempt from global-norm clip in the dense path) and routes to the
        optimizer's sparse rule."""
        from ..sparse.rows import RowsGrad
        rows_grads = {k: g for k, g in grads.items()
                      if isinstance(g, RowsGrad)}
        grads = {k: g for k, g in grads.items()
                 if not isinstance(g, RowsGrad)}
        if getattr(self, "master_grad", False):
            # amp master_grad: promote low-precision grads before clipping
            # so the global-norm (and every later consumer) sees fp32
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32)
                if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
        if self.grad_clip is not None:
            with region("clip"):
                grads = self.grad_clip(grads)
        step = state["step"]
        lr = _lr_value(self._lr, step)
        masters = state.get("master", {})
        new_params, new_state = dict(params), {k: dict(v) if isinstance(v, dict) else v
                                               for k, v in state.items()}
        decay_mask = self._decay_mask(params)
        for name, g in grads.items():
            p = params[name]
            master = masters.get(name) if isinstance(masters, dict) else None
            p_compute = master if master is not None else p
            slots = {k: v[name] for k, v in state.items()
                     if isinstance(v, dict) and k not in ("master",) and name in v}
            wd = self._wd_coeff if decay_mask.get(name, True) else 0.0
            if self._l1_coeff and decay_mask.get(name, True):
                # L1Decay: subgradient of coeff*|w| added to the grad
                g = g + self._l1_coeff * jnp.sign(p_compute)
            new_p, new_low, new_slots = self._update_leaf(
                name, p_compute.astype(jnp.float32), g, lr, slots, step, wd,
                p.dtype)
            if master is not None:
                new_state["master"][name] = new_p
            new_params[name] = new_low
            for k, v in new_slots.items():
                new_state[k][name] = v
        for name, rg in rows_grads.items():
            p = params[name]
            master = masters.get(name) if isinstance(masters, dict) else None
            p_compute = master if master is not None else p
            slots = {k: v[name] for k, v in state.items()
                     if isinstance(v, dict) and k not in ("master",) and name in v}
            wd = self._wd_coeff if decay_mask.get(name, True) else 0.0
            new_p, new_slots = self._update_rows(
                name, p_compute.astype(jnp.float32), rg, lr, slots, step, wd)
            if master is not None:
                new_state["master"][name] = new_p
            new_params[name] = new_p.astype(p.dtype)
            for k, v in new_slots.items():
                new_state[k][name] = v
        new_state["step"] = step + 1
        return new_params, new_state

    # ---- paddle-style eager surface --------------------------------------

    def step(self):
        if self._owner is None:
            raise RuntimeError("pass parameters=model.parameters() to use .step()")
        if not hasattr(self, "_eager_grads") or self._eager_grads is None:
            raise RuntimeError(
                "no gradients staged: call opt.set_grads(grads) first, or use "
                "the compiled paddle_tpu.jit.TrainStep path")
        params = raw_params(self._owner)
        if self._eager_state is None:
            self._eager_state = self.init(params)
        new_params, self._eager_state = self.apply(self._eager_grads, self._eager_state, params)
        for k, v in new_params.items():
            self._owner._assign_by_path(k, v)
        self._eager_grads = None

    def set_grads(self, grads: Dict[str, jax.Array]):
        self._eager_grads = grads

    def clear_grad(self):
        self._eager_grads = None

    def get_lr(self):
        if isinstance(self._lr, LRScheduler):
            return self._lr.get_lr()
        return float(self._lr)

    def set_lr(self, value):
        self._lr = value

    @property
    def _learning_rate(self):
        return self._lr

    def state_dict(self):
        return self._eager_state or {}

    def set_state_dict(self, d):
        self._eager_state = d


class SGD(Optimizer):
    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        return p - lr * g, {}

    def _update_rows(self, name, p, rg, lr, slots, step, wd):
        """Scatter-add update: on touched rows this exactly equals the
        dense rule (SGD is linear in the grad, so duplicate rows need no
        coalescing); weight decay applies to touched rows only (reference
        sparse-SGD semantics), using pre-update values like the dense
        ``g + wd*p``."""
        if wd:
            cg = rg.coalesce()
            touched = p.at[cg.rows].get(mode="fill", fill_value=0.0)
            p = p.at[cg.rows].add(-lr * wd * touched, mode="drop")
        return p.at[rg.rows].add(-lr * rg.values.astype(p.dtype),
                                 mode="drop"), {}


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=0.0, grad_clip=None,
                 multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _init_slots(self, params):
        return {"velocity": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        v = self.momentum * slots["velocity"] + g
        if self.use_nesterov:
            p = p - lr * (g + self.momentum * v)
        else:
            p = p - lr * v
        return p, {"velocity": v}


class LarsMomentum(Momentum):
    """Reference: paddle.optimizer.LarsMomentum — layer-adaptive rate
    scaling: local_lr = lr * lars_coeff * ||w|| / (||g|| + wd*||w||)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 lars_coeff=0.001, lars_weight_decay=0.0005, grad_clip=None,
                 multi_precision=False, epsilon=1e-9):
        super().__init__(learning_rate, momentum, parameters,
                         weight_decay=0.0, grad_clip=grad_clip,
                         multi_precision=multi_precision)
        self.lars_coeff = lars_coeff
        self.lars_wd = lars_weight_decay
        self.epsilon = epsilon

    def _update_one(self, name, p, g, lr, slots, step, wd):
        w_norm = jnp.linalg.norm(p)
        g_norm = jnp.linalg.norm(g)
        local = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            lr * self.lars_coeff * w_norm
            / (g_norm + self.lars_wd * w_norm + self.epsilon), lr)
        g = g + self.lars_wd * p
        v = self.momentum * slots["velocity"] + local * g
        return p - v, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.0, grad_clip=None,
                 multi_precision=False, lazy_mode=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lazy_mode = lazy_mode

    def _init_slots(self, params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"moment1": jax.tree.map(z, params),
                "moment2": jax.tree.map(z, params)}

    def _update_rows(self, name, p, rg, lr, slots, step, wd):
        """``lazy_mode`` sparse Adam (reference:
        AdamDenseParamSparseGradKernel): moments and parameter update only
        for the touched (unique) rows; untouched rows keep stale moments.
        Without lazy_mode the RowsGrad densifies and every row's moments
        decay, exactly like dense Adam on a mostly-zero grad."""
        if not self.lazy_mode:
            return super()._update_rows(name, p, rg, lr, slots, step, wd)
        cg = rg.coalesce()
        rows = cg.rows
        g = cg.values.astype(jnp.float32)
        m, v = slots["moment1"], slots["moment2"]
        p_r = p.at[rows].get(mode="fill", fill_value=0.0)
        m_r = m.at[rows].get(mode="fill", fill_value=0.0)
        v_r = v.at[rows].get(mode="fill", fill_value=0.0)
        new_p_r, m_r, v_r = self._adam_core(p_r, g, lr, m_r, v_r, step, wd,
                                            decoupled=False)
        return (p.at[rows].set(new_p_r, mode="drop"),
                {"moment1": m.at[rows].set(m_r, mode="drop"),
                 "moment2": v.at[rows].set(v_r, mode="drop")})

    def _adam_core(self, p, g, lr, m, v, step, wd, decoupled):
        if wd and not decoupled:
            g = g + wd * p
        m = self.beta1 * m + (1 - self.beta1) * g
        v = self.beta2 * v + (1 - self.beta2) * jnp.square(g)
        t = (step + 1).astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        update = mhat / (jnp.sqrt(vhat) + self.epsilon)
        if wd and decoupled:
            update = update + wd * p
        return p - lr * update, m, v

    def _update_one(self, name, p, g, lr, slots, step, wd):
        new_p, m, v = self._adam_core(p, g, lr, slots["moment1"], slots["moment2"],
                                      step, wd, decoupled=False)
        return new_p, {"moment1": m, "moment2": v}


class AdamW(Adam):
    """Decoupled weight decay (reference: AdamwDenseKernel).

    ``use_fused``: route eligible parameter updates through the fused
    Pallas AdamW kernel (ops/pallas/fused_adamw.py) — moments, parameter
    and its low-precision copy in one elementwise pass over aliased
    buffers on TPU, on the leaf in the shape the step holds it.  ``None``
    (auto) uses the kernel wherever its dispatch serves (TPU backend, no
    mesh, float32 state of two or more dimensions on whole tiles);
    ``False`` pins the XLA composition.  Both compute the same formula
    (tests/test_fused_kernels.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, grad_clip=None,
                 multi_precision=False, apply_decay_param_fun=None, lr_ratio=None,
                 use_fused=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, multi_precision)
        self.apply_decay_param_fun = apply_decay_param_fun
        self.use_fused = use_fused

    def _update_leaf(self, name, p, g, lr, slots, step, wd, dtype):
        if self.use_fused is not False:
            from ..ops import dispatch
            impl = dispatch.get("fused_adamw")
            if impl is not None:
                t = (step + 1).astype(jnp.float32)
                low = None if dtype == p.dtype else dtype
                out = impl(p, g, slots["moment1"], slots["moment2"],
                           jnp.asarray(lr, jnp.float32),
                           1.0 / (1.0 - self.beta1 ** t),
                           1.0 / (1.0 - self.beta2 ** t),
                           beta1=self.beta1, beta2=self.beta2,
                           eps=self.epsilon, wd=float(wd), low_dtype=low)
                if out is not None:
                    new_p, m, v = out[:3]
                    return (new_p, new_p if low is None else out[3],
                            {"moment1": m, "moment2": v})
        return super()._update_leaf(name, p, g, lr, slots, step, wd, dtype)

    def _update_one(self, name, p, g, lr, slots, step, wd):
        new_p, m, v = self._adam_core(p, g, lr, slots["moment1"], slots["moment2"],
                                      step, wd, decoupled=True)
        return new_p, {"moment1": m, "moment2": v}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False):
        super().__init__(learning_rate, parameters, lamb_weight_decay, grad_clip,
                         multi_precision)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.exclude_fn = exclude_from_weight_decay_fn

    def _init_slots(self, params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"moment1": jax.tree.map(z, params),
                "moment2": jax.tree.map(z, params)}

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if self.exclude_fn is not None and self.exclude_fn(name):
            wd = 0.0
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * jnp.square(g)
        t = (step + 1).astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self.epsilon) + wd * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p - lr * trust * r, {"moment1": m, "moment2": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=0.0, grad_clip=None, initial_accumulator_value=0.0):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self.epsilon = epsilon
        self.init_acc = initial_accumulator_value

    def _init_slots(self, params):
        return {"moment": jax.tree.map(
            lambda p: jnp.full(p.shape, self.init_acc, jnp.float32), params)}

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        acc = slots["moment"] + jnp.square(g)
        return p - lr * g / (jnp.sqrt(acc) + self.epsilon), {"moment": acc}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=0.0, grad_clip=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self.rho, self.epsilon, self.momentum, self.centered = rho, epsilon, momentum, centered

    def _init_slots(self, params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        slots = {"mean_square": jax.tree.map(z, params),
                 "momentum_acc": jax.tree.map(z, params)}
        if self.centered:
            slots["mean_grad"] = jax.tree.map(z, params)
        return slots

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        ms = self.rho * slots["mean_square"] + (1 - self.rho) * jnp.square(g)
        out_slots = {"mean_square": ms}
        denom = ms
        if self.centered:
            mg = self.rho * slots["mean_grad"] + (1 - self.rho) * g
            denom = ms - jnp.square(mg)
            out_slots["mean_grad"] = mg
        mom = self.momentum * slots["momentum_acc"] + lr * g / jnp.sqrt(denom + self.epsilon)
        out_slots["momentum_acc"] = mom
        return p - mom, out_slots


__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Lamb", "Adagrad",
           "RMSProp", "lr", "LRScheduler"]

lr = lr_mod


class Adadelta(Optimizer):
    """Reference: paddle.optimizer.Adadelta (adadelta kernel)."""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=0.0, grad_clip=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self.epsilon, self.rho = epsilon, rho

    def _init_slots(self, params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"avg_squared_grad": jax.tree.map(z, params),
                "avg_squared_update": jax.tree.map(z, params)}

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        asg = self.rho * slots["avg_squared_grad"] + (1 - self.rho) * jnp.square(g)
        asu = slots["avg_squared_update"]
        update = g * jnp.sqrt(asu + self.epsilon) / jnp.sqrt(asg + self.epsilon)
        asu = self.rho * asu + (1 - self.rho) * jnp.square(update)
        return p - lr * update, {"avg_squared_grad": asg,
                                 "avg_squared_update": asu}


class Adamax(Optimizer):
    """Reference: paddle.optimizer.Adamax (infinity-norm Adam variant)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.0,
                 grad_clip=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _init_slots(self, params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"moment": jax.tree.map(z, params),
                "inf_norm": jax.tree.map(z, params)}

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        m = self.beta1 * slots["moment"] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * slots["inf_norm"], jnp.abs(g))
        t = step + 1
        lr_t = lr / (1 - self.beta1 ** t)
        return p - lr_t * m / (u + self.epsilon), {"moment": m, "inf_norm": u}


__all__ += ["Adadelta", "Adamax"]


class ASGD(Optimizer):
    """Averaged SGD (reference: paddle.optimizer.ASGD) — plain SGD steps
    plus a running average of the iterates; ``averaged_params`` of the
    state is what evaluation should use."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=0.0, grad_clip=None, multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self.batch_num = batch_num

    def _init_slots(self, params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"avg": jax.tree.map(z, params)}

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        new_p = p - lr * g
        t = (step + 1).astype(jnp.float32)
        avg = slots["avg"] + (new_p - slots["avg"]) / t
        return new_p, {"avg": avg}


class Rprop(Optimizer):
    """Resilient backprop (reference: paddle.optimizer.Rprop) — per-weight
    step sizes grown/shrunk by the sign agreement of successive grads;
    full-batch regimes only (the reference documents the same)."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False):
        super().__init__(learning_rate, parameters, 0.0, grad_clip,
                         multi_precision)
        self.lr_min, self.lr_max = learning_rate_range
        self.eta_minus, self.eta_plus = etas

    def _init_slots(self, params):
        # schedulers work too: seed the per-weight step sizes from the
        # step-0 learning rate
        lr0 = float(_lr_value(self._lr, jnp.zeros((), jnp.int32)))
        return {"prev_grad": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "step_size": jax.tree.map(
                lambda p: jnp.full(p.shape, lr0, jnp.float32), params)}

    def _update_one(self, name, p, g, lr, slots, step, wd):
        sign = jnp.sign(g * slots["prev_grad"])
        size = jnp.clip(
            jnp.where(sign > 0, slots["step_size"] * self.eta_plus,
                      jnp.where(sign < 0, slots["step_size"] * self.eta_minus,
                                slots["step_size"])),
            self.lr_min, self.lr_max)
        # sign flip: no step this iteration (classic Rprop-), grad zeroed
        g_eff = jnp.where(sign < 0, 0.0, g)
        new_p = p - jnp.sign(g_eff) * size
        return new_p, {"prev_grad": g_eff, "step_size": size}


class NAdam(Adam):
    """Adam with Nesterov momentum (reference: paddle.optimizer.NAdam)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, momentum_decay=0.004, parameters=None,
                 weight_decay=0.0, grad_clip=None, multi_precision=False):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, multi_precision)
        self.momentum_decay = momentum_decay

    def _init_slots(self, params):
        slots = super()._init_slots(params)
        slots["mu_product"] = jax.tree.map(
            lambda p: jnp.ones((), jnp.float32), params)
        return slots

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        t = (step + 1).astype(jnp.float32)
        mu_t = self.beta1 * (1 - 0.5 * 0.96 ** (t * self.momentum_decay))
        mu_t1 = self.beta1 * (1 - 0.5 * 0.96 ** ((t + 1) *
                                                 self.momentum_decay))
        mu_prod = slots["mu_product"] * mu_t
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * g * g
        m_hat = (mu_t1 * m / (1 - mu_prod * mu_t1)
                 + (1 - mu_t) * g / (1 - mu_prod))
        v_hat = v / (1 - self.beta2 ** t)
        new_p = p - lr * m_hat / (jnp.sqrt(v_hat) + self.epsilon)
        return new_p, {"moment1": m, "moment2": v, "mu_product": mu_prod}


class RAdam(Adam):
    """Rectified Adam (reference: paddle.optimizer.RAdam) — per-step
    variance rectification; falls back to un-adapted momentum while the
    variance estimate is unreliable."""

    def _update_one(self, name, p, g, lr, slots, step, wd):
        if wd:
            g = g + wd * p
        t = (step + 1).astype(jnp.float32)
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * g * g
        m_hat = m / (1 - self.beta1 ** t)
        rho_inf = 2.0 / (1 - self.beta2) - 1.0
        rho_t = rho_inf - 2.0 * t * self.beta2 ** t / (1 - self.beta2 ** t)
        r = jnp.sqrt(((rho_t - 4) * (rho_t - 2) * rho_inf)
                     / jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t,
                                   1e-12))
        v_hat = jnp.sqrt(v / (1 - self.beta2 ** t))
        adaptive = p - lr * r * m_hat / (v_hat + self.epsilon)
        plain = p - lr * m_hat
        new_p = jnp.where(rho_t > 5.0, adaptive, plain)
        return new_p, {"moment1": m, "moment2": v}


__all__ += ["ASGD", "Rprop", "NAdam", "RAdam"]


from .lbfgs import LBFGS  # noqa: E402,F401

__all__ += ["LBFGS"]
