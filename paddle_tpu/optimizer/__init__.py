"""Optimizers.

TPU-native equivalent of the reference's python/paddle/optimizer/*.py over
operators/optimizers/*. Each optimizer's update rule is ONE jitted jax
function applied per parameter — XLA fuses the elementwise update chain; the
LR comes in as an argument so schedulers never retrigger compilation.
Accumulators (moments etc.) live as device arrays keyed by parameter, the
analogue of the reference's _create_accumulators machinery
(/root/reference/python/paddle/optimizer/optimizer.py)."""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import state
from ..framework.selected_rows import SelectedRows
from ..framework.tensor import Parameter, Tensor
from .lr import LRScheduler
from . import lr  # noqa: F401


# ---------------------------------------------------------------------------
# grad clip (reference: python/paddle/fluid/clip.py)


class ClipGradBase:
    def __call__(self, params_grads):
        raise NotImplementedError


class ClipGradByValue(ClipGradBase):
    def __init__(self, max, min=None):  # noqa: A002
        self.max = float(max)
        self.min = float(min) if min is not None else -float(max)

    def __call__(self, params_grads):
        return [(p, jnp.clip(g, self.min, self.max)) for p, g in params_grads]


class ClipGradByNorm(ClipGradBase):
    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        out = []
        for p, g in params_grads:
            norm = jnp.sqrt(jnp.sum(jnp.square(g)))
            scale = jnp.minimum(self.clip_norm / jnp.maximum(norm, 1e-12), 1.0)
            out.append((p, g * scale))
        return out


class ClipGradByGlobalNorm(ClipGradBase):
    def __init__(self, clip_norm, group_name="default_group"):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for p, g in params_grads
                 if getattr(p, "need_clip", True))
        global_norm = jnp.sqrt(sq)
        scale = self.clip_norm / jnp.maximum(global_norm, self.clip_norm)
        return [(p, g * scale if getattr(p, "need_clip", True) else g)
                for p, g in params_grads]


# regularizers (reference: fluid/regularizer.py)
class L2Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)

    def __call__(self, p, g):
        return g + self.coeff * p


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)

    def __call__(self, p, g):
        return g + self.coeff * jnp.sign(p)


# ---------------------------------------------------------------------------


class Optimizer:
    """Base optimizer (reference: optimizer.py Optimizer with
    _create_accumulators / _append_optimize_op; here: _update is a pure jax
    fn (param, grad, lr, *accumulators) -> (new_param, *new_accumulators))."""

    _accumulator_names: List[str] = []

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._lr = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        if weight_decay is None:
            self._regularization = None
        elif isinstance(weight_decay, (float, int)):
            self._regularization = L2Decay(float(weight_decay))
        else:
            self._regularization = weight_decay
        self._grad_clip = grad_clip
        self._accumulators: Dict[int, Dict[str, jax.Array]] = {}
        self._step_count = 0

    # -- lr ----------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, value):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    def set_lr_scheduler(self, scheduler):
        self._lr = scheduler

    # -- accumulators --------------------------------------------------------
    def _get_accumulators(self, p: Parameter):
        acc = self._accumulators.get(id(p))
        if acc is None:
            acc = self._create_accumulators(p)
            self._accumulators[id(p)] = acc
        return acc

    def _create_accumulators(self, p: Parameter):
        return {name: jnp.zeros_like(p._data)
                for name in self._accumulator_names}

    # -- the update ----------------------------------------------------------
    def _per_param_static_args(self, p):
        """Hashable hyperparameter tuple for this parameter (hook for
        per-param weight-decay exemptions à la AdamW/Lamb)."""
        return self._static_args()

    def step(self):
        params = self._parameter_list
        if params is None:
            raise ValueError("optimizer constructed without parameters")
        params_grads = []
        sparse_grads = []
        for p in params:
            if not getattr(p, "trainable", True) or p.stop_gradient:
                continue
            if p._grad is None:
                continue
            if isinstance(p._grad, SelectedRows):
                # row-sparse grad (Embedding(sparse=True)); regularizers and
                # clipping need the dense view — only the bare path stays
                # factored (matches the reference, which forbids weight decay
                # on SelectedRows grads)
                if (self._regularization is None
                        and getattr(p, "regularizer", None) is None
                        and self._grad_clip is None):
                    sparse_grads.append((p, p._grad))
                    continue
                g = p._grad.to_dense()
            else:
                g = p._grad._data
            if self._regularization is not None and getattr(p, "regularizer", None) is None:
                g = self._regularization(p._data, g)
            elif getattr(p, "regularizer", None) is not None:
                g = p.regularizer(p._data, g)
            params_grads.append((p, g))
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        self._step_count += 1
        lr = self.get_lr()
        for p, g in params_grads:
            accs = self._get_accumulators(p)
            param_lr = getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
            self._apply_one(p, g, lr * param_lr, accs)
        for p, sr in sparse_grads:
            accs = self._get_accumulators(p)
            param_lr = getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
            self._apply_one_sparse(p, sr, lr * param_lr, accs)

    def _apply_one_sparse(self, p, sr: "SelectedRows", lr, accs):
        """Default: densify (XLA fuses the scatter); SGD/lazy-Adam override
        with true row-wise updates (reference: the SelectedRows branches of
        sgd_op.h / adam_op.h)."""
        self._apply_one(p, sr.to_dense(), lr, accs)

    def _apply_one(self, p, g, lr, accs):
        names = self._accumulator_names
        fn = _update_exec(self._rule_cls(p), self._per_param_static_args(p))
        out = fn(p._data, g, np.float32(lr), np.int32(self._step_count),
                 *[accs[n] for n in names])
        p._data = out[0]
        for i, n in enumerate(names):
            accs[n] = out[1 + i]

    def _static_args(self):
        """Hashable tuple of hyperparameters baked into the jitted update."""
        return ()

    def _rule_cls(self, p):
        """Class whose _update_rule applies to this parameter."""
        return type(self)

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, *accs):
        raise NotImplementedError

    # -- bookkeeping ---------------------------------------------------------
    def clear_grad(self, set_to_zero=True):
        if self._parameter_list:
            for p in self._parameter_list:
                p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """Dygraph: backward + step. Static: record the optimize directive
        on the loss's Program; the Executor traces backward + update into
        the compiled module (reference: optimizer.minimize appending
        backward + optimizer ops into the ProgramDesc)."""
        from ..static.program import Variable
        if isinstance(loss, Variable):
            loss.program.optimize_directive = (self, loss)
            if self._parameter_list is None:
                self._parameter_list = loss.program.all_parameters()
            return None, None
        loss.backward()
        self.step()
        return None, None

    def state_dict(self):
        """Accumulators keyed by PARAMETER ORDER (stable across fresh
        processes, unlike auto-generated tensor names); name-based keys
        are also emitted for reference-style consumers."""
        sd = {}
        for i, p in enumerate(self._parameter_list or []):
            accs = self._accumulators.get(id(p))
            if not accs:
                continue
            for name, arr in accs.items():
                t = Tensor(arr, _internal=True)
                sd[f"@acc_{i}_{name}"] = t
                if p.name:
                    sd[f"{p.name}_{name}"] = t
        if isinstance(self._lr, LRScheduler):
            sd["LR_Scheduler"] = self._lr.state_dict()
        # the reference stores beta1_pow/beta2_pow accumulators; our
        # analogue of that bias-correction state is the step count
        sd["@step_count"] = self._step_count
        return sd

    def set_state_dict(self, state_dict):
        sched = state_dict.get("LR_Scheduler")
        if sched and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(sched)
        if "@step_count" in state_dict:
            self._step_count = int(np.asarray(state_dict["@step_count"]))
        if not self._parameter_list:
            return
        for i, p in enumerate(self._parameter_list):
            accs = self._get_accumulators(p)
            for name in list(accs):
                v = state_dict.get(f"@acc_{i}_{name}")
                if v is None:
                    v = state_dict.get(f"{p.name}_{name}")
                if v is not None:
                    accs[name] = jnp.asarray(
                        v.numpy() if isinstance(v, Tensor) else v)

    set_dict = set_state_dict


@functools.lru_cache(maxsize=None)
def _update_exec(cls, static_args):
    rule = cls._update_rule

    def fn(param, grad, lr, t, *accs):
        return rule(static_args, param, grad, lr, t, *accs)

    return jax.jit(fn, donate_argnums=(0,) + tuple(range(4, 4 + len(cls._accumulator_names))))


# ---------------------------------------------------------------------------
# concrete optimizers (update rules mirror the reference's
# operators/optimizers/*.cc kernels)


@functools.lru_cache(maxsize=None)
def _sgd_sparse_exec():
    def fn(param, rows, vals, lr):
        return param.at[rows].add((-lr * vals).astype(param.dtype))

    # XLA scatter-add folds duplicate rows natively — no merge pass needed
    return jax.jit(fn, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _adam_lazy_exec(b1, b2, eps, coeff):
    """Lazy (row-wise) Adam/AdamW on merged SelectedRows (reference:
    adam_op.h SparseAdamFunctor with lazy_mode=true — moments decay and the
    param moves ONLY on touched rows)."""

    def fn(param, rows, vals, lr, t, m1, m2):
        g = vals.astype(jnp.float32)
        p_rows = param[rows].astype(jnp.float32)
        if coeff:
            p_rows = p_rows * (1.0 - lr * coeff)
        m1r = b1 * m1[rows] + (1 - b1) * g
        m2r = b2 * m2[rows] + (1 - b2) * jnp.square(g)
        tf = t.astype(jnp.float32)
        c1 = 1 - jnp.power(jnp.float32(b1), tf)
        c2 = 1 - jnp.power(jnp.float32(b2), tf)
        step = lr * (m1r / c1) / (jnp.sqrt(m2r / c2) + eps)
        return (param.at[rows].set((p_rows - step).astype(param.dtype)),
                m1.at[rows].set(m1r), m2.at[rows].set(m2r))

    return jax.jit(fn, donate_argnums=(0, 5, 6))


class SGD(Optimizer):
    _accumulator_names = []

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t):
        g = grad.astype(param.dtype)
        return (param - lr * g,)

    def _apply_one_sparse(self, p, sr, lr, accs):
        p._data = _sgd_sparse_exec()(p._data, sr.rows, sr.values,
                                     np.float32(lr))


class Momentum(Optimizer):
    _accumulator_names = ["velocity"]

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._momentum = float(momentum)
        self._nesterov = bool(use_nesterov)

    def _static_args(self):
        return (self._momentum, self._nesterov)

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, velocity):
        mu, nesterov = static_args
        g = grad.astype(param.dtype)
        v = mu * velocity + g
        if nesterov:
            new_p = param - lr * (g + mu * v)
        else:
            new_p = param - lr * v
        return new_p, v


class Lars(Optimizer):
    """LARS — layer-wise adaptive rate scaling over momentum.

    reference: fluid LarsMomentumOptimizer
    (paddle/fluid/operators/optimizers/lars_momentum_op.cc; enabled by the
    fleet meta switch `strategy.lars`,
    fleet/meta_optimizers/lars_optimizer.py). local_lr scales the step by
    ||w|| / (||g|| + wd·||w|| + eps) per layer so large-batch SGD keeps
    per-layer update magnitudes balanced."""

    _accumulator_names = ["velocity"]

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, epsilon=0.0, parameters=None,
                 exclude_from_weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._momentum = float(momentum)
        self._coeff = float(lars_coeff)
        self._wd = float(lars_weight_decay)
        self._eps = float(epsilon)
        self._exclude = tuple(exclude_from_weight_decay or ())

    def _per_param_static_args(self, p):
        wd = self._wd
        name = getattr(p, "name", "") or ""
        if any(tag in name for tag in self._exclude):
            wd = 0.0
        return (self._momentum, self._coeff, wd, self._eps)

    def _static_args(self):
        return (self._momentum, self._coeff, self._wd, self._eps)

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, velocity):
        mu, coeff, wd, eps = static_args
        g = grad.astype(jnp.float32)
        p32 = param.astype(jnp.float32)
        w_norm = jnp.sqrt(jnp.sum(p32 * p32))
        g_norm = jnp.sqrt(jnp.sum(g * g))
        ratio = coeff * w_norm / (g_norm + wd * w_norm + eps + 1e-12)
        local_lr = lr * jnp.where((w_norm > 0) & (g_norm > 0), ratio, 1.0)
        v = mu * velocity + local_lr * (g + wd * p32)
        return (p32 - v).astype(param.dtype), v


class Adam(Optimizer):
    _accumulator_names = ["moment1", "moment2"]

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1 = float(beta1)
        self._beta2 = float(beta2)
        self._epsilon = float(epsilon)
        self._lazy_mode = bool(lazy_mode)

    def _static_args(self):
        return (self._beta1, self._beta2, self._epsilon)

    def _sparse_decay_coeff(self, p):
        return 0.0

    def _apply_one_sparse(self, p, sr, lr, accs):
        if not self._lazy_mode:
            # non-lazy semantics: moments decay on EVERY row — same as a
            # dense update with zero grads on untouched rows
            return self._apply_one(p, sr.to_dense(), lr, accs)
        sr = sr.merged()
        fn = _adam_lazy_exec(self._beta1, self._beta2, self._epsilon,
                             self._sparse_decay_coeff(p))
        out = fn(p._data, sr.rows, sr.values, np.float32(lr),
                 np.int32(self._step_count), accs["moment1"],
                 accs["moment2"])
        p._data, accs["moment1"], accs["moment2"] = out

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, m1, m2):
        b1, b2, eps = static_args
        from ..ops.pallas_kernels import (_note_update_path,
                                          fused_adamw_or_none)
        fused = fused_adamw_or_none(param, grad, lr, t, m1, m2, beta1=b1,
                                    beta2=b2, epsilon=eps, coeff=0.0)
        if fused is not None:
            return fused
        _note_update_path("xla_adamw")
        g = grad.astype(jnp.float32)
        p32 = param.astype(jnp.float32)
        m1n = b1 * m1 + (1 - b1) * g
        m2n = b2 * m2 + (1 - b2) * jnp.square(g)
        tf = t.astype(jnp.float32)
        c1 = 1 - jnp.power(jnp.float32(b1), tf)
        c2 = 1 - jnp.power(jnp.float32(b2), tf)
        step = lr * (m1n / c1) / (jnp.sqrt(m2n / c2) + eps)
        return (p32 - step).astype(param.dtype), m1n, m2n

    def _create_accumulators(self, p):
        return {n: jnp.zeros(p._data.shape, jnp.float32)
                for n in self._accumulator_names}


class AdamW(Adam):
    """Decoupled weight decay (reference: optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode=lazy_mode)
        self._coeff = float(weight_decay) if not callable(weight_decay) else weight_decay
        self._apply_decay_param_fun = apply_decay_param_fun

    def _sparse_decay_coeff(self, p):
        if self._decay_applies(p) and not callable(self._coeff):
            return self._coeff
        return 0.0

    def _static_args(self):
        return (self._beta1, self._beta2, self._epsilon, self._coeff)

    def _decay_applies(self, p):
        return (self._apply_decay_param_fun is None
                or self._apply_decay_param_fun(p.name))

    def _per_param_static_args(self, p):
        if self._decay_applies(p):
            return (self._beta1, self._beta2, self._epsilon, self._coeff)
        return (self._beta1, self._beta2, self._epsilon)

    def _rule_cls(self, p):
        return AdamW if self._decay_applies(p) else Adam

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, m1, m2):
        b1, b2, eps, coeff = static_args
        from ..ops.pallas_kernels import (_note_update_path,
                                          fused_adamw_or_none)
        fused = fused_adamw_or_none(param, grad, lr, t, m1, m2, beta1=b1,
                                    beta2=b2, epsilon=eps, coeff=coeff)
        if fused is not None:
            return fused
        _note_update_path("xla_adamw")
        g = grad.astype(jnp.float32)
        p32 = param.astype(jnp.float32)
        p32 = p32 * (1.0 - lr * coeff)
        m1n = b1 * m1 + (1 - b1) * g
        m2n = b2 * m2 + (1 - b2) * jnp.square(g)
        tf = t.astype(jnp.float32)
        c1 = 1 - jnp.power(jnp.float32(b1), tf)
        c2 = 1 - jnp.power(jnp.float32(b2), tf)
        step = lr * (m1n / c1) / (jnp.sqrt(m2n / c2) + eps)
        return (p32 - step).astype(param.dtype), m1n, m2n


class Adamax(Optimizer):
    _accumulator_names = ["moment", "inf_norm"]

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._epsilon = float(beta1), float(beta2), float(epsilon)

    def _static_args(self):
        return (self._beta1, self._beta2, self._epsilon)

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, m, u):
        b1, b2, eps = static_args
        g = grad.astype(param.dtype)
        mn = b1 * m + (1 - b1) * g
        un = jnp.maximum(b2 * u, jnp.abs(g))
        tf = t.astype(jnp.float32)
        c1 = 1 - jnp.power(jnp.float32(b1), tf)
        return param - lr / c1 * mn / (un + eps), mn, un


class Adagrad(Optimizer):
    _accumulator_names = ["moment"]

    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon = float(epsilon)
        self._init_val = float(initial_accumulator_value)

    def _create_accumulators(self, p):
        return {"moment": jnp.full(p._data.shape, self._init_val, jnp.float32)}

    def _static_args(self):
        return (self._epsilon,)

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, moment):
        (eps,) = static_args
        g = grad.astype(jnp.float32)
        mn = moment + jnp.square(g)
        return (param.astype(jnp.float32) - lr * g / (jnp.sqrt(mn) + eps)
                ).astype(param.dtype), mn


class Adadelta(Optimizer):
    _accumulator_names = ["avg_squared_grad", "avg_squared_update"]

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon, self._rho = float(epsilon), float(rho)

    def _static_args(self):
        return (self._epsilon, self._rho)

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, sq_g, sq_u):
        eps, rho = static_args
        g = grad.astype(jnp.float32)
        sq_gn = rho * sq_g + (1 - rho) * jnp.square(g)
        upd = -jnp.sqrt((sq_u + eps) / (sq_gn + eps)) * g
        sq_un = rho * sq_u + (1 - rho) * jnp.square(upd)
        return (param.astype(jnp.float32) + lr * upd).astype(param.dtype), sq_gn, sq_un


class RMSProp(Optimizer):
    _accumulator_names = ["mean_square", "mean_grad", "momentum_acc"]

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._epsilon = float(rho), float(epsilon)
        self._momentum, self._centered = float(momentum), bool(centered)

    def _static_args(self):
        return (self._rho, self._epsilon, self._momentum, self._centered)

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, ms, mg, mom):
        rho, eps, mu, centered = static_args
        g = grad.astype(jnp.float32)
        msn = rho * ms + (1 - rho) * jnp.square(g)
        if centered:
            mgn = rho * mg + (1 - rho) * g
            denom = msn - jnp.square(mgn) + eps
        else:
            mgn = mg
            denom = msn + eps
        momn = mu * mom + lr * g / jnp.sqrt(denom)
        return (param.astype(jnp.float32) - momn).astype(param.dtype), msn, mgn, momn


class Lamb(Optimizer):
    _accumulator_names = ["moment1", "moment2"]

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._beta1, self._beta2 = float(beta1), float(beta2)
        self._epsilon = float(epsilon)
        self._lamb_wd = float(lamb_weight_decay)
        self._exclude_fn = exclude_from_weight_decay_fn

    def _static_args(self):
        return (self._beta1, self._beta2, self._epsilon, self._lamb_wd)

    def _per_param_static_args(self, p):
        wd = self._lamb_wd
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        return (self._beta1, self._beta2, self._epsilon, wd)

    def _create_accumulators(self, p):
        return {n: jnp.zeros(p._data.shape, jnp.float32)
                for n in self._accumulator_names}

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, m1, m2):
        b1, b2, eps, wd = static_args
        g = grad.astype(jnp.float32)
        p32 = param.astype(jnp.float32)
        m1n = b1 * m1 + (1 - b1) * g
        m2n = b2 * m2 + (1 - b2) * jnp.square(g)
        tf = t.astype(jnp.float32)
        mhat = m1n / (1 - jnp.power(jnp.float32(b1), tf))
        vhat = m2n / (1 - jnp.power(jnp.float32(b2), tf))
        r = mhat / (jnp.sqrt(vhat) + eps) + wd * p32
        w_norm = jnp.linalg.norm(p32)
        r_norm = jnp.linalg.norm(r)
        ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return (p32 - lr * ratio * r).astype(param.dtype), m1n, m2n


class Ftrl(Optimizer):
    """FTRL-Proximal (reference: operators/optimizers/ftrl_op.h FTRLFunctor;
    python API fluid.optimizer.FtrlOptimizer). Accumulates squared gradients
    and a linear term; the closed-form proximal step shrinks weights whose
    accumulated linear term is inside the l1 ball to exactly zero."""

    _accumulator_names = ["squared", "linear"]

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0, lr_power=-0.5,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._l1 = float(l1)
        self._l2 = float(l2)
        self._lr_power = float(lr_power)

    def _static_args(self):
        return (self._l1, self._l2, self._lr_power)

    def _create_accumulators(self, p):
        return {n: jnp.zeros(p._data.shape, jnp.float32)
                for n in self._accumulator_names}

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, squared, linear):
        l1, l2, lr_power = static_args
        g = grad.astype(jnp.float32)
        p32 = param.astype(jnp.float32)
        new_sq = squared + jnp.square(g)
        if lr_power == -0.5:
            sigma = (jnp.sqrt(new_sq) - jnp.sqrt(squared)) / lr
        else:
            sigma = (jnp.power(new_sq, -lr_power)
                     - jnp.power(squared, -lr_power)) / lr
        lin = linear + g - sigma * p32
        x = l1 * jnp.sign(lin) - lin
        if lr_power == -0.5:
            y = jnp.sqrt(new_sq) / lr + 2.0 * l2
        else:
            y = jnp.power(new_sq, -lr_power) / lr + 2.0 * l2
        new_p = jnp.where(jnp.abs(lin) > l1, x / y, 0.0)
        return new_p.astype(param.dtype), new_sq, lin


class DecayedAdagrad(Optimizer):
    """reference: operators/optimizers/decayed_adagrad_op.cc — Adagrad
    with an exponentially decayed squared-gradient accumulator."""

    _accumulator_names = ["moment"]

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._decay = float(decay)
        self._epsilon = float(epsilon)

    def _static_args(self):
        return (self._decay, self._epsilon)

    def _create_accumulators(self, p):
        return {"moment": jnp.zeros(p._data.shape, jnp.float32)}

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, moment):
        decay, eps = static_args
        g = grad.astype(jnp.float32)
        mn = decay * moment + (1.0 - decay) * jnp.square(g)
        return (param.astype(jnp.float32)
                - lr * g / (jnp.sqrt(mn) + eps)).astype(param.dtype), mn


def _proximal_shrink(prox, lr, l1, l2):
    """Closed-form proximal operator of lr*(l1|w|_1 + l2/2 |w|_2^2)."""
    return (jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
            / (1.0 + lr * l2))


class ProximalGD(Optimizer):
    """reference: operators/optimizers/proximal_gd_op.cc — SGD followed
    by the l1/l2 proximal shrink."""

    _accumulator_names = []

    def __init__(self, learning_rate, l1=0.0, l2=0.0, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._l1 = float(l1)
        self._l2 = float(l2)

    def _static_args(self):
        return (self._l1, self._l2)

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t):
        l1, l2 = static_args
        prox = param.astype(jnp.float32) - lr * grad.astype(jnp.float32)
        return _proximal_shrink(prox, lr, l1, l2).astype(param.dtype),


class ProximalAdagrad(Optimizer):
    """reference: operators/optimizers/proximal_adagrad_op.cc — Adagrad
    step with the l1/l2 proximal shrink at the adapted learning rate."""

    _accumulator_names = ["moment"]

    def __init__(self, learning_rate, l1=0.0, l2=0.0, epsilon=1e-6,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._l1 = float(l1)
        self._l2 = float(l2)
        self._epsilon = float(epsilon)

    def _static_args(self):
        return (self._l1, self._l2, self._epsilon)

    def _create_accumulators(self, p):
        return {"moment": jnp.zeros(p._data.shape, jnp.float32)}

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, moment):
        l1, l2, eps = static_args
        g = grad.astype(jnp.float32)
        mn = moment + jnp.square(g)
        alr = lr / (jnp.sqrt(mn) + eps)
        prox = param.astype(jnp.float32) - alr * g
        return _proximal_shrink(prox, alr, l1, l2).astype(param.dtype), mn


@functools.lru_cache(maxsize=None)
def _dpsgd_exec(clip, batch_size):
    def fn(param, grad, lr, noise):
        g = grad.astype(jnp.float32)
        l2 = jnp.sqrt(jnp.sum(jnp.square(g)))
        scale = jnp.where(l2 > clip, l2 / clip, 1.0)
        step = lr * (g / scale + noise / batch_size)
        return (param.astype(jnp.float32) - step).astype(param.dtype)

    return jax.jit(fn, donate_argnums=(0,))


class Dpsgd(Optimizer):
    """Differentially-private SGD (reference: operators/optimizers/dpsgd_op.h,
    CCS'16 "Deep Learning with Differential Privacy"): per-step global-norm
    clip of the gradient plus one gaussian noise draw scaled by 1/batch_size.
    The noise is drawn host-side (per step, like the reference's Box-Muller
    draw) and enters the jitted update as a scalar argument."""

    _accumulator_names = []

    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16.0,
                 sigma=1.0, parameters=None, seed=0, name=None):
        super().__init__(learning_rate, parameters, None, None)
        self._clip = float(clip)
        self._batch_size = float(batch_size)
        self._sigma = float(sigma)
        self._noise_rng = np.random.RandomState(seed or None)

    def _apply_one(self, p, g, lr, accs):
        noise = float(self._noise_rng.normal(0.0, self._sigma))
        p._data = _dpsgd_exec(self._clip, self._batch_size)(
            p._data, g, np.float32(lr), np.float32(noise))

    @staticmethod
    def _update_rule(static_args, param, grad, lr, t, *accs):
        raise NotImplementedError(
            "Dpsgd is dygraph-only: its per-step host-side gaussian noise "
            "draw cannot be baked into a compiled static update; use it "
            "with loss.backward() + opt.step()")
