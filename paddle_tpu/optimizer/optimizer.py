"""Optimizer base class.

reference parity: python/paddle/optimizer/optimizer.py:91 (``Optimizer`` with
``step`` :1477, ``minimize`` :1391, ``_apply_optimize`` :1186, accumulator
machinery ``_add_accumulator``), reshaped TPU-first:

- Optimizer state ("accumulators") is a per-parameter dict of ``jax.Array``s,
  i.e. a pytree. The whole update is pure jnp code over (param, grad, accs),
  so a train step wrapped in ``paddle_tpu.jit`` compiles parameter updates
  into the same XLA program as forward+backward — the TPU counterpart of the
  reference's fused_adam multi-tensor kernel (phi/kernels/gpu/fused_adam_kernel.cu).
- In-place semantics (the reference's ``adamw_`` inplace ops) are realized by
  rebinding the Parameter's payload cell (``Tensor._set_value``), which the
  jit tracer records for functionalization.
"""
from __future__ import annotations

import time
from typing import Callable, Iterable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..tensor import Parameter, Tensor
from ..autograd import no_grad
from .lr import LRScheduler

__all__ = ["Optimizer"]


class _L2Decay:
    """L2 regularization added to the gradient (reference:
    python/paddle/regularizer.py L2Decay)."""

    def __init__(self, coeff: float):
        self.coeff = float(coeff)

    def __call__(self, param_value, grad_value):
        return grad_value + self.coeff * param_value


class _L1Decay:
    """reference: python/paddle/regularizer.py L1Decay."""

    def __init__(self, coeff: float):
        self.coeff = float(coeff)

    def __call__(self, param_value, grad_value):
        return grad_value + self.coeff * jnp.sign(param_value)


def _coerce_regularizer(weight_decay):
    if weight_decay is None:
        return None
    if callable(weight_decay):
        return weight_decay
    return _L2Decay(float(weight_decay))


class Optimizer:
    """Base optimizer (reference: python/paddle/optimizer/optimizer.py:91).

    Subclasses implement ``_update(param_value, grad_value, accs, lr)``
    returning ``(new_param_value, new_accs)`` — pure jnp, jit-traceable —
    and list their accumulator names/initializers in ``_accumulator_specs``.
    """

    # name -> init fn(param_value) for per-param state; subclasses override.
    _accumulator_specs: dict = {}

    def __init__(
        self,
        learning_rate: Union[float, LRScheduler] = 0.001,
        parameters: Optional[Iterable] = None,
        weight_decay=None,
        grad_clip=None,
        name: Optional[str] = None,
    ):
        # per-param overrides from the param-group API:
        # [{'params': [...], 'learning_rate': mult, 'weight_decay': wd}, ...]
        self._group_lr_mult: dict = {}    # param uid -> lr multiplier
        self._group_wd: dict = {}         # param uid -> regularizer
        if parameters is not None:
            parameters = list(parameters)
            if parameters and isinstance(parameters[0], dict):
                self._param_groups = parameters
                flat = []
                for g in parameters:
                    for p in g["params"]:
                        flat.append(p)
                        if "learning_rate" in g:
                            self._group_lr_mult[p._uid] = float(g["learning_rate"])
                        if "weight_decay" in g:
                            self._group_wd[p._uid] = _coerce_regularizer(
                                g["weight_decay"])
                parameters = flat
            else:
                self._param_groups = None
        else:
            self._param_groups = None
        self._parameter_list = parameters
        self._learning_rate = learning_rate
        self.regularization = _coerce_regularizer(weight_decay)
        self._grad_clip = grad_clip
        self._name = name or type(self).__name__
        # param uid -> {acc_name: jax.Array} (uid, not name: two params may
        # share a user-chosen name, and uid is already the group-override key)
        self._accumulators: dict = {}
        self._global_step = 0

    # -------------------------------------------------------------- lr plumbing
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value: float):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "optimizer's learning rate can't be set when it uses an LRScheduler"
            )
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler: LRScheduler):
        self._learning_rate = scheduler

    def _lr_value(self):
        """Current lr as a jnp scalar (traceable). Under paddle_tpu.jit the
        tracer installs ``_lr_override`` so the lr is a traced input of the
        compiled step — scheduler.step() between calls then needs no retrace."""
        override = getattr(self, "_lr_override", None)
        if override is not None:
            return override
        return jnp.asarray(self.get_lr(), dtype=jnp.float32)

    # ---------------------------------------------------------- accumulators
    def _materialize_accumulators(self):
        """Eagerly create all per-param state (normally lazy on first step) —
        lets paddle_tpu.jit compile a train step without an eager warm-up
        call (to_static(..., warmup=False))."""
        multi_precision = getattr(self, "_multi_precision", False)
        for p in self._parameter_list or []:
            if getattr(p, "trainable", True) and not p.stop_gradient:
                accs = self._get_accumulators(p)
                if multi_precision and p._value.dtype in (
                        jnp.bfloat16, jnp.float16) and "@master" not in accs:
                    accs["@master"] = p._value.astype(jnp.float32)

    def _get_accumulators(self, p: Parameter) -> dict:
        accs = self._accumulators.get(p._uid)
        if accs is None:
            accs = {
                name: init(p._value) for name, init in self._accumulator_specs.items()
            }
            self._accumulators[p._uid] = accs
        return accs

    # ---------------------------------------------------------------- update
    def _update(self, param_value, grad_value, accs: dict, lr):
        raise NotImplementedError

    def _param_lr(self, param) -> float:
        """Per-parameter lr multiplier (ParamAttr learning_rate × param-group
        learning_rate, reference: optimizer.py _create_param_lr)."""
        mult = float(getattr(param, "optimize_attr", {}).get("learning_rate", 1.0))
        return mult * self._group_lr_mult.get(param._uid, 1.0)

    def _param_regularizer(self, param):
        """Effective regularizer: per-param > per-group > optimizer-wide."""
        if getattr(param, "regularizer", None) is not None:
            return param.regularizer
        if param._uid in self._group_wd:
            return self._group_wd[param._uid]
        return self.regularization

    def _collect_params_grads(self):
        params = self._parameter_list
        if params is None:
            raise ValueError(
                "optimizer constructed without a parameter list; pass "
                "parameters=model.parameters()"
            )
        out = []
        for p in params:
            if p.stop_gradient or p.grad is None:
                continue
            if not getattr(p, "trainable", True):
                continue
            out.append((p, p.grad))
        return out

    @no_grad()
    def step(self):
        """Apply one optimizer update (reference: optimizer.py:1477).

        Two AMP hooks (paddle_tpu.amp):
        - master weights (``multi_precision``, reference: optimizer.py
          _create_master_weight): low-precision params keep an fp32 "master"
          accumulator that carries the true state; the param cell holds its
          down-cast.
        - ``_found_inf`` (set by GradScaler before step, reference:
          check_finite_and_unscale + update_loss_scaling ops): when the traced
          flag is true the whole update is a jnp.where no-op — the traceable
          equivalent of the reference's skip-step.

        Telemetry: each call lands in
        ``paddle_tpu_train_optimizer_step_seconds`` /
        ``..._steps_total``. Inside a jit-compiled train step this python
        body runs only at trace time, so the metrics then count *traces*
        (and time tracing), not executed steps — eager training gets
        per-step numbers (docs/OBSERVABILITY.md).
        """
        from .. import metrics

        _reg = metrics.get_registry()
        _t0 = time.perf_counter() if _reg.enabled else 0.0
        params_grads = self._collect_params_grads()
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        lr = self._lr_value()
        found_inf = getattr(self, "_found_inf", None)
        if found_inf is not None and isinstance(found_inf, Tensor):
            found_inf = found_inf._value
        multi_precision = getattr(self, "_multi_precision", False)
        for p, g in params_grads:
            gv = g._value
            use_master = multi_precision and p._value.dtype in (
                jnp.bfloat16, jnp.float16)
            accs = self._get_accumulators(p)
            if use_master:
                if "@master" not in accs:
                    accs["@master"] = p._value.astype(jnp.float32)
                pv = accs["@master"]
                gv = gv.astype(jnp.float32)
            else:
                pv = p._value
                if gv.dtype != pv.dtype:
                    gv = gv.astype(pv.dtype)
            reg = self._param_regularizer(p)
            if reg is not None:
                gv = reg(pv, gv)
            plr = self._param_lr(p)
            # "adamw_update" etc.: a device trace can then tell the
            # optimizer's fusions from the model's
            with jax.named_scope(type(self).__name__.lower() + "_update"):
                new_val, new_accs = self._update(pv, gv, accs, lr * plr)
            if found_inf is not None:
                new_val = jnp.where(found_inf, pv, new_val)
                new_accs = {
                    k: jnp.where(found_inf, accs[k], v) if k in accs
                    and getattr(v, "shape", None) == getattr(accs[k], "shape", None)
                    else v
                    for k, v in new_accs.items()
                }
            if use_master:
                new_accs["@master"] = new_val
                p._set_value(new_val.astype(p._value.dtype))
            else:
                p._set_value(new_val)
            self._accumulators[p._uid] = new_accs
        if found_inf is not None:
            # the skip used to be silent; counted AFTER the update loop
            # so the blocking host read of the flag overlaps the already-
            # dispatched device work instead of serializing ahead of it.
            # bool() on a traced flag raises (under jit the skip is data-
            # dependent and the host can't observe it), so only eager
            # skips count — which is where GradScaler runs. Sentinel-
            # tagged skips count in paddle_tpu_train_skipped_batches_total
            # instead.
            try:
                skip_now = bool(found_inf)
            except Exception:
                skip_now = False
            if skip_now and getattr(self, "_found_inf_origin",
                                    "amp") == "amp":
                _reg.counter(
                    "paddle_tpu_amp_skipped_steps_total",
                    "Optimizer updates suppressed by the _found_inf skip "
                    "path (GradScaler non-finite gradients)").inc()
        self._found_inf = None  # consume-once: a stale flag must not freeze future steps
        self._found_inf_origin = "amp"  # consumed with the flag it tags
        self._global_step += 1
        # _t0 > 0 guard: if the registry was enabled mid-step, _t0 is the
        # 0.0 sentinel and observing perf_counter()-0 would poison the
        # histogram with an absolute-clock outlier
        if _reg.enabled and _t0 > 0.0:
            _reg.histogram(
                "paddle_tpu_train_optimizer_step_seconds",
                "One Optimizer.step(): clip + per-param updates"
            ).observe(time.perf_counter() - _t0)
            _reg.counter(
                "paddle_tpu_train_optimizer_steps_total",
                "Optimizer.step() calls (trace-time only under jit)").inc()

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        """reference: optimizer.py:1391 — in dygraph the reference's
        ``backward`` only *collects* grads already produced by a prior
        ``loss.backward()`` call; it never re-runs autodiff. Matching that
        contract here: callers must run ``loss.backward()`` first (the
        documented pattern), otherwise we raise instead of silently
        double-accumulating.

        Inside a ``static.program_guard`` this is DECLARATIVE (reference:
        static-graph minimize appends backward+opt ops to the Program): the
        loss/optimizer register with the program; the actual grads + step
        happen in Executor.run."""
        from ..static import _collect_parameters, _guard_stack

        if _guard_stack:
            prog = _guard_stack[-1][0]
            prog.loss = loss
            prog.optimizer = self
            if parameters is not None:
                plist = list(parameters)
            elif self._parameter_list is not None:
                plist = list(self._parameter_list)
            else:
                # static contract: minimize() without parameters= trains
                # every trainable var reachable from the loss
                plist = _collect_parameters(loss)
            if no_grad_set:
                frozen_ids = {id(p) for p in no_grad_set
                              if not isinstance(p, str)}
                frozen_names = {p for p in no_grad_set if isinstance(p, str)}
                plist = [p for p in plist
                         if id(p) not in frozen_ids
                         and getattr(p, "name", None) not in frozen_names]
            self._parameter_list = plist
            self._materialize_accumulators()
            return None, []
        if (self._parameter_list is not None
                and not any(p.grad is not None for p in self._parameter_list)):
            raise RuntimeError(
                "Optimizer.minimize found no gradients: call loss.backward() "
                "before minimize() (minimize only applies already-computed "
                "grads, matching the reference dygraph contract)")
        self.step()
        return None, self._collect_params_grads()

    @no_grad()
    def clear_grad(self, set_to_zero: bool = False):
        """reference: optimizer.py clear_grad."""
        if self._parameter_list is None:
            return
        for p in self._parameter_list:
            if set_to_zero and p.grad is not None:
                p.grad = Tensor(jnp.zeros_like(p.grad._value))
            else:
                p.grad = None

    clear_gradients = clear_grad

    # ------------------------------------------------------------ state dict
    def state_dict(self) -> dict:
        """Accumulators + LR scheduler state (reference: optimizer.py
        state_dict — accumulator tensors keyed by name).

        Keys are ``pos:{index}.{acc_name}`` where index is the parameter's
        position in the optimizer's parameter list — stable across processes,
        unlike auto-generated tensor names (tensor.py's process-global uid
        counter shifts between runs).
        """
        sd = {}
        pos_of = {p._uid: i for i, p in enumerate(self._parameter_list or [])}
        for uid, accs in self._accumulators.items():
            if uid not in pos_of:
                continue  # param no longer tracked by this optimizer
            for aname, val in accs.items():
                sd[f"pos:{pos_of[uid]}.{aname}"] = Tensor(val)
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        sd["@global_step"] = self._global_step
        return sd

    def set_state_dict(self, state_dict: dict):
        state_dict = dict(state_dict)
        lr_state = state_dict.pop("LR_Scheduler", None)
        if lr_state is not None and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(lr_state)
        self._global_step = int(state_dict.pop("@global_step", 0))
        params = self._parameter_list or []
        for key, val in state_dict.items():
            pkey, _, aname = key.rpartition(".")
            if not pkey or not pkey.startswith("pos:"):
                continue
            idx = int(pkey[4:])
            if idx >= len(params):
                raise KeyError(
                    f"optimizer state refers to parameter index {idx} but "
                    f"this optimizer has only {len(params)} parameters"
                )
            uid = params[idx]._uid
            arr = val._value if isinstance(val, Tensor) else jnp.asarray(val)
            self._accumulators.setdefault(uid, {})[aname] = arr

    load_state_dict = set_state_dict

    def __repr__(self):
        return f"{type(self).__name__}(lr={self.get_lr()})"
