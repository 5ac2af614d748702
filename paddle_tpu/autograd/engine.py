"""Eager autograd engine.

TPU-native counterpart of the reference's eager autograd
(``paddle/fluid/eager/``): ``GradNode`` plays the role of ``GradNodeBase``
(grad_node_info.h:168) and ``backward`` the role of ``RunBackward``
(backward.cc:104) — a topological walk with per-tensor accumulation
(GradTensorHolder semantics) and hooks.

The key TPU-native difference: instead of codegen'd per-op GradNode classes
calling hand-written grad kernels, every op's backward is obtained from
``jax.vjp`` at forward time. The vjp closure holds the saved residuals (the
reference's TensorWrapper role) as device arrays, and calling it launches the
backward XLA computation. Because jax.vjp works on tracers, the entire tape —
forward build + backward walk — can itself run under ``jax.jit`` and compile
into a single fused XLA program (see paddle_tpu.jit).

Edges snapshot (tensor, uid, producer_node) at record time, so in-place
rebinding a tensor to a new value/node (the reference's inplace ops +
version-counter concern) cannot corrupt or cycle the graph: a rebound tensor
gets a fresh uid, and old edges keep pointing at the old uid/node.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from ..tensor import Tensor, _uid_counter

_state = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_state, "grad_enabled", True)


def set_grad_enabled(mode: bool):
    _state.grad_enabled = bool(mode)


class no_grad(contextlib.ContextDecorator):
    """reference: paddle.no_grad (python/paddle/fluid/dygraph/base.py)."""

    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(False)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


class enable_grad(contextlib.ContextDecorator):
    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(True)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


class GradNode:
    """One tape entry (reference: GradNodeBase, grad_node_info.h:168).

    ``fn``/``in_vals`` keep the op's pure function + recorded input values so
    ``grad(create_graph=True)`` can re-derive the VJP *as a tape op* (the
    reference's double-grad story: codegen'd higher-order GradNodes; here
    jax.vjp composes, so one generic re-derivation covers every op)."""

    __slots__ = ("vjp_fn", "edges", "out_uids", "out_avals", "out_tuple",
                 "name", "post_hooks", "fn", "in_vals")

    def __init__(self, vjp_fn, inputs: Sequence[Tensor], out_uids, out_avals, name="",
                 out_tuple=False, fn=None, in_vals=None):
        self.vjp_fn = vjp_fn
        # (tensor, uid-at-record, producer-node-at-record) per differentiable input
        self.edges = [(t, t._uid, t._grad_node) for t in inputs]
        self.out_uids = list(out_uids)
        self.out_avals = list(out_avals)  # (shape, dtype) per output slot
        self.out_tuple = out_tuple  # forward returned a tuple (even 1-element)
        self.name = name
        self.post_hooks = None
        self.fn = fn
        self.in_vals = in_vals  # values the vjp was taken at (post-amp-cast)

    def __repr__(self):
        return f"GradNode({self.name})"


def make_node_for_outputs(vjp_fn, inputs, out_tensors, name="", out_tuple=False,
                          fn=None, in_vals=None):
    """Record a GradNode and attach it to out_tensors (all Tensors)."""
    node = GradNode(
        vjp_fn,
        inputs,
        [t._uid for t in out_tensors],
        [(tuple(t._value.shape), t._value.dtype) for t in out_tensors],
        name=name,
        out_tuple=out_tuple,
        fn=fn,
        in_vals=in_vals,
    )
    for i, t in enumerate(out_tensors):
        t._grad_node = node
        t._output_index = i
    return node


# AMP dispatch state, mutated by paddle_tpu.amp.auto_cast (the eager AMP
# interception point — reference: eager_amp_auto_cast.h + AmpOperators,
# fluid/imperative/amp_auto_cast.h:39). Kept here so the hot path reads one
# module-global dict instead of importing the amp package per op.
amp_state = {
    "enabled": False, "dtype": None, "level": "O1",
    "white": frozenset(), "black": frozenset(),
}

# FLAGS_check_nan_inf / FLAGS_benchmark (framework/flags.py) — module-level
# bools so the hot path pays one dict-free read (reference: the per-op sweep
# in eager/nan_inf_utils.cc gated by the same flag)
check_nan_inf_enabled = False
benchmark_sync_enabled = False

# active saved_tensors_hooks (pack, unpack) stack — see
# paddle_tpu.autograd.saved_tensors_hooks
_saved_tensor_hooks: list = []


def _nan_inf_sweep(outs, name: str):
    seq = outs if isinstance(outs, tuple) else (outs,)
    for i, o in enumerate(seq):
        if hasattr(o, "dtype") and jnp.issubdtype(o.dtype, jnp.inexact):
            if isinstance(o, jax.core.Tracer):
                continue  # traced values are checked when materialized
            if bool(jnp.any(~jnp.isfinite(o))):
                raise FloatingPointError(
                    f"NaN/Inf detected in output {i} of op {name!r} "
                    f"(FLAGS_check_nan_inf sweep)")


def _amp_cast(arrays, name):
    st = amp_state
    if name in st["black"]:
        target = jnp.float32
    elif st["level"] == "O2" or name in st["white"]:
        target = st["dtype"]
    else:
        return arrays
    return [
        a.astype(target)
        if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != target else a
        for a in arrays
    ]


# Observers consulted with every op's input tensors. Used by
# static.nn.control_flow's capture discovery (finding which pre-existing
# tensors a branch callable closes over) — the tape-level counterpart of the
# reference's block-input analysis in conditional_block's assign pass.
_op_input_observers: list = []


def apply_op(fn: Callable, tensors: Sequence[Tensor], attrs: dict = None,
             differentiable: bool = True, name: str = "") -> "Tensor | tuple":
    """Run one op through the tape.

    ``fn(*arrays, **attrs)`` must be a pure jax function of the tensor
    payloads. When grad is enabled and any input requires it, the forward runs
    under ``jax.vjp`` and a GradNode is recorded on the outputs — the
    counterpart of the generated ``xxx_ad_func`` forwards (eager_gen.py:1291).
    """
    if _op_input_observers:
        for _obs in _op_input_observers:
            _obs(tensors)
    attrs = attrs or {}
    arrays = [t._value for t in tensors]
    if amp_state["enabled"]:
        arrays = _amp_cast(arrays, name)
    needs_grad = (
        differentiable
        and is_grad_enabled()
        and any(not t.stop_gradient for t in tensors)
    )
    if not needs_grad:
        outs = fn(*arrays, **attrs)
        if check_nan_inf_enabled:
            _nan_inf_sweep(outs, name)
        if benchmark_sync_enabled:
            jax.block_until_ready(outs)
        if isinstance(outs, tuple):
            return tuple(Tensor(o, stop_gradient=True) for o in outs)
        return Tensor(outs, stop_gradient=True)

    f = (lambda *xs: fn(*xs, **attrs)) if attrs else fn
    if _saved_tensor_hooks:
        # saved_tensors_hooks (reference: autograd/saved_tensors_hooks.py):
        # pack() replaces residual storage at record time; backward unpacks
        # and recomputes the vjp from the restored inputs. The jax.vjp
        # residuals themselves are closure-held, so "saved tensors" here
        # are the op inputs and recompute replaces residual retention.
        pack, unpack = _saved_tensor_hooks[-1]
        outs = f(*arrays)
        packed = [pack(a) for a in arrays]

        def vjp_fn(cotangents, _f=f, _packed=packed, _unpack=unpack):
            vals = [_unpack(p) for p in _packed]
            _, inner_vjp = jax.vjp(_f, *vals)
            return inner_vjp(cotangents)
    elif not any(isinstance(a, jax.core.Tracer) for a in arrays):
        # Deferred linearization (measured in round 3, BENCH_NOTES_r03.json): eager-time
        # jax.vjp costs ~1.4ms/op vs ~36µs for the plain forward, so concrete
        # dispatches run the forward alone and linearize lazily at backward —
        # ops never reached by backward (eval forwards, pruned branches) pay
        # nothing. Under a trace (tracer inputs) the eager jax.vjp stays:
        # lazy re-linearization there would duplicate the traced graph and
        # lean on XLA CSE to clean it up.
        outs = f(*arrays)

        def vjp_fn(cotangents, _f=f, _vals=tuple(arrays)):
            _, inner_vjp = jax.vjp(_f, *_vals)
            return inner_vjp(cotangents)
    else:
        outs, vjp_fn = jax.vjp(f, *arrays)
    if check_nan_inf_enabled:
        _nan_inf_sweep(outs, name)
    if benchmark_sync_enabled:
        jax.block_until_ready(outs)
    is_tuple = isinstance(outs, tuple)
    outs_seq = outs if is_tuple else (outs,)
    out_tensors = tuple(Tensor(o, stop_gradient=False) for o in outs_seq)
    make_node_for_outputs(vjp_fn, tensors, out_tensors,
                          name=name or getattr(fn, "__name__", "op"),
                          out_tuple=is_tuple, fn=f, in_vals=tuple(arrays))
    return out_tensors if is_tuple else out_tensors[0]


def inplace_rebind(x: Tensor, out: Tensor):
    """Give ``x`` the value/tape-position of ``out`` (reference: inplace op
    semantics + version counter). ``x`` gets a fresh uid so edges recorded
    against its old value keep routing gradient to the old producer.

    When no node was recorded (no_grad / non-differentiable inputs), only the
    value moves — x keeps its own stop_gradient, so e.g. a Parameter updated
    in-place under no_grad stays trainable.
    """
    x._set_value(out._value)
    x._uid = next(_uid_counter)
    if out._grad_node is not None:
        x._grad_node = out._grad_node
        x._output_index = out._output_index
        x.stop_gradient = out.stop_gradient
        out._grad_node.out_uids[out._output_index] = x._uid
    else:
        x._grad_node = None
        x._output_index = 0
    return x


def _toposort(roots: Sequence[GradNode]):
    """Reverse-postorder DFS over snapshot edges: consumers before producers
    (reference: the in-degree queue walk in backward.cc:104)."""
    order, visited = [], set()
    for root in roots:
        if id(root) in visited:
            continue
        visited.add(id(root))
        stack = [(root, iter([e[2] for e in root.edges if e[2] is not None]))]
        while stack:
            node, children = stack[-1]
            advanced = False
            for child in children:
                if id(child) not in visited:
                    visited.add(id(child))
                    stack.append((child, iter([e[2] for e in child.edges if e[2] is not None])))
                    advanced = True
                    break
            if not advanced:
                order.append(node)
                stack.pop()
    order.reverse()  # consumers first
    return order


def _run_backward(
    out_tensors: Sequence[Tensor],
    out_grads: Optional[Sequence],
    retain_graph: bool,
    accumulate_into_leaves: bool,
    wanted_uids: Optional[set] = None,
):
    """Core walk shared by .backward() and paddle.grad().

    Returns {uid: raw cotangent array} for every tensor uid that received a
    gradient during the walk.
    """
    grads_by_uid: dict[int, jax.Array] = {}
    roots = []
    for i, t in enumerate(out_tensors):
        if t._grad_node is None and t.stop_gradient:
            raise RuntimeError(
                f"Tensor {t.name} has stop_gradient=True and no grad node; backward() on it is meaningless"
            )
        g = None if out_grads is None else out_grads[i]
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for tensors with a single element; "
                    f"got shape {t.shape}. Pass grad_tensor explicitly."
                )
            g_arr = jnp.ones(t._value.shape, t._value.dtype)
        else:
            g_arr = g._value if isinstance(g, Tensor) else jnp.asarray(g)
        uid = t._uid
        grads_by_uid[uid] = grads_by_uid[uid] + g_arr if uid in grads_by_uid else g_arr
        if t._grad_node is not None:
            roots.append(t._grad_node)

    order = _toposort(roots)

    # uid -> tensor, for hook application (applied ONCE on the finalized
    # gradient — when a producer node consumes it, or at end of walk for
    # leaves) and for end-of-walk leaf .grad accumulation. Mirrors the
    # reference's hook placement on the grad-accumulation node.
    hooked: dict[int, Tensor] = {}
    leaf_targets: dict[int, Tensor] = {}
    hooks_applied: set[int] = set()

    def _register(t: Tensor, uid: int):
        if t._uid != uid:
            return  # tensor rebound since edge was recorded: old value has no hooks/.grad
        if t._hooks:
            hooked[uid] = t
        if not t.stop_gradient and t._grad_node is None:
            leaf_targets[uid] = t

    for t in out_tensors:
        _register(t, t._uid)

    def _apply_hooks(uid: int):
        t = hooked.get(uid)
        if t is None or uid in hooks_applied or uid not in grads_by_uid:
            return
        hooks_applied.add(uid)
        g = grads_by_uid[uid]
        for hook in t._hooks:
            if hook is None:
                continue
            res = hook(Tensor(g))
            if res is not None:
                g = res._value if isinstance(res, Tensor) else jnp.asarray(res)
        grads_by_uid[uid] = g

    for node in order:
        if node.vjp_fn is None:
            raise RuntimeError(
                f"Trying to backward through node {node.name} a second time; "
                "set retain_graph=True if you need to."
            )
        cotangents = []
        for uid, (shape, dtype) in zip(node.out_uids, node.out_avals):
            _apply_hooks(uid)  # grad for this uid is final: all consumers ran
            g = grads_by_uid.get(uid)
            cotangents.append(jnp.zeros(shape, dtype) if g is None else g.astype(dtype))
        cts = tuple(cotangents) if node.out_tuple else cotangents[0]
        in_grads = node.vjp_fn(cts)
        if node.post_hooks:
            for hook in node.post_hooks:
                in_grads = hook(in_grads) or in_grads
        for (t, uid, producer), g in zip(node.edges, in_grads):
            if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
                continue
            if producer is None and t.stop_gradient and (
                wanted_uids is None or uid not in wanted_uids
            ):
                continue  # dead branch: nobody wants this grad
            grads_by_uid[uid] = grads_by_uid[uid] + g if uid in grads_by_uid else g
            _register(t, uid)
        if not retain_graph:
            node.vjp_fn = None

    for uid, t in leaf_targets.items():
        _apply_hooks(uid)
        g = grads_by_uid.get(uid)
        if g is None or not accumulate_into_leaves:
            continue
        if t.grad is None:
            t.grad = Tensor(g)
        else:
            t.grad = Tensor(t.grad._value + g)
    return grads_by_uid


def backward(tensors: Sequence[Tensor], grad_tensors=None, retain_graph: bool = False):
    """reference: paddle.autograd.backward / egr::Backward (backward.cc:423)."""
    _run_backward(tensors, grad_tensors, retain_graph, accumulate_into_leaves=True)


def _run_backward_create_graph(out_tensors, out_grads, wanted_uids: set):
    """The double-grad walk (reference: higher-order GradNodes emitted by
    eager_gen + prim composite rules). Cotangents are TENSORS and every VJP
    application is re-derived through ``apply_op`` from the node's recorded
    (fn, input values) — so the returned grads carry their own tape and can
    be differentiated again (any order: jax.vjp composes)."""
    grads_by_uid: dict[int, Tensor] = {}
    roots = []
    for i, t in enumerate(out_tensors):
        g = None if out_grads is None else out_grads[i]
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for tensors with a "
                    f"single element; got shape {t.shape}.")
            gt = Tensor(jnp.ones(t._value.shape, t._value.dtype),
                        stop_gradient=True)
        else:
            gt = g if isinstance(g, Tensor) else Tensor(jnp.asarray(g))
        uid = t._uid
        grads_by_uid[uid] = (grads_by_uid[uid] + gt) if uid in grads_by_uid \
            else gt
        if t._grad_node is not None:
            roots.append(t._grad_node)

    # tensor hooks fire on the finalized grad exactly like the first-order
    # walk — a hook (e.g. grad clipping) silently skipped under create_graph
    # would make double-grad results diverge from backward()/grad()
    hooked: dict[int, Tensor] = {}
    hooks_applied: set[int] = set()

    def _register(t: Tensor, uid: int):
        if t._uid == uid and t._hooks:
            hooked[uid] = t

    for t in out_tensors:
        _register(t, t._uid)

    def _apply_hooks(uid: int):
        t = hooked.get(uid)
        if t is None or uid in hooks_applied or uid not in grads_by_uid:
            return
        hooks_applied.add(uid)
        g = grads_by_uid[uid]
        for hook in t._hooks:
            if hook is None:
                continue
            res = hook(g)
            if res is not None:
                g = res if isinstance(res, Tensor) else Tensor(jnp.asarray(res))
        grads_by_uid[uid] = g

    for node in _toposort(roots):
        if node.fn is None or node.in_vals is None:
            raise RuntimeError(
                f"node {node.name} was not recorded with its forward fn; "
                "create_graph=True cannot differentiate through it")
        cts = []
        for uid, (shape, dtype) in zip(node.out_uids, node.out_avals):
            _apply_hooks(uid)  # grad final: all consumers ran
            g = grads_by_uid.get(uid)
            cts.append(Tensor(jnp.zeros(shape, dtype), stop_gradient=True)
                       if g is None else g.astype(str(dtype)))
        # differentiation inputs: the edge tensors when not rebound (their
        # lineage carries second-order grads further back), else constants
        # at the recorded values
        in_tensors = []
        for (t, uid, _), v in zip(node.edges, node.in_vals):
            if t._uid == uid and tuple(t._value.shape) == tuple(v.shape):
                in_tensors.append(t)
            else:
                in_tensors.append(Tensor(v, stop_gradient=True))
        n_in = len(in_tensors)
        out_tuple = node.out_tuple
        node_fn = node.fn

        def grad_op(*vals, _fn=node_fn, _n=n_in, _tuple=out_tuple):
            ins, gs = vals[:_n], vals[_n:]
            _, vjp = jax.vjp(_fn, *ins)
            res = vjp(tuple(gs) if _tuple else gs[0])
            return tuple(res)

        in_grads = apply_op(grad_op, in_tensors + cts,
                            name=f"{node.name}_grad")
        if not isinstance(in_grads, tuple):
            in_grads = (in_grads,)
        for (t, uid, producer), g in zip(node.edges, in_grads):
            if g is None or g._value.dtype == jax.dtypes.float0:
                continue
            if producer is None and t.stop_gradient and uid not in wanted_uids:
                continue
            grads_by_uid[uid] = (grads_by_uid[uid] + g) \
                if uid in grads_by_uid else g
            _register(t, uid)
    for uid in list(hooked):
        _apply_hooks(uid)  # leaves: finalized at end of walk
    return grads_by_uid


def grad(
    outputs,
    inputs,
    grad_outputs=None,
    retain_graph: Optional[bool] = None,
    create_graph: bool = False,
    only_inputs: bool = True,
    allow_unused: bool = False,
):
    """reference: paddle.grad (eager GeneralGrad, eager/general_grad.h).

    Examples:
        >>> x = paddle.to_tensor(2.0, stop_gradient=False)
        >>> y = x * x
        >>> (gx,) = paddle.grad(y, x)
        >>> float(gx)
        4.0

    ``create_graph=True`` returns grads that are themselves on the tape
    (differentiable — the double-grad path), re-deriving each op's VJP from
    its recorded forward; see ``_run_backward_create_graph``. Forward-mode /
    program-level higher-order AD also lives in paddle_tpu.incubate.autograd.
    """
    del only_inputs
    outputs = [outputs] if isinstance(outputs, Tensor) else list(outputs)
    inputs = [inputs] if isinstance(inputs, Tensor) else list(inputs)
    if grad_outputs is not None and isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]
    if retain_graph is None:
        retain_graph = create_graph
    wanted = {t._uid for t in inputs}
    if create_graph:
        grads_by_uid = _run_backward_create_graph(outputs, grad_outputs,
                                                  wanted_uids=wanted)
    else:
        grads_by_uid = _run_backward(
            outputs, grad_outputs, retain_graph, accumulate_into_leaves=False,
            wanted_uids=wanted
        )
    results = []
    for t in inputs:
        g = grads_by_uid.get(t._uid)
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    f"One of the differentiated tensors ({t.name}) appears unused in the graph; "
                    "pass allow_unused=True to get None for it."
                )
            results.append(None)
        elif create_graph:
            results.append(g)  # already a tape Tensor with lineage
        else:
            results.append(Tensor(g))
    return results
