"""StaticFunction — the trace/compile engine behind ``paddle_tpu.jit.to_static``.

TPU-native counterpart of the reference's dy2static stack
(``python/paddle/jit/api.py:232`` ``to_static`` → ``StaticFunction``
``dy2static/program_translator.py:304`` → AST transform → Program →
``PartialProgramLayer``) **and** of the static-graph executor
(``InterpreterCore``, ``new_executor/interpretercore.h:41``): on TPU both
collapse into "trace the imperative code with JAX tracers, compile one XLA
program per input signature, cache it" (cache keyed like ``_ExecutorCache``,
``fluid/executor.py:722``).

No AST rewriting is needed: the eager engine (autograd/engine.py) is
traceable by construction, so the *same* imperative train-step code — forward,
``loss.backward()`` tape walk, ``opt.step()`` — runs under ``jax.jit`` tracers
and lowers to a single fused XLA program, parameter updates included (the
reference needed separate eager/static engines + program passes for this).

Mutable state is functionalized through *slots*: every Parameter/buffer cell,
optimizer accumulator, and RNG key reachable from the function is passed in
and returned as an explicit pytree, with input buffers donated so XLA updates
parameters in place (the buffer-donation answer to the reference's inplace
``adamw_`` ops — SURVEY.md §7 hard part #2).
"""
from __future__ import annotations

import gc
import hashlib
import os
import pickle
import tempfile
import weakref
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from .. import tensor as tensor_mod
from ..generator import Generator, default_generator
from ..nn.layer_base import Layer
from ..optimizer.optimizer import Optimizer
from ..tensor import Tensor

__all__ = ["StaticFunction", "InputSpec", "set_compile_cache_dir",
           "get_compile_cache_dir", "clear_compile_cache"]


class InputSpec:
    """reference: paddle.static.InputSpec (python/paddle/static/input.py).

    ``None`` dims mean "polymorphic": each distinct concrete value simply
    compiles (and caches) one more XLA executable — padding/bucketing is the
    caller's policy (SURVEY.md §7 hard part #3).
    """

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        from .. import dtypes

        self.shape = tuple(shape)
        self.dtype = dtypes.convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


# --------------------------------------------------------------------- slots
class _TensorSlot:
    """A mutable Tensor cell captured as compiled-step state."""

    __slots__ = ("t",)

    def __init__(self, t: Tensor):
        self.t = t

    def get(self):
        return self.t._value

    def set(self, v):
        self.t._value = v

    def sanitize(self):
        """Drop trace-time tape residue so no tracer outlives the trace."""
        t = self.t
        t._grad_node = None
        if t.grad is not None and isinstance(t.grad._value, jax.core.Tracer):
            t.grad = None


class _AccSlot:
    """One optimizer accumulator array (state lives in Optimizer._accumulators)."""

    __slots__ = ("opt", "uid", "name")

    def __init__(self, opt: Optimizer, uid: int, name: str):
        self.opt, self.uid, self.name = opt, uid, name

    def get(self):
        return self.opt._accumulators[self.uid][self.name]

    def set(self, v):
        self.opt._accumulators[self.uid][self.name] = v

    def sanitize(self):
        pass


class _GenSlot:
    """The global PRNG key (generator.py) — randomness becomes a pure
    function of the captured key, threefry compiled into the program."""

    __slots__ = ("gen",)

    def __init__(self, gen: Generator):
        self.gen = gen

    def get(self):
        return self.gen.get_state()

    def set(self, v):
        self.gen.set_state(v)

    def sanitize(self):
        pass


class _WriteRecorder:
    """Hooks tensor_mod._trace_recorders during the warm-up eager call to
    catch mutable cells the structural scan missed (module-global EMA tensors
    and the like)."""

    def __init__(self):
        self.written: dict[int, weakref.ref] = {}

    def record_write(self, t: Tensor):
        self.written[id(t)] = weakref.ref(t)

    def alive_tensors(self):
        gc.collect()  # temporaries written in-place then dropped must not become state
        return [r() for r in self.written.values() if r() is not None]


# ----------------------------------------------------------------- discovery
def _scan_state(objs: Sequence[Any], transient: Sequence[Any] = ()):
    """Walk closures/args for Layers, Optimizers, Generators, Tensors and any
    object exposing ``__jit_state__()`` (e.g. amp.GradScaler). Returns
    (slots, optimizers, layers).

    ``transient`` objects (call arguments) are walked for Layers/Optimizers,
    but bare Tensors found there are data batches, not persistent state —
    registering them as slots would pin the warm-up batch in HBM forever and
    round-trip it through every compiled call."""
    seen: set[int] = set()
    tensors: list[Tensor] = []
    opts: list[Optimizer] = []
    layers: list[Layer] = []
    gens: list[Generator] = [default_generator]
    stack = [(o, False) for o in objs] + [(o, True) for o in transient]
    while stack:
        o, is_transient = stack.pop()
        if o is None or id(o) in seen:
            continue
        seen.add(id(o))
        if isinstance(o, Tensor):
            if not is_transient:
                tensors.append(o)
        elif isinstance(o, Layer):
            layers.append(o)
            tensors.extend(o.parameters())
            tensors.extend(o.buffers())
        elif isinstance(o, Optimizer):
            opts.append(o)
            stack.extend((p, False) for p in (o._parameter_list or []))
            if getattr(o, "_grad_clip", None) is not None:
                stack.append((o._grad_clip, False))
        elif isinstance(o, Generator):
            gens.append(o)
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend((v, is_transient) for v in o)
        elif isinstance(o, dict):
            stack.extend((v, is_transient) for v in o.values())
        if hasattr(o, "__jit_state__"):
            try:
                stack.extend((v, False) for v in o.__jit_state__())
            except Exception:
                pass
    slots: list = []
    slot_ids: set[int] = set()
    for t in tensors:
        if id(t) not in slot_ids:
            slot_ids.add(id(t))
            slots.append(_TensorSlot(t))
    for g in dict.fromkeys(gens):
        slots.append(_GenSlot(g))
    return slots, opts, layers, slot_ids


def _closure_objects(fn: Callable):
    """Objects the function can reach: bound self, closure cells, defaults,
    and the module globals it actually references (``co_names`` — a
    module-level train step holds its model/optimizer as globals, not
    closure cells)."""
    objs = []
    f = fn
    if hasattr(f, "__self__") and f.__self__ is not None:
        objs.append(f.__self__)
        f = f.__func__
    if getattr(f, "__closure__", None):
        for cell in f.__closure__:
            try:
                objs.append(cell.cell_contents)
            except ValueError:
                pass
    if getattr(f, "__defaults__", None):
        objs.extend(f.__defaults__)
    code = getattr(f, "__code__", None)
    glob = getattr(f, "__globals__", None)
    if code is not None and glob is not None:
        import dis
        import types

        # only names actually loaded as globals — co_names also lists
        # attribute names, which could collide with unrelated module globals.
        # Recurse into nested code objects (lambdas / inner defs): a branch
        # callable passed to static.nn.cond reaches its globals too.
        loaded = set()
        stack = [code]
        while stack:
            c = stack.pop()
            loaded.update(
                ins.argval for ins in dis.get_instructions(c)
                if ins.opname in ("LOAD_GLOBAL", "LOAD_NAME")
            )
            stack.extend(k for k in c.co_consts
                         if isinstance(k, types.CodeType))
        for name in loaded:
            if name in glob:
                objs.append(glob[name])
    return objs


# ------------------------------------------------------------ arg flattening
class _Static:
    """Marker wrapping a non-tensor leaf; identity participates in cache key."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v


def _flatten_args(tree):
    """Split (args, kwargs) into (traced arrays, spec) where spec rebuilds the
    structure with placeholders for traced leaves. Tensors and bare jax/numpy
    arrays are traced; python scalars/strings/None are static."""
    arrays: list = []
    meta: list = []  # parallel to arrays: (stop_gradient,)

    def go(x):
        if isinstance(x, Tensor):
            arrays.append(x._value)
            meta.append(bool(x.stop_gradient))
            return ("T", len(arrays) - 1)
        if isinstance(x, (jax.Array, np.ndarray)):
            arrays.append(jnp.asarray(x))
            meta.append(True)
            return ("A", len(arrays) - 1)
        if isinstance(x, (list, tuple)):
            return (type(x).__name__, [go(v) for v in x])
        if isinstance(x, dict):
            return ("dict", [(k, go(v)) for k, v in sorted(x.items(), key=lambda kv: str(kv[0]))])
        return ("S", _Static(x))

    spec = go(tree)
    return arrays, meta, spec


def _rebuild_args(spec, arrays, meta):
    kind, payload = spec
    if kind == "T":
        return Tensor(arrays[payload], stop_gradient=meta[payload])
    if kind == "A":
        return arrays[payload]
    if kind == "S":
        return payload.v
    if kind == "list":
        return [_rebuild_args(s, arrays, meta) for s in payload]
    if kind == "tuple":
        return tuple(_rebuild_args(s, arrays, meta) for s in payload)
    if kind == "dict":
        return {k: _rebuild_args(s, arrays, meta) for k, s in payload}
    raise AssertionError(kind)


def _spec_key(spec, arrays, meta):
    kind, payload = spec
    if kind in ("T", "A"):
        a = arrays[payload]
        # weak_type participates: jax.jit would silently retrace on a
        # weak/strong flip, but an AOT-loaded executable (persistent
        # compile cache) REJECTS the mismatched aval — keying on it keeps
        # both paths one-signature-one-program
        return (kind, tuple(a.shape), str(a.dtype), meta[payload],
                bool(getattr(a, "weak_type", False)))
    if kind == "S":
        v = payload.v
        try:
            hash(v)
            return ("S", v)
        except TypeError:
            return ("S", repr(v))
    if kind in ("list", "tuple"):
        return (kind, tuple(_spec_key(s, arrays, meta) for s in payload))
    if kind == "dict":
        return ("dict", tuple((k, _spec_key(s, arrays, meta)) for k, s in payload))
    raise AssertionError(kind)


def _flatten_out(out):
    arrays: list = []

    def go(x):
        if isinstance(x, Tensor):
            arrays.append(x._value)
            return ("T", len(arrays) - 1, bool(x.stop_gradient))
        if isinstance(x, (jax.Array, jax.core.Tracer)):
            arrays.append(x)
            return ("A", len(arrays) - 1, True)
        if isinstance(x, (list, tuple)):
            return (type(x).__name__, [go(v) for v in x], None)
        if isinstance(x, dict):
            return ("dict", [(k, go(v)) for k, v in x.items()], None)
        return ("S", x, None)

    spec = go(out)
    return arrays, spec


def _rebuild_out(spec, arrays):
    kind, payload, extra = spec
    if kind == "T":
        return Tensor(arrays[payload], stop_gradient=extra)
    if kind == "A":
        return arrays[payload]
    if kind == "S":
        return payload
    if kind == "list":
        return [_rebuild_out(s, arrays) for s in payload]
    if kind == "tuple":
        return tuple(_rebuild_out(s, arrays) for s in payload)
    if kind == "dict":
        return {k: _rebuild_out(s, arrays) for k, s in payload}
    raise AssertionError(kind)


def _donation_off() -> bool:
    return os.environ.get("PADDLE_TPU_NO_DONATE") == "1"


def _aliased_bytes(executable) -> int:
    """Bytes of argument buffers a compiled executable writes its outputs
    into; 0 where the backend has no memory analysis."""
    try:
        return int(executable.memory_analysis().alias_size_in_bytes)
    except Exception:
        return 0


def _buffer_ptr(v):
    try:
        return v.unsafe_buffer_pointer()
    except Exception:
        return id(v)


def _leaf_indices(spec):
    """Positions in the flat array list of every traced leaf under ``spec``
    (a node of :func:`_flatten_args`' structure)."""
    kind, payload = spec
    if kind in ("T", "A"):
        return [payload]
    if kind in ("list", "tuple"):
        return [i for s in payload for i in _leaf_indices(s)]
    if kind == "dict":
        return [i for _, s in payload for i in _leaf_indices(s)]
    return []


def _unalias(state_vals, arrays, given=()):
    """State buffers, and the argument leaves ``given`` (indices into
    ``arrays``) that the caller gives up, are donated to the compiled step;
    XLA rejects a donated buffer that aliases another argument (e.g. two
    accumulators both produced by one CSE'd zeros_like, a Parameter also
    passed as a data input, one array passed both given-up and kept). Copy
    any such duplicate so every donated buffer is unique. Returns the state
    values and the argument list, with copies in the duplicates' places."""
    given = set(given)
    seen = {_buffer_ptr(v) for i, v in enumerate(arrays) if i not in given}

    def unique(v):
        ptr = _buffer_ptr(v)
        if ptr in seen:
            return jnp.array(v, copy=True)
        seen.add(ptr)
        return v

    state_vals = [unique(v) for v in state_vals]
    if given:
        arrays = [unique(v) if i in given else v
                  for i, v in enumerate(arrays)]
    return state_vals, arrays


# -------------------------------------------------- persistent compile cache
# Executable reuse across processes (and across StaticFunction instances in
# one process): `_build` consults a process-wide memory layer, then an
# on-disk layer of serialized XLA executables, before paying a fresh trace +
# XLA compile. Both layers are off unless a cache directory is configured —
# via the StaticFunction ``cache_dir=`` ctor arg, :func:`set_compile_cache_dir`,
# or the ``PADDLE_TPU_COMPILE_CACHE`` env var; the fresh build compiles ahead
# of time either way, so `_build` has the executable in hand (its memory
# analysis is published there). Every materialization increments
# paddle_tpu_jit_compiles_total{fn, source="memory|disk|fresh"} exactly
# once: the per-fn SUM keeps the old one-inc-per-build meaning, while the
# source split makes warm restarts and rolling reloads monitorable
# (docs/OBSERVABILITY.md).
_cache_dir_override: Optional[str] = None
_MEMORY_CACHE: dict = {}  # full key string -> (aot_executable, out_spec)


def set_compile_cache_dir(path: Optional[str]) -> None:
    """Enable (or, with None, disable) the persistent compile cache for
    every StaticFunction that doesn't pin its own ``cache_dir=``. The
    directory is created lazily on first store."""
    global _cache_dir_override
    _cache_dir_override = None if path is None else str(path)


def get_compile_cache_dir() -> Optional[str]:
    """The process-default cache dir: :func:`set_compile_cache_dir` wins,
    else the ``PADDLE_TPU_COMPILE_CACHE`` env var, else None (disabled)."""
    if _cache_dir_override is not None:
        return _cache_dir_override
    return os.environ.get("PADDLE_TPU_COMPILE_CACHE") or None


def clear_compile_cache(memory: bool = True, disk: bool = False) -> int:
    """Drop cached executables; returns how many entries were dropped.
    ``memory`` clears the process-wide layer (tests use this to force the
    next build through the DISK path, simulating a cold process);
    ``disk`` unlinks every ``*.jitcache`` file in the resolved cache dir."""
    n = 0
    if memory:
        n += len(_MEMORY_CACHE)
        _MEMORY_CACHE.clear()
    if disk:
        d = get_compile_cache_dir()
        if d is not None and os.path.isdir(d):
            for name in os.listdir(d):
                if name.endswith(".jitcache"):
                    try:
                        os.unlink(os.path.join(d, name))
                        n += 1
                    except OSError:
                        pass
    return n


def _code_fingerprint(fn) -> str:
    """sha256 over the function's bytecode, constants, and names —
    recursing into nested code objects (closures, comprehensions) — so a
    source edit invalidates cached executables even when shapes match.
    Unintrospectable callables fingerprint by qualified name: better a
    coarse key than a stale executable."""
    h = hashlib.sha256()

    def feed(code):
        h.update(code.co_code)
        h.update(repr(code.co_names).encode())
        for c in code.co_consts:
            if hasattr(c, "co_code"):
                # recurse INSTEAD of repr-ing: a code object's repr
                # embeds its memory address, which would make the
                # fingerprint process-unique and defeat the disk cache
                feed(c)
            else:
                h.update(repr(c).encode())

    target = getattr(fn, "__wrapped__", fn)
    code = getattr(target, "__code__", None)
    if code is None:
        h.update(repr(getattr(fn, "__qualname__", fn)).encode())
    else:
        feed(code)
    return h.hexdigest()


def _load_disk_entry(path: str, full_key: str):
    """(aot, out_spec) deserialized from ``path``, or None. ANY failure —
    missing file, truncated pickle, version/device drift surfacing as a
    deserialization error, a digest collision caught by the stored
    full-key mismatch — means "not cached": the caller falls back to a
    fresh build, never crashes."""
    try:
        with open(path, "rb") as f:
            entry = pickle.load(f)
        if entry.get("key") != full_key:
            return None
        from jax.experimental import serialize_executable

        aot = serialize_executable.deserialize_and_load(
            entry["payload"], entry["in_tree"], entry["out_tree"])
        return aot, entry["out_spec"]
    except Exception:
        return None


def _store_disk_entry(path: str, full_key: str, aot, out_spec) -> None:
    """Serialize an AOT executable to ``path`` atomically (tmp file +
    os.replace: a concurrently starting process reads either the old
    complete entry or the new one, never a torn write). Best-effort: an
    unserializable executable or unwritable dir just means the next
    process compiles fresh."""
    try:
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(aot)
        blob = pickle.dumps({"key": full_key, "payload": payload,
                             "in_tree": in_tree, "out_tree": out_tree,
                             "out_spec": out_spec})
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except Exception:
        pass


# ------------------------------------------------------------ StaticFunction
class _Compiled:
    __slots__ = ("jitted", "out_spec", "aot", "given")

    def __init__(self, jitted, out_spec=None, aot=None, given=()):
        self.jitted = jitted
        self.out_spec = out_spec
        # the executable `_build` compiled (or the persistent cache
        # held): calls run it; `jitted` stays alive regardless, for
        # cost_analysis/lower and as the path a call degrades to
        self.aot = aot
        # flat argument leaves this program consumes (donate_argnums)
        self.given = given


class StaticFunction:
    """Callable wrapper compiling the wrapped imperative fn per input
    signature (reference: StaticFunction, dy2static/program_translator.py:304).
    """

    def __init__(self, function: Callable, input_spec=None, build_strategy=None,
                 property=False, full_graph=True, observe: Sequence[Any] = (),
                 warmup: bool = True, dy2static: bool = True,
                 cache_dir: Optional[str] = None,
                 cache_key_extra: Optional[str] = None,
                 donate_argnums: Sequence[int] = ()):
        if dy2static and os.environ.get("PADDLE_TPU_DY2STATIC") != "0":
            # AST pass rewriting Python if/while on tensor values into
            # static.nn control flow (jit/dy2static.py — reference:
            # jit/dy2static/ast_transformer.py). Semantics-preserving for
            # Python-bool control flow; no-ops when source is unavailable.
            from .dy2static import ast_transform

            function = ast_transform(function)
        self._fn = function
        self._input_spec = input_spec
        self._observe = list(observe)
        self._do_warmup = warmup
        self._slots: Optional[list] = None
        self._slot_ids: set[int] = set()
        self._opts: list[Optimizer] = []
        self._layers: list[Layer] = []
        self._cache: dict = {}
        self._abstract_args: dict = {}  # cache key -> ShapeDtypeStruct tree
        self._warmed_up = False
        # persistent compile cache: an instance-pinned dir beats the
        # process default (set_compile_cache_dir / PADDLE_TPU_COMPILE_CACHE).
        # cache_key_extra folds caller context the shape-only spec key
        # can't see — constants baked into the traced program (model
        # config, pool geometry) — into the persistent key, so two
        # functions with equal signatures but different closures never
        # share an executable.
        self._cache_dir = None if cache_dir is None else str(cache_dir)
        self._cache_key_extra = ("" if cache_key_extra is None
                                 else str(cache_key_extra))
        # positional arguments of `function` whose arrays the caller gives
        # up: the compiled program consumes their buffers (XLA writes its
        # outputs into them), and after a call the caller's references
        # are deleted arrays. Ownership is a fact of the call site, so it
        # is declared where the StaticFunction is constructed.
        self._donate_argnums = tuple(sorted({int(i) for i in donate_argnums}))
        self.__name__ = getattr(function, "__name__", "static_fn")
        self.__doc__ = getattr(function, "__doc__", None)

    # -- introspection -------------------------------------------------------
    def _latest_key(self):
        return next(reversed(self._abstract_args), None)

    def _compiled(self, key=None) -> Optional[_Compiled]:
        """The program of a called signature (``key=None``: the most
        recent call's)."""
        return self._cache.get(self._latest_key() if key is None else key)

    def _lowered(self, key=None):
        """``jax.stages.Lowered`` of a compiled signature from its recorded
        abstract arguments (``key=None``: the most recent); None before
        any call compiled. Lowering may re-trace the function, which
        leaves tracers in the state slots — they are put back."""
        if key is None:
            key = self._latest_key()
        compiled = self._cache.get(key)
        abstract = self._abstract_args.get(key)
        if compiled is None or abstract is None:
            return None
        saved = [slot.get() for slot in self._slots]
        try:
            return compiled.jitted.lower(*abstract)
        finally:
            for slot, v in zip(self._slots, saved):
                slot.set(v)

    def cost_analysis(self, key=None) -> Optional[dict]:
        """XLA cost analysis (flops / bytes accessed / ...) of a compiled
        signature — the TPU answer to the reference auto_parallel cost model
        (engine.py:1751, auto_parallel/cost/). ``key=None`` picks the most
        recent signature. Returns None before any call compiled."""
        lowered = self._lowered(key)
        if lowered is None:
            return None
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost) if cost else {}

    def program_text(self, key=None, compiled: bool = False) -> Optional[str]:
        """Text of a compiled signature (``key=None``: the most recent;
        keys are those of the signature cache): the StableHLO it lowers
        to, or with ``compiled=True`` the HLO after XLA's passes — SPMD
        partitioning included, so collectives show only there; that costs
        a compile unless JAX's persistent cache holds it. A Pallas TPU
        kernel shows in both as ``tpu_custom_call`` — chip_smoke.py
        asserts on that instead of trusting the dispatch. None before any
        call compiled."""
        built = self._compiled(key)
        if compiled and built is not None and built.aot is not None:
            return built.aot.as_text()  # the executable the calls run
        lowered = self._lowered(key)
        if lowered is None:
            return None
        return lowered.compile().as_text() if compiled else lowered.as_text()

    def aliased_bytes(self, key=None) -> Optional[int]:
        """Bytes of argument buffers that a compiled signature's executable
        writes its outputs into (``key=None``: the most recent) — what the
        ``paddle_tpu_jit_aliased_bytes`` gauge read when it was built. None
        before any call compiled, or where the call runs ``jitted``."""
        compiled = self._compiled(key)
        if compiled is None or compiled.aot is None:
            return None
        return _aliased_bytes(compiled.aot)

    def lower(self, *args, **kwargs):
        """AOT trace + lower WITHOUT executing (reference counterpart: the
        build-program-only half of Executor.run; jax answer: jax.stages).
        Returns the ``jax.stages.Lowered`` for this signature — call
        ``.compile()`` on it for cost/memory analysis. No step runs, so no
        gradient/activation buffers are ever allocated: this is the
        memory-budget path for models too big to step on the host
        (tools/llama7b_budget.py). State shardings (ZeRO/TP annotations on
        the live params) are carried into the lowering."""
        if not self._warmed_up:
            if self._do_warmup:
                # structural scan would miss the in-place-written cells the
                # eager warmup records; silently downgrading state discovery
                # would corrupt later real calls
                raise RuntimeError(
                    "StaticFunction.lower() before the first call requires "
                    "warmup=False (structural state discovery); either call "
                    "the function once first, or construct with "
                    "warmup=False and list state in observe=")
            self._setup_no_warmup()
        _, compiled, operands = self._prepare(args, kwargs, compile=False)
        return compiled.jitted.lower(*operands)

    # -- paddle API surface --------------------------------------------------
    @property
    def dygraph_function(self):
        return self._fn

    def concrete_program_specified_input_spec(self, *a, **k):  # legacy shim
        return None

    def rollback(self):
        return self._fn

    # -- warm-up -------------------------------------------------------------
    def _warmup(self, args, kwargs):
        """First call runs eagerly: materializes lazy optimizer accumulators,
        and records every cell written in-place (counterpart of the program
        build phase of the reference's first Executor.run)."""
        rec = _WriteRecorder()
        tensor_mod._trace_recorders.append(rec)
        try:
            out = self._fn(*args, **kwargs)
        finally:
            tensor_mod._trace_recorders.remove(rec)
        slots, opts, layers, slot_ids = _scan_state(
            _closure_objects(self._fn) + self._observe,
            transient=list(args) + list(kwargs.values()),
        )
        for t in rec.alive_tensors():
            if id(t) not in slot_ids:
                slot_ids.add(id(t))
                slots.append(_TensorSlot(t))
        for opt in opts:
            for uid, accs in opt._accumulators.items():
                for name in accs:
                    slots.append(_AccSlot(opt, uid, name))
        self._slots, self._opts, self._layers = slots, opts, layers
        self._slot_ids = slot_ids
        self._warmed_up = True
        return out

    # -- compile -------------------------------------------------------------
    def _resolve_cache_dir(self) -> Optional[str]:
        return (self._cache_dir if self._cache_dir is not None
                else get_compile_cache_dir())

    def _persistent_key(self, key, example, given) -> str:
        """The FULL persistent-cache key, as a stable string: everything
        that shapes the executable's bytes or its calling convention.
        Signature key (shapes/dtypes/weak_type of args, training flags),
        state/lr avals, the function's code fingerprint and caller-
        supplied extra, the donation policy, and the jax + device
        fingerprint (a different jaxlib or device kind must miss)."""
        state_vals, lr_vals = example[:2]
        dev = jax.devices()[0]
        state_avals = tuple((tuple(v.shape), str(v.dtype),
                             bool(getattr(v, "weak_type", False)))
                            for v in state_vals)
        return repr((
            self.__name__, _code_fingerprint(self._fn),
            self._cache_key_extra, key, state_avals, len(lr_vals),
            _donation_off(), given,
            jax.__version__, jax.lib.__version__,
            dev.platform, dev.device_kind,
        ))

    def _given_up(self, spec) -> tuple:
        """Flat leaf indices of the positional arguments named in
        ``donate_argnums``; nothing under ``PADDLE_TPU_NO_DONATE=1``."""
        if not self._donate_argnums or _donation_off():
            return ()
        positional = spec[1][0][1]  # spec of (args, kwargs) -> args' specs
        return tuple(i for n in self._donate_argnums
                     for i in _leaf_indices(positional[n]))

    def _build(self, spec, meta, key=None, example=None, given=()):
        # every signature-cache miss materializes ONE program, counted
        # exactly once with its source: "fresh" paid a trace + XLA
        # compile, "disk" deserialized a persisted executable (warm
        # restart), "memory" reused another StaticFunction's build in
        # this process (e.g. a second engine replica). The per-fn SUM
        # across sources keeps the old one-inc-per-build meaning — the
        # "decode compiles exactly once" invariant stays a monitorable
        # metric (paddle_tpu_jit_compiles_total{fn,source}), and a
        # recompile storm shows up on /metrics before it shows up as a
        # latency cliff. ``example`` (the first call's operands) is what
        # the executable is compiled for; without it (``lower()``) nothing
        # compiles here and a call runs ``jitted``.
        from ..metrics import get_registry

        slots, opts, fn = self._slots, self._opts, self._fn
        holder = _Compiled(None, given=given)

        def _functional(state_vals, lr_vals, arg_arrays, given_arrays):
            for slot, v in zip(slots, state_vals):
                slot.set(v)
            for opt, lr in zip(opts, lr_vals):
                opt._lr_override = lr
            if given:
                arg_arrays = list(arg_arrays)
                for i, v in zip(given, given_arrays):
                    arg_arrays[i] = v
            try:
                args, kwargs = _rebuild_args(spec, arg_arrays, meta)
                out = fn(*args, **kwargs)
            finally:
                for opt in opts:
                    opt._lr_override = None
            out_arrays, out_spec = _flatten_out(out)
            holder.out_spec = out_spec
            new_state = [slot.get() for slot in slots]
            return out_arrays, new_state

        # State buffers are donated so XLA reuses them for the updated state
        # (in-place optimizer semantics, reference: inplace op pass), and so
        # are the argument leaves the constructor's caller gave up
        # (``given_arrays``; empty unless declared). A donation-induced
        # wrongness would be TPU-only in effect — PADDLE_TPU_NO_DONATE=1
        # disables both as a bisect axis.
        donate = () if _donation_off() else (0, 3)
        holder.jitted = jax.jit(_functional, donate_argnums=donate)
        source = "fresh"
        registry = get_registry()
        if example is not None:
            cache_dir = self._resolve_cache_dir()
            full_key = path = ent = None
            if cache_dir is not None:
                full_key = self._persistent_key(key, example, given)
                path = os.path.join(
                    cache_dir,
                    f"{self.__name__}-"
                    f"{hashlib.sha256(full_key.encode()).hexdigest()[:32]}"
                    ".jitcache")
                ent = _MEMORY_CACHE.get(full_key)
                if ent is not None:
                    source = "memory"
                else:
                    ent = _load_disk_entry(path, full_key)
                    if ent is not None:
                        _MEMORY_CACHE[full_key] = ent
                        source = "disk"
            if ent is not None:
                holder.aot, holder.out_spec = ent
            else:
                try:
                    # the trace fires _functional, which captures
                    # out_spec on `holder` as a side effect
                    holder.aot = holder.jitted.lower(*example).compile()
                except Exception:
                    # an unlowerable corner falls back to the plain
                    # jax.jit path — correctness never depends on the
                    # ahead-of-time build
                    holder.aot = None
                if holder.aot is not None and full_key is not None:
                    _MEMORY_CACHE[full_key] = (holder.aot, holder.out_spec)
                    _store_disk_entry(path, full_key, holder.aot,
                                      holder.out_spec)
            if holder.aot is not None:
                # what donation bought, as the executable has it: bytes of
                # arguments whose buffers the outputs are written into. A
                # step that should update a pool in place reads the pool's
                # bytes here; 0 means every donated buffer was copied.
                registry.gauge(
                    "paddle_tpu_jit_aliased_bytes",
                    "Bytes of argument buffers that the StaticFunction "
                    "program built last for this fn writes its outputs "
                    "into (XLA memory analysis): donated state and "
                    "donate_argnums leaves updated in place",
                    labels=("fn",),
                ).labels(fn=self.__name__).set(float(_aliased_bytes(holder.aot)))
        registry.counter(
            "paddle_tpu_jit_compiles_total",
            "XLA programs materialized into a StaticFunction signature "
            "cache, by source: \"fresh\" paid an XLA compile, \"disk\" "
            "loaded the persistent compile cache, \"memory\" reused a "
            "process-wide build", labels=("fn", "source"),
        ).labels(fn=self.__name__, source=source).inc()
        return holder

    # -- call ----------------------------------------------------------------
    def _setup_no_warmup(self):
        """Discover state without an eager warm-up call (to_static(...,
        warmup=False)): structural scan only — optimizer accumulators are
        materialized explicitly, and cells invisible to the scan (module
        globals are covered; arbitrary object attributes are not) must be
        reachable via ``observe`` or ``__jit_state__``."""
        slots, opts, layers, slot_ids = _scan_state(
            _closure_objects(self._fn) + self._observe, transient=())
        for opt in opts:
            opt._materialize_accumulators()
            for uid, accs in opt._accumulators.items():
                for name in accs:
                    slots.append(_AccSlot(opt, uid, name))
        self._slots, self._opts, self._layers = slots, opts, layers
        self._slot_ids = slot_ids
        self._warmed_up = True

    def _prepare(self, args, kwargs, compile=True):
        """One call's signature key, its program (built on a miss; compiled
        too unless ``compile=False``) and the operands of ``_functional``:
        state, learning rates, the argument leaves with None where a leaf
        is given up, and the given-up leaves."""
        arrays, meta, spec = _flatten_args((args, kwargs))
        key = (
            _spec_key(spec, arrays, meta),
            tuple(l.training for l in self._layers),
        )
        compiled = self._cache.get(key)
        given = self._given_up(spec) if compiled is None else compiled.given
        state_vals, arrays = _unalias([s.get() for s in self._slots],
                                      arrays, given)
        lr_vals = [jnp.asarray(o.get_lr(), jnp.float32) for o in self._opts]
        kept = list(arrays)
        for i in given:
            kept[i] = None
        operands = (state_vals, lr_vals, kept, [arrays[i] for i in given])
        if compiled is None:
            compiled = self._build(spec, tuple(meta), key,
                                   operands if compile else None, given)
            self._cache[key] = compiled
        return key, compiled, operands

    def __call__(self, *args, **kwargs):
        if not self._warmed_up:
            if not self._do_warmup:
                self._setup_no_warmup()
            else:
                return self._warmup(args, kwargs)
        key, compiled, operands = self._prepare(args, kwargs)
        self._abstract_args.pop(key, None)  # move-to-end: dict order = recency
        # mesh shardings are part of the program (a re-lowering without
        # them is another program); single-device placement is not
        self._abstract_args[key] = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=(a.sharding if isinstance(
                    getattr(a, "sharding", None), NamedSharding) else None)),
            operands)
        if compiled.aot is not None:
            try:
                out_arrays, new_state = compiled.aot(*operands)
            except Exception:
                # a calling-convention mismatch (aval drift the key
                # missed, a sharding the executable was not compiled for)
                # degrades to the jax.jit path for good — the signature
                # check fails BEFORE execution, so the donated buffers
                # are still intact for the retry
                compiled.aot = None
                out_arrays, new_state = compiled.jitted(*operands)
        else:
            out_arrays, new_state = compiled.jitted(*operands)
        for slot, v in zip(self._slots, new_state):
            slot.set(v)
            slot.sanitize()
        return _rebuild_out(compiled.out_spec, out_arrays)
