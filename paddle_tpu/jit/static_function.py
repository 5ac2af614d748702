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
as an explicit pytree. The trace of a signature shows which slots the function
WROTE: those are donated and returned, so XLA updates them in place (the
buffer-donation answer to the reference's inplace ``adamw_`` ops — SURVEY.md
§7 hard part #2); a slot the trace only READ is an ordinary input and no
output (a serving step's parameters, a fine-tune's frozen layers).

Everything about a call that follows from its signature alone — the written
slots, the given-up argument leaves, the abstract operands, the output
structure — is decided once, when the signature's program is built, and kept
as that signature's launch plan (``_Plan``); a later call of the signature
reads the current values, launches, and hands back the results.
"""
from __future__ import annotations

import gc
import hashlib
import itertools
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
from ..profiler.ring import get_tracer
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
    """Marker wrapping a non-tensor leaf of a call's arguments."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v


def _flatten_args(args, kwargs):
    """One call's traced arrays, what each leaf is, the signature those
    leaves give, and the structure around them. Tensors and bare jax/numpy
    arrays are traced; python scalars/strings are static (``None`` is
    structure). ``meta`` is parallel to the leaves: a Tensor's
    ``stop_gradient``, None for a bare array, a ``_Static`` for the rest.

    The signature is one flat tuple: per traced leaf its kind, shape, dtype
    and weak_type (jax.jit would silently retrace on a weak/strong flip, but
    an AOT-loaded executable REJECTS the mismatched aval — keying on it
    keeps both paths one-signature-one-program), per static leaf its value."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    arrays: list = []
    meta: list = []
    sig: list = [treedef]
    for x in leaves:
        if isinstance(x, Tensor):
            a, stop = x._value, bool(x.stop_gradient)
            meta.append(stop)
            sig.append(stop)
        elif isinstance(x, (jax.Array, np.ndarray)):
            a = x if isinstance(x, jax.Array) else jnp.asarray(x)
            meta.append(None)
            sig.append("A")
        else:
            meta.append(_Static(x))
            try:
                hash(x)
                sig += ("S", x)
            except TypeError:
                sig += ("S", repr(x))
            continue
        arrays.append(a)
        sig += (a.shape, a.dtype, bool(getattr(a, "weak_type", False)))
    return arrays, meta, tuple(sig), treedef


def _rebuild_args(treedef, meta, arrays):
    """The (args, kwargs) that :func:`_flatten_args` took apart, around
    ``arrays`` (tracers, inside the trace)."""
    it = iter(arrays)
    return treedef.unflatten([
        m.v if isinstance(m, _Static)
        else next(it) if m is None
        else Tensor(next(it), stop_gradient=m)
        for m in meta])


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


def _n_traced_leaves(tree) -> int:
    return sum(isinstance(x, (Tensor, jax.Array, np.ndarray))
               for x in jax.tree_util.tree_leaves(tree))


def _dead_ref():
    return None


def _with_room(f):
    """``f()``, from a frame so large that the interpreter opens one roomy
    chunk of its frame stack for it, in which every frame below then fits.

    CPython (3.11 on) keeps Python frames in chunks of 16 KiB: a call whose
    frame does not fit maps a new chunk, and its return unmaps it. A trace is
    some hundred frames deep and re-enters its deepest dozen tens of thousands
    of times, so where a chunk happens to end inside them, every one of those
    calls pays both. How many frames the caller stands on decides that: the
    same serving-step trace took 9, 15 or 23 s a bucket on the chip's host
    (PERF.md section 6, PR 33), and one frame more or less in a refactor
    moved `setup_s` by seconds. The size is the only thing this frame is for."""
    return f()


_with_room.__code__ = _with_room.__code__.replace(co_stacksize=1 << 16)


def _abstract(a):
    # mesh shardings are part of the program (a re-lowering without them is
    # another program); single-device placement is not
    return jax.ShapeDtypeStruct(
        a.shape, a.dtype, weak_type=bool(getattr(a, "weak_type", False)),
        sharding=(a.sharding if isinstance(getattr(a, "sharding", None),
                                           NamedSharding) else None))


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
_MEMORY_CACHE: dict = {}  # full key string -> (aot_executable, out_spec, written)


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
    """(aot, out_spec, written) deserialized from ``path``, or None. ANY failure —
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
        return aot, entry["out_spec"], tuple(entry["written"])
    except Exception:
        return None


def _store_disk_entry(path: str, full_key: str, aot, out_spec,
                      written) -> None:
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
                             "out_spec": out_spec, "written": written})
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
class _Plan:
    """How one signature is launched: everything about a call that follows
    from the signature alone, decided when its program was built."""

    __slots__ = ("aot", "out_spec", "given", "written", "read", "donates",
                 "abstract", "trace", "_jitted", "_refs", "_ptrs", "_ptr_set")

    def __init__(self, given, donates):
        # the executable `_build` compiled (or the persistent cache held):
        # calls run it; None where nothing compiled (``lower()``) or a call
        # degraded to ``jitted``
        self.aot = None
        self.out_spec = None
        # flat argument leaves this program consumes (donate_argnums)
        self.given = given
        # slot indices the trace saw written (donated and returned) and only
        # read (plain inputs, no outputs); None until a trace or a
        # persistent-cache entry says
        self.written = self.read = None
        self.donates = donates
        self.abstract = None    # ShapeDtypeStruct tree of `operands`
        # the jax.jit of this signature, or (a plan that the persistent
        # cache filled) the call that traces it when someone asks
        self._jitted = self.trace = None
        # buffer pointers of the operands the program does not consume, as
        # last looked up (see `_kept_pointers`)
        self._refs, self._ptrs, self._ptr_set = [], [], frozenset()

    @property
    def jitted(self):
        """The ``jax.jit`` of this signature: what ``lower``/``cost_analysis``
        read and what a call runs where there is no executable."""
        if self._jitted is None:
            self._jitted = self.trace()
        return self._jitted

    def set_written(self, written, n_state) -> None:
        if self.written is not None and self.written != tuple(written):
            raise RuntimeError(
                "the persistent compile cache holds this signature with "
                f"slots {self.written} written; the trace wrote {written}")
        self.written = tuple(written)
        mine = set(self.written)
        self.read = tuple(i for i in range(n_state) if i not in mine)

    def operands(self, state, lr_vals, arrays):
        """This call's operands, as ``jitted`` and the executable take them:
        written state, read-only state, learning rates, the argument leaves
        with None where a leaf is given up, and the given-up leaves. Written
        state and given-up leaves are donated; XLA rejects a donated buffer
        that is also another argument (two accumulators both produced by one
        CSE'd zeros_like, a Parameter also passed as a data input, one array
        passed both given-up and kept), so any such duplicate rides as a
        copy and every donated buffer is unique."""
        written = [state[i] for i in self.written]
        read = [state[i] for i in self.read]
        kept = list(arrays)
        given = [kept[i] for i in self.given]
        for i in self.given:
            kept[i] = None
        if self.donates and (written or given):
            others = self._kept_pointers(
                read + [a for a in kept if a is not None])
            mine = set()

            def unique(v):
                ptr = _buffer_ptr(v)
                if ptr in others or ptr in mine:
                    return jnp.array(v, copy=True)
                mine.add(ptr)
                return v

            written = [unique(v) for v in written]
            given = [unique(v) for v in given]
        return written, read, lr_vals, kept, given

    def _kept_pointers(self, vals):
        """Buffer pointers of the operands the program only reads. An array
        object keeps its buffer for life, so one that the last call of this
        plan already looked up (held weakly: a plan must not keep a replaced
        parameter alive) is not asked again — a serving step asks its 48
        donated pool arrays and its fresh grids, not its 293 parameters."""
        refs, ptrs = self._refs, self._ptrs
        if len(refs) != len(vals):
            refs[:], ptrs[:] = [_dead_ref] * len(vals), [None] * len(vals)
        changed = False
        for j, v in enumerate(vals):
            if refs[j]() is not v:
                try:
                    refs[j] = weakref.ref(v)
                except TypeError:
                    refs[j] = _dead_ref
                ptrs[j] = _buffer_ptr(v)
                changed = True
        if changed:
            self._ptr_set = frozenset(ptrs)
        return self._ptr_set


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
        self._cache: dict = {}  # signature key -> _Plan
        # cache key -> ShapeDtypeStruct tree of the plan's operands, in
        # order of recency (the last is the most recent call's)
        self._abstract_args: dict = {}
        self._latest_plan: Optional[_Plan] = None
        self._calls = None  # (reused a plan, built one) counter children
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

    def _plan(self, key=None) -> Optional[_Plan]:
        """The plan of a called signature (``key=None``: the most recent
        call's)."""
        return self._cache.get(self._latest_key() if key is None else key)

    def _lowered(self, key=None):
        """``jax.stages.Lowered`` of a compiled signature from its recorded
        abstract operands (``key=None``: the most recent); None before
        any call compiled."""
        plan = self._plan(key)
        if plan is None or plan.abstract is None:
            return None
        return plan.jitted.lower(*plan.abstract)

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
        plan = self._plan(key)
        if compiled and plan is not None and plan.aot is not None:
            return plan.aot.as_text()  # the executable the calls run
        lowered = self._lowered(key)
        if lowered is None:
            return None
        return lowered.compile().as_text() if compiled else lowered.as_text()

    def aliased_bytes(self, key=None) -> Optional[int]:
        """Bytes of argument buffers that a compiled signature's executable
        writes its outputs into (``key=None``: the most recent) — what the
        ``paddle_tpu_jit_aliased_bytes`` gauge read when it was built. None
        before any call compiled, or where the call runs ``jitted``."""
        plan = self._plan(key)
        if plan is None or plan.aot is None:
            return None
        return _aliased_bytes(plan.aot)

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
        plan, operands, _ = self._prepare(args, kwargs, compile=False)
        return plan.jitted.lower(*operands)

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

    def _persistent_key(self, key, state, lr_vals, given) -> str:
        """The FULL persistent-cache key, as a stable string: everything
        that shapes the executable's bytes or its calling convention.
        Signature key (shapes/dtypes/weak_type of args, training flags),
        state/lr avals, the function's code fingerprint and caller-
        supplied extra, the donation policy, the calling convention (an
        entry stored when ALL state was donated and returned is another
        executable: a miss) and the jax + device fingerprint (a different
        jaxlib or device kind must miss)."""
        dev = jax.devices()[0]
        state_avals = tuple((tuple(v.shape), str(v.dtype),
                             bool(getattr(v, "weak_type", False)))
                            for v in state)
        return repr((
            self.__name__, _code_fingerprint(self._fn),
            self._cache_key_extra, key, state_avals, len(lr_vals),
            _donation_off(), given, "written-state-donated",
            jax.__version__, jax.lib.__version__,
            dev.platform, dev.device_kind,
        ))

    def _given_up(self, args) -> tuple:
        """Flat indices, among a call's traced leaves, of those under the
        positional arguments named in ``donate_argnums``; nothing under
        ``PADDLE_TPU_NO_DONATE=1``."""
        if not self._donate_argnums or _donation_off():
            return ()
        ends = list(itertools.accumulate(map(_n_traced_leaves, args)))
        return tuple(i for n in self._donate_argnums
                     for i in range(ends[n - 1] if n else 0, ends[n]))

    def _trace(self, plan, treedef, meta, state, lr_vals, arrays):
        """Trace the function once for one signature, learn from the trace
        which state slots it wrote, and return the ``jax.jit`` that takes
        the written slots donated and the rest read-only. ``state``,
        ``lr_vals`` and ``arrays`` are arrays or their abstract values."""
        slots, opts, fn = self._slots, self._opts, self._fn
        given, seen = plan.given, {}

        def all_state_in(state_vals, lr_vals, arg_arrays):
            for slot, v in zip(slots, state_vals):
                slot.set(v)
            for opt, lr in zip(opts, lr_vals):
                opt._lr_override = lr
            try:
                args, kwargs = _rebuild_args(treedef, meta, arg_arrays)
                out = fn(*args, **kwargs)
            finally:
                for opt in opts:
                    opt._lr_override = None
            out_arrays, seen["out_spec"] = _flatten_out(out)
            # a slot that still holds the very tracer it was given was not
            # written: read-only for this program
            seen["written"] = [i for i, (slot, v)
                               in enumerate(zip(slots, state_vals))
                               if slot.get() is not v]
            seen["n_out"] = len(out_arrays)
            return out_arrays + [slots[i].get() for i in seen["written"]]

        saved = [slot.get() for slot in slots]
        try:
            closed = _with_room(
                lambda: jax.make_jaxpr(all_state_in)(state, lr_vals, arrays))
        finally:
            # no tracer outlives the trace: the state is what it was
            for slot, v in zip(slots, saved):
                slot.set(v)
                slot.sanitize()
        plan.set_written(seen["written"], len(slots))
        plan.out_spec = seen["out_spec"]
        written, read, n_out = plan.written, plan.read, seen["n_out"]

        def _functional(written_vals, read_vals, lr_vals, arg_arrays,
                        given_arrays):
            state_vals = [None] * len(slots)
            for i, v in zip(written + read, written_vals + read_vals):
                state_vals[i] = v
            arg_arrays = list(arg_arrays)
            for i, v in zip(given, given_arrays):
                arg_arrays[i] = v
            outs = jax.core.eval_jaxpr(closed.jaxpr, closed.consts,
                                       *state_vals, *lr_vals, *arg_arrays)
            return outs[:n_out], outs[n_out:]

        # Written state is donated so XLA reuses its buffers for the updated
        # state (in-place optimizer semantics, reference: inplace op pass),
        # and so are the argument leaves the constructor's caller gave up
        # (``given_arrays``; empty unless declared). A donation-induced
        # wrongness would be TPU-only in effect — PADDLE_TPU_NO_DONATE=1
        # disables both as a bisect axis.
        return jax.jit(_functional,
                       donate_argnums=(0, 4) if plan.donates else ())

    def _build(self, key, treedef, meta, given, state, lr_vals, arrays,
               compile=True):
        """The plan of a signature this function has not run yet, and the
        first call's operands."""
        # every signature-cache miss materializes ONE program, counted
        # exactly once with its source: "fresh" paid a trace + XLA
        # compile, "disk" deserialized a persisted executable (warm
        # restart), "memory" reused another StaticFunction's build in
        # this process (e.g. a second engine replica). The per-fn SUM
        # across sources keeps the old one-inc-per-build meaning — the
        # "decode compiles exactly once" invariant stays a monitorable
        # metric (paddle_tpu_jit_compiles_total{fn,source}), and a
        # recompile storm shows up on /metrics before it shows up as a
        # latency cliff. Without ``compile`` (``lower()``) nothing
        # compiles here and a call runs ``jitted``.
        #
        # On the trace ring the build is one ``step.build`` span (counts:
        # ``fresh``, the source above as 1 or 0) with its parts as
        # children: ``step.load`` (the disk entry), ``step.trace``,
        # ``step.lower``, ``step.compile``; a "memory" build is the span
        # alone.
        from ..metrics import get_registry

        tracer, name = get_tracer(), self.__name__
        build = tracer.begin("step.build", name)
        part = None
        source = "fresh"
        try:
            plan = _Plan(given, donates=not _donation_off())
            registry = get_registry()
            cache_dir = self._resolve_cache_dir() if compile else None
            full_key = path = ent = None
            if cache_dir is not None:
                full_key = self._persistent_key(key, state, lr_vals, given)
                path = os.path.join(
                    cache_dir,
                    f"{name}-"
                    f"{hashlib.sha256(full_key.encode()).hexdigest()[:32]}"
                    ".jitcache")
                ent = _MEMORY_CACHE.get(full_key)
                if ent is not None:
                    source = "memory"
                else:
                    part = tracer.begin("step.load", name)
                    ent = _load_disk_entry(path, full_key)
                    if ent is not None:
                        _MEMORY_CACHE[full_key] = ent
                        source = "disk"
            if ent is not None:
                tracer.end(part)
                part = None
                plan.aot, plan.out_spec, written = ent
                plan.set_written(written, len(state))
                abstract = jax.tree_util.tree_map(_abstract,
                                                  (state, lr_vals, arrays))
                plan.trace = lambda: self._trace(plan, treedef, meta,
                                                 *abstract)
            else:
                part = (tracer.begin("step.trace", name) if part is None
                        else tracer.next("step.trace", part))
                plan._jitted = self._trace(plan, treedef, meta, state,
                                           lr_vals, arrays)
            operands = plan.operands(state, lr_vals, arrays)
            plan.abstract = jax.tree_util.tree_map(_abstract, operands)
            if compile and ent is None:
                part = tracer.next("step.lower", part)
                try:
                    lowered = _with_room(
                        lambda: plan.jitted.lower(*operands))
                    part = tracer.next("step.compile", part)
                    plan.aot = _with_room(lowered.compile)
                except Exception:
                    # an unlowerable corner falls back to the plain
                    # jax.jit path — correctness never depends on the
                    # ahead-of-time build
                    plan.aot = None
                if plan.aot is not None and full_key is not None:
                    _MEMORY_CACHE[full_key] = (plan.aot, plan.out_spec,
                                               plan.written)
                    _store_disk_entry(path, full_key, plan.aot,
                                      plan.out_spec, plan.written)
            tracer.end(part)
            part = None
            if plan.aot is not None:
                # what donation bought, as the executable has it: bytes
                # of arguments whose buffers the outputs are written
                # into. A step that should update a pool in place reads
                # the pool's bytes here; 0 means every donated buffer
                # was copied.
                registry.gauge(
                    "paddle_tpu_jit_aliased_bytes",
                    "Bytes of argument buffers that the StaticFunction "
                    "program built last for this fn writes its outputs "
                    "into (XLA memory analysis): written state and "
                    "donate_argnums leaves updated in place",
                    labels=("fn",),
                ).labels(fn=name).set(float(_aliased_bytes(plan.aot)))
            registry.counter(
                "paddle_tpu_jit_compiles_total",
                "XLA programs materialized into a StaticFunction "
                "signature cache, by source: \"fresh\" paid an XLA "
                "compile, \"disk\" loaded the persistent compile cache, "
                "\"memory\" reused a process-wide build",
                labels=("fn", "source"),
            ).labels(fn=name, source=source).inc()
        finally:
            tracer.end(part)
            tracer.end(build, (int(source == "fresh"),))
        return plan, operands

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
        """One call's plan (built on a miss; compiled too unless
        ``compile=False``), its operands, and whether this call built the
        plan."""
        arrays, meta, sig, treedef = _flatten_args(args, kwargs)
        key = (sig, tuple(l.training for l in self._layers))
        plan = self._cache.get(key)
        state = [s.get() for s in self._slots]
        lr_vals = [jnp.asarray(o.get_lr(), jnp.float32) for o in self._opts]
        built = plan is None
        if built:
            plan, operands = self._build(
                key, treedef, meta, self._given_up(args), state, lr_vals,
                arrays, compile)
            self._cache[key] = plan
            self._abstract_args[key] = plan.abstract
        else:
            operands = plan.operands(state, lr_vals, arrays)
            if plan is not self._latest_plan:
                # move-to-end: dict order = recency
                self._abstract_args[key] = self._abstract_args.pop(key)
        self._latest_plan = plan
        return plan, operands, built

    def _count_call(self, built: bool) -> None:
        if self._calls is None:
            from ..metrics import get_registry

            calls = get_registry().counter(
                "paddle_tpu_jit_calls_total",
                "Calls of a StaticFunction's compiled program, by path: "
                "\"plan\" launched from the signature's launch plan, "
                "\"build\" had to build it first (trace, compile or a "
                "persistent-cache load)", labels=("fn", "path"))
            self._calls = tuple(calls.labels(fn=self.__name__, path=p)
                                for p in ("plan", "build"))
        self._calls[built].inc()

    def __call__(self, *args, **kwargs):
        if not self._warmed_up:
            if not self._do_warmup:
                self._setup_no_warmup()
            else:
                return self._warmup(args, kwargs)
        # on the trace ring: step.operands -> step.launch -> step.outputs,
        # under whatever span is open (the engine's step.dispatch)
        tracer = get_tracer()
        sp = tracer.begin("step.operands", self.__name__)
        try:
            plan, operands, built = self._prepare(args, kwargs)
            self._count_call(built)
            sp = tracer.next("step.launch", sp)
            if plan.aot is not None:
                try:
                    out_arrays, new_written = plan.aot(*operands)
                except Exception:
                    # a calling-convention mismatch (aval drift the key
                    # missed, a sharding the executable was not compiled
                    # for) degrades to the jax.jit path for good — the
                    # signature check fails BEFORE execution, so the
                    # donated buffers are still intact for the retry
                    plan.aot = None
                    out_arrays, new_written = plan.jitted(*operands)
            else:
                out_arrays, new_written = plan.jitted(*operands)
            sp = tracer.next("step.outputs", sp)
            slots = self._slots
            for i, v in zip(plan.written, new_written):
                slots[i].set(v)
            return _rebuild_out(plan.out_spec, out_arrays)
        finally:
            tracer.end(sp)
