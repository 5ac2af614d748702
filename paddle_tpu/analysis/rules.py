"""The tpulint rule set — each rule guards one runtime invariant.

| rule | invariant it guards | introduced by |
|---|---|---|
| TPL001 | no host sync inside a compiled scope | PR 1/9 one-fetch discipline |
| TPL002 | decode/prefill compile once (no retrace hazards) | PR 1 |
| TPL003 | metric catalog == docs/OBSERVABILITY.md, both ways | PR 2 |
| TPL004 | fault-point catalog == docs/RESILIENCE.md, both ways | PR 3 |
| TPL005 | sampling is a pure function of (prompt, seed) | PR 7 |
| TPL006 | shared registry/router state mutates under its lock | PR 2/5 |
| TPL007 | the lock-acquisition graph is acyclic (no deadlock) | PR 13 |
| TPL008 | check-then-act stays inside ONE critical section | PR 13 |
| TPL009 | no blocking/unbounded work while a lock is held | PR 13 |
| TPL010 | trace-event catalog == docs/OBSERVABILITY.md, both ways | PR 17 |

Every rule is syntactic (per-module AST, no import resolution) and errs
toward silence: a miss is caught by the runtime drills these rules
summarize; a false positive trains people to sprinkle suppressions.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .catalog import (FaultSite, MetricRegistration, TraceEmit,
                      collect_fault_sites, collect_label_uses,
                      collect_metric_registrations, collect_trace_emits,
                      parse_event_doc, parse_fault_doc, parse_metric_doc,
                      registration_of)
from .core import Finding, LintConfig, ModuleInfo, Project
from .locks import LockWorld, module_lock_decls
from .scopes import CompiledScopes, Taint, dotted_name

__all__ = ["FILE_RULES", "PROJECT_RULES", "RULE_IDS"]


def _jax_random_aliases(tree: ast.Module) -> Set[str]:
    """Names the module bound to jax.random (`from jax import random`,
    `import jax.random as jrandom`): their draws are key-threaded and
    pure — TPL005's stdlib branch and TPL002's varying-scalar call-site
    scan must both leave them alone."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for alias in node.names:
                if alias.name == "random":
                    out.add(alias.asname or "random")
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "jax.random" and alias.asname:
                    out.add(alias.asname)
    return out


def _time_seed_of(call: ast.Call) -> Optional[str]:
    """The dotted name of a wall-clock/entropy source called anywhere
    inside ``call``'s arguments, or None."""
    for sub in ast.walk(call):
        if isinstance(sub, ast.Call) and sub is not call:
            src = dotted_name(sub.func) or ""
            if src in _TIME_SOURCES:
                return src
    return None


def _in_scope(relpath: str, scope: str) -> bool:
    """Path-boundary-aware prefix test: scope "paddle_tpu/serving"
    covers the dir and its contents but NOT a sibling like
    paddle_tpu/serving_utils.py. Empty scope covers everything
    (fixtures widen to ("",))."""
    if not scope:
        return True
    scope = scope.rstrip("/")
    return relpath == scope or relpath.startswith(scope + "/")


def _scopes(module: ModuleInfo) -> CompiledScopes:
    cached = getattr(module, "_compiled_scopes", None)
    if cached is None:
        cached = CompiledScopes(module.tree)
        module._compiled_scopes = cached
    return cached


def _taint(module: ModuleInfo, fn) -> Taint:
    """One Taint pass per (module, compiled fn) — TPL001 and TPL002
    both consume it; building it twice would double the forward pass
    and let the two rules drift apart on a future taint fix."""
    cache = getattr(module, "_taint_cache", None)
    if cache is None:
        cache = {}
        module._taint_cache = cache
    taint = cache.get(fn)
    if taint is None:
        taint = cache[fn] = Taint(fn)
    return taint


def _compiled_roots(scopes: CompiledScopes):
    """Compiled fns not lexically covered by a compiled ancestor's walk
    — by POSITION, not by mark reason: a decorated def nested inside a
    compiled fn keeps its 'decorated' reason but must still not be
    walked twice (one defect, one finding)."""
    nested: Set[ast.AST] = set()
    for fn in scopes.compiled:
        for sub in ast.walk(fn):
            if sub is not fn and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested.add(sub)
    for fn, reason in scopes.compiled.items():
        if fn not in nested:
            yield fn, reason


_SYNC_METHODS = {"item", "numpy", "tolist", "block_until_ready"}
_CAST_BUILTINS = {"float", "int", "bool", "complex"}
_NP_MATERIALIZERS = {"np.asarray", "np.array", "numpy.asarray",
                     "numpy.array"}


class TPL001HostSyncInCompiled:
    """``.item()`` / ``float()`` / ``np.asarray`` / ``device_get`` on a
    traced value inside a compiled scope. Each is a device→host fetch:
    under trace it either raises (ConcretizationError) or — worse —
    silently bakes one concrete value into the compiled program. The
    compiled step's contract is ONE fetch, owned by the host caller."""

    id = "TPL001"

    def check(self, module: ModuleInfo, config: LintConfig) -> List[Finding]:
        out: List[Finding] = []
        scopes = _scopes(module)
        for fn, _reason in _compiled_roots(scopes):
            taint = _taint(module, fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if (isinstance(func, ast.Attribute)
                        and func.attr in _SYNC_METHODS
                        and taint.is_traced(func.value)):
                    out.append(Finding(
                        self.id, module.relpath, node.lineno,
                        node.col_offset,
                        f"host sync `.{func.attr}()` on a traced value "
                        f"inside compiled fn `{fn.name}`"))
                elif (isinstance(func, ast.Name)
                        and func.id in _CAST_BUILTINS and node.args
                        and taint.is_traced(node.args[0])):
                    out.append(Finding(
                        self.id, module.relpath, node.lineno,
                        node.col_offset,
                        f"`{func.id}()` forces a traced value to host "
                        f"inside compiled fn `{fn.name}`"))
                else:
                    name = dotted_name(func) or ""
                    if (name in _NP_MATERIALIZERS and node.args
                            and taint.is_traced(node.args[0])):
                        out.append(Finding(
                            self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"`{name}()` materializes a traced value on "
                            f"host inside compiled fn `{fn.name}`"))
                    elif name.split(".")[-1] == "device_get":
                        out.append(Finding(
                            self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"`{name}()` inside compiled fn `{fn.name}` "
                            f"— device fetch has no place under trace"))
        return out


_TIME_CALLS = {"time.time", "time.perf_counter", "time.time_ns",
               "time.monotonic", "datetime.now", "datetime.datetime.now"}


def _has_varying_host_scalar(arg: ast.AST,
                             jax_random_names: Set[str] = frozenset()
                             ) -> Optional[str]:
    for sub in ast.walk(arg):
        if isinstance(sub, ast.Call):
            name = dotted_name(sub.func) or ""
            if name in _TIME_CALLS:
                return f"`{name}()`"
            if (name.startswith(("random.", "np.random.",
                                 "numpy.random."))
                    and name.split(".", 1)[0] not in jax_random_names):
                return f"`{name}()`"
        if isinstance(sub, ast.JoinedStr) and _fstring_varies(sub):
            return "an f-string"
    return None


def _fstring_varies(node: ast.JoinedStr) -> bool:
    """True when the f-string can take a different value between calls.
    Literal text and ALL_CAPS module constants (`f"v{VERSION}"`) format
    to the same string every call — one signature, one compile — and
    must not fire."""
    for fv in node.values:
        if not isinstance(fv, ast.FormattedValue):
            continue
        expr = fv.value
        if isinstance(expr, ast.Constant):
            continue
        if isinstance(expr, ast.Name) and expr.id.isupper():
            continue
        return True
    return False


class TPL002RecompileHazard:
    """Inside a compiled scope: Python control flow on traced values
    (retrace per branch — or a ConcretizationError at first trace) and
    string conversion of traced values (f-string / ``str()`` — host
    sync dressed as formatting). At call sites of compiled callables:
    time/random-derived scalars passed as arguments — every distinct
    value is a new signature, i.e. a recompile per step (the 138 s
    compile of gpt13 in BENCH_NOTES_r05.json makes that a production
    outage, not a slowdown)."""

    id = "TPL002"

    def check(self, module: ModuleInfo, config: LintConfig) -> List[Finding]:
        out: List[Finding] = []
        scopes = _scopes(module)
        for fn, _reason in _compiled_roots(scopes):
            taint = _taint(module, fn)
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.While)):
                    if taint.is_traced(node.test):
                        kw = "while" if isinstance(node, ast.While) else "if"
                        out.append(Finding(
                            self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"Python `{kw}` on a traced value inside "
                            f"compiled fn `{fn.name}` — use jnp.where/"
                            f"lax.cond (retrace or ConcretizationError)"))
                elif isinstance(node, ast.IfExp):
                    if taint.is_traced(node.test):
                        out.append(Finding(
                            self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"conditional expression on a traced value "
                            f"inside compiled fn `{fn.name}` — use "
                            f"jnp.where"))
                elif isinstance(node, ast.Assert):
                    if taint.is_traced(node.test):
                        out.append(Finding(
                            self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"assert on a traced value inside compiled "
                            f"fn `{fn.name}` — use checkify or a host-"
                            f"side flag output"))
                elif isinstance(node, ast.JoinedStr):
                    if taint.is_traced(node):
                        out.append(Finding(
                            self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"f-string over a traced value inside "
                            f"compiled fn `{fn.name}` — host sync "
                            f"dressed as formatting"))
                elif isinstance(node, ast.Call):
                    func = node.func
                    if (isinstance(func, ast.Name)
                            and func.id in ("str", "repr", "format")
                            and node.args
                            and taint.is_traced(node.args[0])):
                        out.append(Finding(
                            self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"`{func.id}()` of a traced value inside "
                            f"compiled fn `{fn.name}`"))
                elif isinstance(node, ast.For):
                    it = node.iter
                    if (isinstance(it, ast.Call)
                            and isinstance(it.func, ast.Name)
                            and it.func.id == "range"
                            and any(taint.is_traced(a) for a in it.args)):
                        out.append(Finding(
                            self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"`range()` over a traced value inside "
                            f"compiled fn `{fn.name}` — use lax.scan/"
                            f"fori_loop"))
        # call-site half: varying host scalars into compiled callables
        jax_random_names = _jax_random_aliases(module.tree)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            if callee is None or callee not in scopes.compiled_bindings:
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                varying = _has_varying_host_scalar(arg, jax_random_names)
                if varying is not None:
                    out.append(Finding(
                        self.id, module.relpath, node.lineno,
                        node.col_offset,
                        f"{varying} passed into compiled callable "
                        f"`{callee}` — every distinct value compiles a "
                        f"new program"))
        return out


class TPL003MetricCatalogParity:
    """Every registered metric family is documented in
    docs/OBSERVABILITY.md and every documented family is registered —
    plus label-set consistency: two registrations of one name must
    declare the same labels, and every ``.labels(...)`` call must use
    the declared set. The hand-synced table stops being hand-synced."""

    id = "TPL003"

    def check_project(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        config = project.config
        regs: List[MetricRegistration] = []
        for mod in project.modules:
            regs.extend(collect_metric_registrations(mod.tree, mod.relpath))

        # -- same-name registrations must agree on labels ------------------
        by_name: Dict[str, List[MetricRegistration]] = {}
        for r in regs:
            if r.name is not None:
                by_name.setdefault(r.name, []).append(r)
        for name, rlist in sorted(by_name.items()):
            label_sets = {r.labels for r in rlist if r.labels is not None}
            if len(label_sets) > 1:
                canonical = sorted(label_sets)[0]
                for r in rlist:
                    if r.labels is not None and r.labels != canonical:
                        out.append(Finding(
                            self.id, r.relpath, r.line, 0,
                            f"metric `{name}` registered with conflicting "
                            f"label sets {sorted(map(list, label_sets))} — "
                            f"one family, one label set"))

        # -- docs parity, both directions ----------------------------------
        doc_path = config.observability_doc
        doc_rel = os.path.relpath(doc_path, config.root).replace(os.sep, "/")
        if not os.path.isfile(doc_path):
            out.append(Finding(self.id, doc_rel, 1, 0,
                               "observability catalog doc not found"))
            return out
        documented = parse_metric_doc(doc_path)
        registered_names = set(by_name)
        for name, rlist in sorted(by_name.items()):
            first = min(rlist, key=lambda r: (r.relpath, r.line))
            if not _in_scope(first.relpath, config.metric_doc_scope):
                continue
            if name not in documented:
                out.append(Finding(
                    self.id, first.relpath, first.line, 0,
                    f"metric `{name}` is registered but not documented "
                    f"in {doc_rel}"))
        if project.full_scope:
            # docs→code only when the run covers the registration
            # universe — on a targeted lint the sites simply aren't in
            # the subset
            for name, (lineno, _labels) in sorted(documented.items()):
                if name not in registered_names:
                    out.append(Finding(
                        self.id, doc_rel, lineno, 0,
                        f"documented metric `{name}` has no registration "
                        f"site in the linted code"))

        # -- .labels() call sites vs declared label sets -------------------
        for mod in project.modules:
            out.extend(self._check_label_uses(mod))
        return out

    def _check_label_uses(self, mod: ModuleInfo) -> List[Finding]:
        out: List[Finding] = []
        # receiver name -> [(line, metric name, declared labels or
        # None=unknown)] sorted by line: a rebound receiver validates
        # each .labels() call against the binding LIVE at that line,
        # not whichever assignment ast.walk happened to visit last
        bindings: Dict[str, List[Tuple[int, str,
                                       Optional[Tuple[str, ...]]]]] = {}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            target = dotted_name(node.targets[0])
            if target is None:
                continue
            value = node.value
            reg = (registration_of(value, mod.relpath)
                   if isinstance(value, ast.Call) else None)
            if reg is not None and reg.name is not None:
                bindings.setdefault(target, []).append(
                    (node.lineno, reg.name, reg.labels))
            elif (isinstance(value, ast.Call)
                    and isinstance(value.func, ast.Attribute)
                    and value.func.attr == "labels"
                    and isinstance(value.func.value, ast.Call)):
                # var = reg.histogram(...).labels(...): validate the
                # chained labels() below; the var binds a CHILD, which
                # takes no further .labels() calls
                pass
        for blist in bindings.values():
            blist.sort()
        for call, recv in collect_label_uses(mod.tree):
            declared: Optional[Tuple[str, ...]] = None
            name = None
            if recv is not None:
                for line, bname, blabels in bindings.get(recv, ()):
                    if line > call.lineno:
                        break
                    name, declared = bname, blabels
            elif isinstance(call.func.value, ast.Call):
                # chained reg.counter(...).labels(...) one-liner
                reg = registration_of(call.func.value, mod.relpath)
                if reg is not None:
                    name, declared = reg.name, reg.labels
            if declared is None:
                continue                    # unknown receiver or labels
            has_star = any(kw.arg is None for kw in call.keywords)
            kw_names = {kw.arg for kw in call.keywords if kw.arg}
            extra = kw_names - set(declared)
            if extra:
                out.append(Finding(
                    self.id, mod.relpath, call.lineno, 0,
                    f"labels({', '.join(sorted(extra))}=...) not in the "
                    f"declared label set {list(declared)} of metric "
                    f"`{name or '?'}`"))
            elif (not has_star and not call.args
                    and kw_names != set(declared)):
                missing = sorted(set(declared) - kw_names)
                out.append(Finding(
                    self.id, mod.relpath, call.lineno, 0,
                    f"labels(...) missing declared label(s) "
                    f"{missing} of metric `{name or '?'}`"))
            elif call.args and not call.keywords and len(call.args) != len(
                    declared):
                out.append(Finding(
                    self.id, mod.relpath, call.lineno, 0,
                    f"labels(...) takes {len(call.args)} positional "
                    f"value(s); metric `{name or '?'}` declares "
                    f"{len(declared)}"))
        return out


class TPL004FaultPointParity:
    """Every fault point named in code (``faults.point`` /
    ``declare_point`` / ``inject``) appears in the docs/RESILIENCE.md
    catalog table, and every cataloged point exists in code. A drill
    that arms a point nobody fires — or a point no drill documents —
    is resilience theater."""

    id = "TPL004"

    def check_project(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        config = project.config
        sites: List[FaultSite] = []
        for mod in project.modules:
            sites.extend(collect_fault_sites(mod.tree, mod.relpath))
        doc_path = config.resilience_doc
        doc_rel = os.path.relpath(doc_path, config.root).replace(os.sep, "/")
        if not os.path.isfile(doc_path):
            out.append(Finding(self.id, doc_rel, 1, 0,
                               "resilience catalog doc not found"))
            return out
        documented = parse_fault_doc(doc_path)
        by_name: Dict[str, List[FaultSite]] = {}
        for s in sites:
            by_name.setdefault(s.name, []).append(s)
        for name, slist in sorted(by_name.items()):
            if name not in documented:
                first = min(slist, key=lambda s: (s.relpath, s.line))
                out.append(Finding(
                    self.id, first.relpath, first.line, 0,
                    f"fault point `{name}` is not cataloged in "
                    f"{doc_rel}"))
        if project.full_scope:
            # docs→code direction: full-scope runs only (see TPL003)
            for name, lineno in sorted(documented.items()):
                if name not in by_name:
                    out.append(Finding(
                        self.id, doc_rel, lineno, 0,
                        f"cataloged fault point `{name}` has no "
                        f"point/declare_point/inject site in the linted "
                        f"code"))
        return out


_UNSEEDED_RANDOM = {
    "random", "randint", "uniform", "choice", "choices", "shuffle",
    "sample", "randrange", "gauss", "normalvariate", "betavariate",
    "expovariate", "triangular", "getrandbits", "randbytes",
}
_NP_SEEDED_OK = {"Generator", "SeedSequence", "BitGenerator"}
# constructors that are fine WITH a seed argument and entropy-seeded
# (nondeterministic) without one — `Generator(PCG64(seed))` is the very
# idiom the rule's message recommends
_NP_SEEDED_CTORS = {"default_rng", "RandomState", "PCG64", "PCG64DXSM",
                    "Philox", "MT19937", "SFC64"}
_TIME_SOURCES = {"time.time", "time.time_ns", "time.perf_counter",
                 "time.monotonic", "datetime.now", "datetime.datetime.now",
                 "os.urandom", "uuid.uuid4"}


class TPL005UnseededRandomness:
    """Unseeded randomness under serving/faults/checkpoint. PR 7 made a
    request's token stream a pure function of (prompt, seed) — that
    contract (and every bit-identical chaos drill riding it) dies the
    day someone reaches for the global RNG or a wall-clock PRNGKey."""

    id = "TPL005"

    def check(self, module: ModuleInfo, config: LintConfig) -> List[Finding]:
        if not any(_in_scope(module.relpath, scope)
                   for scope in config.tpl005_scopes):
            return []
        out: List[Finding] = []
        jax_random_names = _jax_random_aliases(module.tree)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            parts = name.split(".")
            # PRNGKey first: under `from jax import random` its dotted
            # name starts with "random." and would fall into (and out
            # of) the stdlib-random branch below without ever reaching
            # the time-source scan
            if parts[-1] == "PRNGKey" or name.endswith("random.key"):
                src = _time_seed_of(node)
                if src is not None:
                    out.append(Finding(
                        self.id, module.relpath, node.lineno,
                        node.col_offset,
                        f"time-derived PRNGKey (`{src}()`) — "
                        f"sampling must be a pure function of "
                        f"(prompt, seed)"))
            elif name.startswith("random.") and "random" not in \
                    jax_random_names:
                fn = parts[-1]
                if fn in _UNSEEDED_RANDOM:
                    out.append(Finding(
                        self.id, module.relpath, node.lineno,
                        node.col_offset,
                        f"`{name}()` uses the process-global RNG — "
                        f"derive from a seeded random.Random or an "
                        f"injected generator"))
                elif fn == "Random":
                    out.extend(self._seed_findings(
                        module, node, "random.Random"))
            elif (name.startswith("np.random.")
                    or name.startswith("numpy.random.")):
                fn = parts[-1]
                if fn in _NP_SEEDED_CTORS:
                    out.extend(self._seed_findings(module, node, fn))
                elif fn not in _NP_SEEDED_OK:
                    out.append(Finding(
                        self.id, module.relpath, node.lineno,
                        node.col_offset,
                        f"`{name}()` uses numpy's global RNG — use an "
                        f"injected np.random.Generator"))
        return out

    def _seed_findings(self, module: ModuleInfo, node: ast.Call,
                       label: str) -> List[Finding]:
        """A seedable ctor must have a seed, and the seed must not be
        wall-clock: `default_rng(time.time_ns())` is the unseeded
        defect wearing an argument."""
        if not node.args and not node.keywords:
            return [Finding(self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"`{label}()` without a seed — pass one")]
        src = _time_seed_of(node)
        if src is not None:
            return [Finding(self.id, module.relpath, node.lineno,
                            node.col_offset,
                            f"`{label}()` seeded from `{src}()` — "
                            f"time-seeded is unseeded; sampling must "
                            f"be a pure function of (prompt, seed)")]
        return []


# attr (as written at the mutation site) -> required lock expr, per file.
# The table states the LOCKING CONTRACT each file already documents;
# new shared state opts in with a trailing
# ``# tpulint: guard=self._lock`` on its initialization line.
_LOCK_TABLE: Dict[str, Tuple[Tuple[str, str], ...]] = {
    "paddle_tpu/metrics/registry.py": (
        ("self._metrics", "self._lock"),
        ("self._children", "self._lock"),
    ),
    "paddle_tpu/faults/injection.py": (
        ("_active", "_lock"),
        ("_catalog", "_lock"),
    ),
    "paddle_tpu/checkpoint/manager.py": (
        ("_LIVE_TMP", "_LIVE_TMP_LOCK"),
    ),
    "paddle_tpu/serving/router.py": (
        ("self._models", "self._lock"),
        ("self._handles", "self._lock"),
        ("self._rr", "self._lock"),
    ),
    "paddle_tpu/metrics/server.py": (
        ("self._cb_engine_probe", "self._probe_lock"),
    ),
    "paddle_tpu/faults/watchdog.py": (
        ("self._in_step_since", "self._lock"),
        ("self._tripped", "self._lock"),
        ("self._healthy_streak", "self._lock"),
        ("self._trips", "self._lock"),
    ),
    "paddle_tpu/serving/api.py": (
        ("self._rr_idx", "self._rr_lock"),
    ),
    "paddle_tpu/distributed/checkpoint/__init__.py": (
        ("_pending", "_pending_lock"),
    ),
}

_MUTATORS = {"append", "add", "remove", "discard", "clear", "pop",
             "popitem", "update", "setdefault", "extend", "insert"}
_GUARD_RE = re.compile(r"#\s*tpulint:\s*guard=(\S+)")
_ATOMIC_OK_RE = re.compile(r"#\s*tpulint:\s*atomic-ok")


def _guard_map(module: ModuleInfo) -> Dict[str, str]:
    """attr -> lock expr for one module: the _LOCK_TABLE rows plus
    ``# tpulint: guard=<lock>`` birth-line annotations. Cached — TPL006,
    TPL008, and the LockWorld seed all consume it."""
    cached = getattr(module, "_guard_map_cache", None)
    if cached is None:
        cached = dict(_LOCK_TABLE.get(module.relpath, ()))
        cached.update(_annotated_guards(module))
        module._guard_map_cache = cached
    return cached


def _annotated_guards(module: ModuleInfo) -> Dict[str, str]:
    """``self._foo = {}  # tpulint: guard=self._lock`` declares the
    guard at the attr's birth line."""
    lines_with_guard: Dict[int, str] = {}
    for i, line in enumerate(module.lines, 1):
        m = _GUARD_RE.search(line)
        if m:
            lines_with_guard[i] = m.group(1)
    if not lines_with_guard:
        return {}
    found: Dict[str, str] = {}
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign):
            targets = [node.target]
        else:
            continue
        lock = lines_with_guard.get(node.lineno)
        if lock is None:
            continue
        for t in targets:
            name = dotted_name(t)
            if name:
                found[name] = lock
    return found


def _lock_world(project: Project) -> LockWorld:
    """One LockWorld per lint run (TPL007 and TPL009 share the
    interprocedural pass — building it twice would double the fixpoint
    and let the two rules drift on a future resolution fix)."""
    world = getattr(project, "_lock_world", None)
    if world is None:
        world = LockWorld(
            project,
            guard_locks_of=lambda m: tuple(sorted(set(_guard_map(m)
                                                      .values()))))
        project._lock_world = world
    return world


class TPL006LockDiscipline:
    """Mutations of declared shared containers must happen inside
    ``with <their lock>:``. Driven by a small annotation table (above)
    plus in-source ``# tpulint: guard=<lock>`` annotations, so new
    shared state declares its lock where it is born. Reads are free —
    the repo's convention is copy-under-lock, read-outside."""

    id = "TPL006"

    def check(self, module: ModuleInfo, config: LintConfig) -> List[Finding]:
        guards = _guard_map(module)
        if not guards:
            return []
        out: List[Finding] = []
        self._visit(module, module.tree, guards, with_stack=[],
                    fn_stack=[], out=out)
        return out

    def _visit(self, module, node, guards, with_stack, fn_stack, out):
        if isinstance(node, ast.With):
            items = []
            for item in node.items:
                try:
                    items.append(ast.unparse(item.context_expr))
                except Exception:
                    pass
            with_stack = with_stack + items
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_stack = fn_stack + [node.name]
            # a fresh frame: `with` scopes don't leak into nested defs
            with_stack = []
        self._check_node(module, node, guards, with_stack, fn_stack, out)
        for child in ast.iter_child_nodes(node):
            self._visit(module, child, guards, with_stack, fn_stack, out)

    def _check_node(self, module, node, guards, with_stack, fn_stack, out):
        def held(lock: str) -> bool:
            return lock in with_stack

        def flag(attr, lock, lineno, col, how):
            out.append(Finding(
                self.id, module.relpath, lineno, col,
                f"{how} of `{attr}` outside `with {lock}:` (declared "
                f"guard)"))

        in_init = bool(fn_stack) and fn_stack[-1] in ("__init__", "__new__")

        def exempt(attr: str) -> bool:
            # inside __init__ the instance under construction is not
            # yet shared — its OWN attrs mutate freely; module-level
            # guarded names get no such pass
            return in_init and attr.startswith("self.")

        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            targets = [node.target]
        elif isinstance(node, ast.Delete):
            targets = node.targets
        for t in targets:
            if isinstance(t, ast.Subscript):
                attr = dotted_name(t.value)
                if (attr in guards and not held(guards[attr])
                        and not exempt(attr)):
                    flag(attr, guards[attr], t.lineno, t.col_offset,
                         "item assignment" if not isinstance(
                             node, ast.Delete) else "item deletion")
            else:
                attr = dotted_name(t)
                if (attr in guards and not held(guards[attr])
                        and not in_init and fn_stack):
                    # rebinding outside __init__ swaps the container
                    # under concurrent readers
                    flag(attr, guards[attr], t.lineno, t.col_offset,
                         "rebinding")
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATORS):
            attr = dotted_name(node.func.value)
            if (attr in guards and not held(guards[attr])
                    and not exempt(attr)):
                flag(attr, guards[attr], node.lineno, node.col_offset,
                     f"`.{node.func.attr}()`")


class TPL007LockOrderCycle:
    """A cycle in the static lock-acquisition graph is a deadlock
    hazard: two threads entering it from different nodes can block each
    other forever. The graph is built interprocedurally by
    :mod:`.locks` from the declared locks (``# tpulint: lock=<name>``
    annotations + the TPL006 guard table), following call edges within
    the linted code. Each cycle is reported ONCE, with the witness path
    of every edge on it — both directions of a 2-cycle name the exact
    acquisition sites to untangle."""

    id = "TPL007"

    def check_project(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        world = _lock_world(project)
        for cyc in world.cycles():
            ring = " → ".join(cyc.nodes + (cyc.nodes[0],))
            wits = "; ".join(f"[{e.src}→{e.dst}] {e.witness}"
                             for e in cyc.edges)
            first = cyc.edges[0]
            out.append(Finding(
                self.id, first.path, first.line, 0,
                f"lock-order cycle {ring} — deadlock hazard; {wits}"))
        return out


class TPL008AtomicityViolation:
    """Check-then-act across a lock release: a value read from a
    guarded container inside ``with <lock>:`` feeds a guarded write in
    a *different* ``with`` block of the SAME lock. Between the two
    blocks another thread may have invalidated the read — merge the
    blocks, or annotate ``# tpulint: atomic-ok`` (read or write line)
    when the gap is intentional (e.g. the value is a snapshot by
    design)."""

    id = "TPL008"

    def check(self, module: ModuleInfo, config: LintConfig) -> List[Finding]:
        guards = _guard_map(module)
        decls = module_lock_decls(
            module, tuple(sorted(set(guards.values()))))
        lock_exprs = {d.expr for d in decls} | set(guards.values())
        if not lock_exprs:
            return []
        ok_lines = {i for i, line in enumerate(module.lines, 1)
                    if _ATOMIC_OK_RE.search(line)}

        def annotated(line: int) -> bool:
            return line in ok_lines or (line - 1) in ok_lines

        out: List[Finding] = []
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            out.extend(self._check_fn(module, fn, guards, lock_exprs,
                                      annotated))
        return out

    def _check_fn(self, module, fn, guards, lock_exprs, annotated):
        nested: Set[ast.AST] = set()
        for sub in ast.walk(fn):
            if sub is not fn and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested.update(ast.walk(sub))
        blocks: List[Tuple[str, ast.With]] = []
        for node in ast.walk(fn):
            if node in nested or not isinstance(node, ast.With):
                continue
            for item in node.items:
                try:
                    expr = ast.unparse(item.context_expr)
                except Exception:
                    continue
                if expr in lock_exprs:
                    blocks.append((expr, node))
        out: List[Finding] = []
        for i, (lock, block_a) in enumerate(blocks):
            attrs = {a for a, lk in guards.items() if lk == lock}
            if not attrs:
                continue
            reads = self._guarded_reads(block_a, attrs)
            if not reads:
                continue
            a_nodes = set(ast.walk(block_a))
            for lock_b, block_b in blocks[i + 1:]:
                if lock_b != lock or block_b in a_nodes:
                    continue
                for wnode, attr in self._guarded_writes(block_b, attrs):
                    used = {n.id for n in ast.walk(wnode)
                            if isinstance(n, ast.Name)} & set(reads)
                    if not used:
                        continue
                    name = sorted(used)[0]
                    rline = reads[name]
                    if annotated(wnode.lineno) or annotated(rline):
                        continue
                    out.append(Finding(
                        self.id, module.relpath, wnode.lineno, 0,
                        f"check-then-act across `{lock}` release: "
                        f"`{name}` (read from a guarded container at "
                        f"line {rline}) feeds this guarded write of "
                        f"`{attr}` in a different `with {lock}:` block "
                        f"— merge the critical sections or annotate "
                        f"`# tpulint: atomic-ok`"))
        return out

    @staticmethod
    def _guarded_reads(block: ast.With, attrs: Set[str]) -> Dict[str, int]:
        """name -> read line for ``n = ...<guarded attr>...`` inside
        the block."""
        reads: Dict[str, int] = {}
        for node in ast.walk(block):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            for sub in ast.walk(node.value):
                if dotted_name(sub) in attrs:
                    reads.setdefault(node.targets[0].id, node.lineno)
                    break
        return reads

    @staticmethod
    def _guarded_writes(block: ast.With, attrs: Set[str]):
        """(statement node, attr) for every guarded-container write in
        the block — same mutation shapes TPL006 patrols."""
        for node in ast.walk(block):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = node.targets
            elif (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _MUTATORS):
                attr = dotted_name(node.func.value)
                if attr in attrs:
                    yield node, attr
                continue
            for t in targets:
                base = t.value if isinstance(t, ast.Subscript) else t
                attr = dotted_name(base)
                if attr in attrs:
                    yield node, attr


class TPL009BlockingUnderLock:
    """Blocking or unbounded-time work reached while a declared lock is
    held — file I/O, ``CheckpointManager.restore``, compile builds
    (``StaticFunction._build``), ``time.sleep``, socket ops,
    ``Thread.join``, engine ``step``. Every other thread touching that
    lock stalls behind the slow holder (the repo convention is
    copy-under-lock, act outside). Interprocedural: a call chain that
    reaches the blocking site counts, with the chain in the message."""

    id = "TPL009"

    def check_project(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        world = _lock_world(project)
        for key in sorted(world.fns):
            fn = world.fns[key]
            direct_lines: Set[int] = set()
            for held, desc, line in fn.blocking:
                if not held:
                    continue
                direct_lines.add(line)
                out.append(Finding(
                    self.id, fn.relpath, line, 0,
                    f"blocking call {desc} while holding lock "
                    f"`{held[-1]}` — copy under the lock, do the slow "
                    f"work outside"))
            for held, callname, line in fn.calls:
                if not held or line in direct_lines:
                    continue
                reached = {}
                for g in world.resolve(fn, callname):
                    for desc, site in world.blocks[g.key].items():
                        reached.setdefault(desc, site)
                if not reached:
                    continue
                desc = sorted(reached)[0]
                path, wline, chain = reached[desc]
                via = f" via {chain}" if chain else ""
                out.append(Finding(
                    self.id, fn.relpath, line, 0,
                    f"call `{callname}()`{via} reaches blocking {desc} "
                    f"({path}:{wline}) while holding lock "
                    f"`{held[-1]}` — copy under the lock, do the slow "
                    f"work outside"))
        return out


class TPL010TraceEventParity:
    """Every literal tracer ``.emit("name", ...)`` site uses an event
    name cataloged in docs/OBSERVABILITY.md's event table, and every
    cataloged event has an emit site. The trace is the post-mortem
    record of the request lifecycle — an undocumented event is a dump
    nobody can read, a documented ghost is a timeline gap nobody will
    notice until the 3 a.m. incident (same discipline as TPL003/004)."""

    id = "TPL010"

    def check_project(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        config = project.config
        emits: List[TraceEmit] = []
        for mod in project.modules:
            emits.extend(collect_trace_emits(mod.tree, mod.relpath))
        doc_path = config.observability_doc
        doc_rel = os.path.relpath(doc_path, config.root).replace(os.sep, "/")
        if not emits and not project.full_scope:
            return out     # targeted lint of trace-free modules
        if not os.path.isfile(doc_path):
            if emits:
                out.append(Finding(self.id, doc_rel, 1, 0,
                                   "observability catalog doc not found"))
            return out
        documented = parse_event_doc(doc_path)
        by_name: Dict[str, List[TraceEmit]] = {}
        for e in emits:
            by_name.setdefault(e.name, []).append(e)
        for name, elist in sorted(by_name.items()):
            first = min(elist, key=lambda e: (e.relpath, e.line))
            if not _in_scope(first.relpath, config.metric_doc_scope):
                continue
            if name not in documented:
                out.append(Finding(
                    self.id, first.relpath, first.line, 0,
                    f"trace event `{name}` is emitted but not cataloged "
                    f"in {doc_rel}"))
        if project.full_scope:
            # docs→code direction: full-scope runs only (see TPL003)
            for name, lineno in sorted(documented.items()):
                if name not in by_name:
                    out.append(Finding(
                        self.id, doc_rel, lineno, 0,
                        f"cataloged trace event `{name}` has no literal "
                        f"emit site in the linted code"))
        return out


FILE_RULES = [TPL001HostSyncInCompiled(), TPL002RecompileHazard(),
              TPL005UnseededRandomness(), TPL006LockDiscipline(),
              TPL008AtomicityViolation()]
PROJECT_RULES = [TPL003MetricCatalogParity(), TPL004FaultPointParity(),
                 TPL007LockOrderCycle(), TPL009BlockingUnderLock(),
                 TPL010TraceEventParity()]
RULE_IDS = ("TPL001", "TPL002", "TPL003", "TPL004", "TPL005", "TPL006",
            "TPL007", "TPL008", "TPL009", "TPL010")


def lock_graph_for(project: Project) -> dict:
    """The static lock-acquisition graph of a linted project (nodes,
    witnessed edges, cycles) — `tools/tpulint.py --lock-graph` and the
    --json payload consume this; it is the same LockWorld TPL007/TPL009
    judged, so what reviewers eyeball IS what the gate enforced."""
    return _lock_world(project).graph()
