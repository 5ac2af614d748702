"""paddle_tpu.analysis (tpulint) — tier-1 suite, `analysis` marker.

Four layers, mirroring docs/ANALYSIS.md:

1. **Fixture corpus** — every rule TPL001-TPL006 fires on its bad
   snippet and stays silent on the clean twin, including the
   acceptance drill for TPL003/TPL004: a deliberately undocumented
   metric/fault point fails, documenting it passes (parity proven in
   BOTH directions).
2. **Mechanics** — inline suppressions, baseline round-trip, stable
   ``--json`` output, CLI exit codes (subprocess, like a CI lane).
3. **Parsers** — the doc-catalog grammar against the real docs, fenced
   code exclusion, ``{eng}`` expansion, and the sanitize-name parity
   pin between analysis.catalog and metrics.registry.
4. **Full repo** — ``lint(paddle_tpu tools examples)`` must report
   zero non-baselined findings: THE gate that keeps the invariants.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPULINT = os.path.join(REPO, "tools", "tpulint.py")
BASELINE = os.path.join(REPO, "tools", "tpulint_baseline.json")

from paddle_tpu.analysis import (  # noqa: E402
    LintConfig, lint_paths, load_baseline, parse_fault_doc,
    parse_metric_doc, split_baseline, to_json, write_baseline)
from paddle_tpu.analysis.catalog import sanitize_metric_name  # noqa: E402


# ---------------------------------------------------------------- helpers
_EMPTY_OBS = "# Observability\n\n| metric | type | meaning |\n|---|---|---|\n"
_EMPTY_RES = "# Resilience\n\n| point | site | drill |\n|---|---|---|\n"


def run_lint(tmp_path, files, obs_doc=_EMPTY_OBS, res_doc=_EMPTY_RES,
             **config_kw):
    """Write a fixture corpus + doc catalogs under ``tmp_path``, lint
    it, and return the LintResult."""
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    (tmp_path / "OBS.md").write_text(obs_doc)
    (tmp_path / "RES.md").write_text(res_doc)
    config = LintConfig(root=str(tmp_path),
                        observability_doc=str(tmp_path / "OBS.md"),
                        resilience_doc=str(tmp_path / "RES.md"),
                        **config_kw)
    return lint_paths([str(tmp_path)], config)


def rules_fired(result):
    return sorted({f.rule for f in result.findings})


# ------------------------------------------------------- TPL001 host sync
class TestTPL001HostSync:
    BAD = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step_fn(x, y):
            h = float(x)            # cast sync
            n = x.item()            # method sync
            a = np.asarray(y)       # materialize
            return x + y

        prog = jax.jit(step_fn)
    """

    CLEAN = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step_fn(x, y):
            b = int(x.shape[0])     # static shape: no sync
            n = len(y)              # static under trace
            s = x.astype(jnp.float32)
            return s * b + n

        prog = jax.jit(step_fn)

        def host_driver(t):
            return float(t.item())  # host code may sync freely
    """

    def test_fires_on_bad(self, tmp_path):
        res = run_lint(tmp_path, {"bad.py": self.BAD})
        msgs = [f.message for f in res.findings if f.rule == "TPL001"]
        assert len(msgs) == 3, res.findings
        assert any("float()" in m for m in msgs)
        assert any(".item()" in m for m in msgs)
        assert any("np.asarray" in m for m in msgs)

    def test_silent_on_clean(self, tmp_path):
        res = run_lint(tmp_path, {"clean.py": self.CLEAN})
        assert "TPL001" not in rules_fired(res), res.findings

    def test_nested_decorated_fn_reports_once(self, tmp_path):
        # a decorated def nested inside a compiled fn keeps its own
        # 'decorated' mark but must not be walked twice — one defect,
        # one finding
        res = run_lint(tmp_path, {"mod.py": """
            import jax

            @jax.jit
            def outer(x):
                @jax.jit
                def inner(y):
                    return float(y)
                return inner(x)
        """})
        msgs = [f.message for f in res.findings if f.rule == "TPL001"]
        assert len(msgs) == 1, res.findings


# -------------------------------------------------- TPL002 recompile hazard
class TestTPL002Recompile:
    BAD = """
        import time
        import jax

        def step_fn(x, n):
            if x > 0:               # traced branch
                x = x * 2
            s = f"val={x}"          # traced f-string
            for i in range(n):      # traced trip count
                x = x + 1
            return x

        prog = jax.jit(step_fn)
        out = prog(1, time.time())  # varying host scalar at call site
    """

    CLEAN = """
        import jax

        def step_fn(x, flag=None):
            if flag is None:        # identity check: static
                x = x + 1
            if x.shape[0] > 4:      # static shape branch
                x = x[:4]
            for i in range(x.shape[0]):   # static trip count
                x = x + i
            return x

        prog = jax.jit(step_fn)
        out = prog(1)
    """

    def test_fires_on_bad(self, tmp_path):
        res = run_lint(tmp_path, {"bad.py": self.BAD})
        msgs = [f.message for f in res.findings if f.rule == "TPL002"]
        assert len(msgs) == 4, res.findings
        assert any("`if`" in m for m in msgs)
        assert any("f-string" in m for m in msgs)
        assert any("range()" in m for m in msgs)
        assert any("time.time" in m for m in msgs)

    def test_silent_on_clean(self, tmp_path):
        res = run_lint(tmp_path, {"clean.py": self.CLEAN})
        assert "TPL002" not in rules_fired(res), res.findings

    def test_taint_is_position_gated(self, tmp_path):
        # a later traced rebind of `n` must not retroactively flag the
        # earlier range(n) over a plain int
        res = run_lint(tmp_path, {"mod.py": """
            import jax

            @jax.jit
            def step_fn(x):
                n = 4
                for i in range(n):
                    x = x + i
                n = x * 2
                return n
        """})
        assert rules_fired(res) == [], res.findings

    def test_comprehension_vars_do_not_leak(self, tmp_path):
        # `v` is scoped to the comprehension (py3); reusing the name
        # for a plain int afterwards must not fire the f-string rule
        res = run_lint(tmp_path, {"mod.py": """
            import jax

            @jax.jit
            def step_fn(xs):
                total = sum(v for v in xs)
                v = 3
                s = f"n={v}"
                return total
        """})
        assert rules_fired(res) == [], res.findings

    def test_jax_random_draw_at_call_site_is_clean(self, tmp_path):
        # `from jax import random`: random.uniform(key, ...) is a
        # key-threaded traced array, not a varying host scalar
        res = run_lint(tmp_path, {"mod.py": """
            import jax
            from jax import random

            def step_fn(x):
                return x + 1

            prog = jax.jit(step_fn)
            out = prog(random.uniform(random.PRNGKey(0), (4,)))
        """})
        assert "TPL002" not in rules_fired(res), res.findings

    def test_untraced_rebind_clears_taint(self, tmp_path):
        # traced-then-untraced: after `n = 0` the name carries no
        # taint, so `if n:` is plain Python — regression for the
        # one-interval taint model
        res = run_lint(tmp_path, {"mod.py": """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step_fn(x):
                n = jnp.sum(x)
                n = 0
                if n:
                    x = x + 1
                return x
        """})
        assert rules_fired(res) == [], res.findings

    def test_constant_fstring_at_call_site_is_clean(self, tmp_path):
        # f"v{VERSION}" formats identically every call — one
        # signature, one compile; f"{step}" varies and must fire
        res = run_lint(tmp_path, {"mod.py": """
            import jax

            VERSION = "1.0"

            def step_fn(x):
                return x + 1

            prog = jax.jit(step_fn)
            out = prog(1, tag=f"v{VERSION}")
            step = 3
            out = prog(1, tag=f"s{step}")
        """})
        tpl002 = [f for f in res.findings if f.rule == "TPL002"]
        assert len(tpl002) == 1, res.findings
        assert "f-string" in tpl002[0].message

    def test_method_receiver_propagates_taint(self, tmp_path):
        # the repo's own paddle-style idiom: x.sum()/x.mean() return
        # tracers exactly like jnp.sum(x) — regression for taint lost
        # through method calls
        res = run_lint(tmp_path, {"mod.py": """
            import jax

            @jax.jit
            def step_fn(x):
                s = x.sum()
                if s > 0:
                    return s.item()
                return s
        """})
        assert rules_fired(res) == ["TPL001", "TPL002"], res.findings

    def test_walrus_binding_propagates_taint(self, tmp_path):
        # `(n := jnp.sum(x))` binds in the enclosing scope — the
        # walrus spelling must fire exactly like the two-line form
        res = run_lint(tmp_path, {"mod.py": """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step_fn(x):
                if (n := jnp.sum(x)) > 0:
                    return float(n)
                return n
        """})
        assert rules_fired(res) == ["TPL001", "TPL002"], res.findings

    def test_host_result_methods_stop_taint(self, tmp_path):
        # float(x.item()) is ONE sync, one finding — the .item()
        # result is a host value and must not re-fire through float()
        res = run_lint(tmp_path, {"mod.py": """
            import jax

            @jax.jit
            def step_fn(x):
                return float(x.item())
        """})
        tpl001 = [f for f in res.findings if f.rule == "TPL001"]
        assert len(tpl001) == 1, res.findings
        assert ".item()" in tpl001[0].message

    def test_taint_flows_through_except_handlers(self, tmp_path):
        # excepthandler bodies are not ast.stmt children — regression:
        # taint (and the rules riding on it) must see inside them
        res = run_lint(tmp_path, {"mod.py": """
            import jax

            @jax.jit
            def step_fn(x):
                try:
                    y = x + 1
                except ValueError:
                    z = x * 2
                    if z > 0:
                        return z.item()
                return y
        """})
        assert rules_fired(res) == ["TPL001", "TPL002"], res.findings


# -------------------------------------------- TPL003 metric catalog parity
_OBS_WITH = ("# Observability\n\n| metric | type | meaning |\n|---|---|---|\n"
             "| `paddle_tpu_demo_requests_total{route}` | counter | x |\n")
_REG_SNIPPET = """
    from paddle_tpu import metrics
    reg = metrics.get_registry()
    M = reg.counter("paddle_tpu_demo_requests_total", "x",
                    labels=("route",))
    M.labels(route="/v1").inc()
"""


class TestTPL003CatalogParity:
    def test_undocumented_metric_fails(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": _REG_SNIPPET},
                       metric_doc_scope="")
        msgs = [f.message for f in res.findings if f.rule == "TPL003"]
        assert any("not documented" in m
                   and "paddle_tpu_demo_requests_total" in m
                   for m in msgs), res.findings

    def test_documenting_it_passes(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": _REG_SNIPPET},
                       obs_doc=_OBS_WITH, metric_doc_scope="")
        assert "TPL003" not in rules_fired(res), res.findings

    def test_documented_but_unregistered_fails(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": "x = 1\n"}, obs_doc=_OBS_WITH)
        msgs = [f.message for f in res.findings if f.rule == "TPL003"]
        assert any("has no registration site" in m for m in msgs)
        assert any(f.path.endswith("OBS.md") for f in res.findings)

    def test_label_keyword_mismatch(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": """
            from paddle_tpu import metrics
            reg = metrics.get_registry()
            M = reg.counter("paddle_tpu_demo_requests_total", "x",
                            labels=("route",))
            M.labels(verb="GET").inc()
        """}, obs_doc=_OBS_WITH)
        msgs = [f.message for f in res.findings if f.rule == "TPL003"]
        assert any("verb" in m and "not in the declared label set" in m
                   for m in msgs), res.findings

    def test_conflicting_label_sets(self, tmp_path):
        res = run_lint(tmp_path, {"a.py": """
            from paddle_tpu import metrics
            A = metrics.get_registry().counter(
                "paddle_tpu_demo_requests_total", "x", labels=("route",))
        """, "b.py": """
            from paddle_tpu import metrics
            B = metrics.get_registry().counter(
                "paddle_tpu_demo_requests_total", "x", labels=("verb",))
        """}, obs_doc=_OBS_WITH)
        msgs = [f.message for f in res.findings if f.rule == "TPL003"]
        assert any("conflicting label sets" in m for m in msgs)

    def test_chained_labels_call_is_validated(self, tmp_path):
        # the one-liner reg.counter(...).labels(...) has a Call
        # receiver with no dotted name — it must still be checked
        res = run_lint(tmp_path, {"mod.py": """
            from paddle_tpu import metrics
            reg = metrics.get_registry()
            reg.counter("paddle_tpu_demo_requests_total", "x",
                        labels=("route",)).labels(bogus="1").inc()
        """}, obs_doc=_OBS_WITH)
        msgs = [f.message for f in res.findings if f.rule == "TPL003"]
        assert any("bogus" in m and "not in the declared label set" in m
                   for m in msgs), res.findings

    def test_rebound_receiver_uses_binding_live_at_call_line(self, tmp_path):
        # `c` is rebound to a second metric mid-module: each .labels()
        # call validates against the binding live at ITS line, and the
        # real mismatch on the first metric is still caught
        obs = ("# O\n\n| metric | type | meaning |\n|---|---|---|\n"
               "| `paddle_tpu_a_total{x}` | counter | a |\n"
               "| `paddle_tpu_b_total{y}` | counter | b |\n")
        res = run_lint(tmp_path, {"mod.py": """
            from paddle_tpu import metrics
            reg = metrics.get_registry()
            c = reg.counter("paddle_tpu_a_total", "a", labels=("x",))
            c.labels(x="1").inc()
            c.labels(wrong="1").inc()
            c = reg.counter("paddle_tpu_b_total", "b", labels=("y",))
            c.labels(y="1").inc()
        """}, obs_doc=obs)
        msgs = [f.message for f in res.findings if f.rule == "TPL003"]
        assert len(msgs) == 1, res.findings
        assert "wrong" in msgs[0] and "paddle_tpu_a_total" in msgs[0]

    def test_record_counter_bridge_counts_as_registration(self, tmp_path):
        obs = ("# O\n\n| metric | type | meaning |\n|---|---|---|\n"
               "| `paddle_tpu_serving_queue_depth` | gauge | bridge |\n")
        res = run_lint(tmp_path, {"mod.py": """
            from paddle_tpu.profiler import record_counter
            record_counter("serving.queue_depth", 3)
        """}, obs_doc=obs)
        assert "TPL003" not in rules_fired(res), res.findings


# ---------------------------------------------- TPL004 fault-point parity
class TestTPL004FaultParity:
    def test_uncataloged_point_fails(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": """
            from paddle_tpu import faults
            faults.point("demo.step")
        """})
        msgs = [f.message for f in res.findings if f.rule == "TPL004"]
        assert any("demo.step" in m and "not cataloged" in m for m in msgs)

    def test_cataloging_it_passes(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": """
            from paddle_tpu import faults
            faults.point("demo.step")
        """}, res_doc=("# R\n\n| point | site | drill |\n|---|---|---|\n"
                       "| `demo.step` | mod.py | delay |\n"))
        assert "TPL004" not in rules_fired(res), res.findings

    def test_cataloged_but_absent_point_fails(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": "x = 1\n"},
                       res_doc=("# R\n\n| point | site | drill |\n"
                                "|---|---|---|\n"
                                "| `ghost.point` | nowhere | — |\n"))
        msgs = [f.message for f in res.findings if f.rule == "TPL004"]
        assert any("ghost.point" in m and "no point/declare_point/inject"
                   in m for m in msgs)

    def test_partial_scope_skips_docs_to_code_direction(self, tmp_path):
        # a targeted lint (one file, not the repo root) must not drown
        # in 'documented but unregistered' findings whose registration
        # sites simply weren't in the linted subset
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "mod.py").write_text("x = 1\n")
        (tmp_path / "OBS.md").write_text(_OBS_WITH)
        (tmp_path / "RES.md").write_text(
            "# R\n\n| point | site | drill |\n|---|---|---|\n"
            "| `ghost.point` | nowhere | — |\n")
        config = LintConfig(root=str(tmp_path),
                            observability_doc=str(tmp_path / "OBS.md"),
                            resilience_doc=str(tmp_path / "RES.md"))
        partial = lint_paths([str(tmp_path / "pkg" / "mod.py")], config)
        assert partial.findings == [], partial.findings
        full = lint_paths([str(tmp_path)], config)
        assert {f.rule for f in full.findings} == {"TPL003", "TPL004"}

    def test_declare_and_inject_sites_count(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": """
            from paddle_tpu import faults
            faults.declare_point("demo.a", "site a")
            with faults.inject("demo.b", delay_s=0.1):
                pass
        """}, res_doc=("# R\n\n| point | site | drill |\n|---|---|---|\n"
                       "| `demo.a` | a | — |\n| `demo.b` | b | — |\n"))
        assert "TPL004" not in rules_fired(res), res.findings


# ------------------------------------------- TPL005 unseeded randomness
class TestTPL005UnseededRandomness:
    BAD = """
        import random
        import time
        import numpy as np
        import jax

        def pick(xs):
            return random.choice(xs)            # global RNG

        rng = np.random.default_rng()           # unseeded
        key = jax.random.PRNGKey(int(time.time()))   # wall-clock key
    """

    CLEAN = """
        import random
        import numpy as np
        import jax

        def pick(xs, seed):
            return random.Random(seed).choice(xs)

        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(1234)
    """

    def test_fires_on_bad(self, tmp_path):
        res = run_lint(tmp_path, {"bad.py": self.BAD},
                       tpl005_scopes=("",))
        msgs = [f.message for f in res.findings if f.rule == "TPL005"]
        assert len(msgs) == 3, res.findings
        assert any("random.choice" in m for m in msgs)
        assert any("default_rng" in m for m in msgs)
        assert any("time-derived PRNGKey" in m for m in msgs)

    def test_silent_on_clean(self, tmp_path):
        res = run_lint(tmp_path, {"clean.py": self.CLEAN},
                       tpl005_scopes=("",))
        assert "TPL005" not in rules_fired(res), res.findings

    def test_scope_filter(self, tmp_path):
        # outside the declared scopes the rule stays silent — demo
        # scripts may roll dice
        res = run_lint(tmp_path, {"bad.py": self.BAD})
        assert "TPL005" not in rules_fired(res), res.findings

    def test_bare_import_prngkey_time_derivation_fires(self, tmp_path):
        # `from jax import random` puts PRNGKey under the "random."
        # prefix — regression: it must still reach the time-source scan
        res = run_lint(tmp_path, {"bare.py": """
            import time
            from jax import random

            key = random.PRNGKey(int(time.time()))
            ok = random.PRNGKey(1234)
        """}, tpl005_scopes=("",))
        msgs = [f.message for f in res.findings if f.rule == "TPL005"]
        assert len(msgs) == 1, res.findings
        assert "time-derived PRNGKey" in msgs[0]

    def test_bare_import_jax_random_fns_are_clean(self, tmp_path):
        # `from jax import random` rebinds the stdlib-colliding name:
        # random.uniform(key, ...) is key-threaded and pure, not the
        # process-global RNG
        res = run_lint(tmp_path, {"jr.py": """
            from jax import random

            def sample(key):
                return random.uniform(key, (2,)), random.choice(
                    key, 5)
        """}, tpl005_scopes=("",))
        assert "TPL005" not in rules_fired(res), res.findings

    def test_keyword_seed_is_clean(self, tmp_path):
        # seed passed by keyword is still a seed — regression: the
        # arg-presence check must consult keywords too
        res = run_lint(tmp_path, {"kw.py": """
            import numpy as np

            rng = np.random.default_rng(seed=42)
            legacy = np.random.RandomState(seed=7)
        """}, tpl005_scopes=("",))
        assert "TPL005" not in rules_fired(res), res.findings

    def test_scope_boundary_excludes_sibling_dirs(self, tmp_path):
        # scope "sub" covers sub/ but not a sibling file sharing the
        # prefix — path-boundary matching, not bare startswith
        files = {"sub/a.py": "import random\nx = random.random()\n",
                 "subx.py": "import random\nx = random.random()\n"}
        res = run_lint(tmp_path, files, tpl005_scopes=("sub",))
        paths = {f.path for f in res.findings if f.rule == "TPL005"}
        assert paths == {"sub/a.py"}, res.findings

    def test_time_seeded_ctor_fires(self, tmp_path):
        # a wall-clock seed is the unseeded defect wearing an
        # argument — both spellings must fire
        res = run_lint(tmp_path, {"ts.py": """
            import time
            import random
            import numpy as np

            rng = np.random.default_rng(time.time_ns())
            r = random.Random(time.time())
            ok = np.random.default_rng(1234)
        """}, tpl005_scopes=("",))
        msgs = [f.message for f in res.findings if f.rule == "TPL005"]
        assert len(msgs) == 2, res.findings
        assert all("time-seeded is unseeded" in m for m in msgs)

    def test_seeded_bit_generators(self, tmp_path):
        # Generator(PCG64(seed)) is the idiom the rule's message
        # recommends — it must not fire; an unseeded PCG64() must
        res = run_lint(tmp_path, {"bg.py": """
            import numpy as np

            good = np.random.Generator(np.random.PCG64(1234))
            bad = np.random.Generator(np.random.PCG64())
        """}, tpl005_scopes=("",))
        msgs = [f.message for f in res.findings if f.rule == "TPL005"]
        assert len(msgs) == 1, res.findings
        assert "PCG64()` without a seed" in msgs[0]


# --------------------------------------------- TPL006 lock discipline
class TestTPL006LockDiscipline:
    BAD = """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._pages = {}  # tpulint: guard=self._lock

            def put(self, k, v):
                self._pages[k] = v        # unguarded mutation

            def drop(self, k):
                self._pages.pop(k)        # unguarded mutator call
    """

    CLEAN = """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._pages = {}  # tpulint: guard=self._lock

            def put(self, k, v):
                with self._lock:
                    self._pages[k] = v

            def snapshot(self):
                return dict(self._pages)  # reads are free
    """

    def test_fires_on_bad(self, tmp_path):
        res = run_lint(tmp_path, {"bad.py": self.BAD})
        msgs = [f.message for f in res.findings if f.rule == "TPL006"]
        assert len(msgs) == 2, res.findings
        assert all("self._lock" in m for m in msgs)

    def test_silent_on_clean(self, tmp_path):
        res = run_lint(tmp_path, {"clean.py": self.CLEAN})
        assert "TPL006" not in rules_fired(res), res.findings

    def test_init_is_exempt(self, tmp_path):
        # the __init__ item-write IS a mutation, but the object is not
        # yet shared (the registry's _MetricFamily.__init__ idiom)
        res = run_lint(tmp_path, {"mod.py": """
            import threading

            class Fam:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._children = {}  # tpulint: guard=self._lock
                    self._children[()] = object()
        """})
        assert "TPL006" not in rules_fired(res), res.findings


# ----------------------------------------------- TPL007 lock-order cycles
class TestTPL007LockOrderCycle:
    BAD = """
        import threading

        lock_a = threading.Lock()  # tpulint: lock=a
        lock_b = threading.Lock()  # tpulint: lock=b

        def fwd():
            with lock_a:
                with lock_b:
                    pass

        def rev():
            with lock_b:
                with lock_a:
                    pass
    """

    CLEAN = """
        import threading

        lock_a = threading.Lock()  # tpulint: lock=a
        lock_b = threading.Lock()  # tpulint: lock=b

        def one():
            with lock_a:
                with lock_b:
                    pass

        def two():
            with lock_a:
                with lock_b:
                    pass
    """

    INTERPROCEDURAL = """
        import threading

        lock_a = threading.Lock()  # tpulint: lock=a
        lock_b = threading.Lock()  # tpulint: lock=b

        def fwd():
            with lock_a:
                grab_b()

        def grab_b():
            with lock_b:
                pass

        def rev():
            with lock_b:
                with lock_a:
                    pass
    """

    def test_inversion_fires_with_both_witness_paths(self, tmp_path):
        """The acceptance drill: an injected lock-order inversion is
        reported ONCE per cycle, and the message carries the witness
        acquisition site of BOTH directions."""
        res = run_lint(tmp_path, {"bad.py": self.BAD})
        found = [f for f in res.findings if f.rule == "TPL007"]
        assert len(found) == 1, res.findings
        msg = found[0].message
        assert "lock-order cycle" in msg and "deadlock hazard" in msg
        assert "[a→b]" in msg and "[b→a]" in msg
        assert msg.count("bad.py:") >= 2     # both acquisition sites

    def test_silent_on_consistent_order(self, tmp_path):
        res = run_lint(tmp_path, {"clean.py": self.CLEAN})
        assert "TPL007" not in rules_fired(res), res.findings

    def test_cycle_through_call_edge(self, tmp_path):
        # fwd holds `a` and CALLS into grab_b -> the a→b edge exists
        # only interprocedurally; rev closes the cycle directly
        res = run_lint(tmp_path, {"ip.py": self.INTERPROCEDURAL})
        found = [f for f in res.findings if f.rule == "TPL007"]
        assert len(found) == 1, res.findings
        assert "grab_b" in found[0].message   # the call-chain witness

    def test_disable_annotation_fixes_it(self, tmp_path):
        # the cycle finding anchors at its first edge's acquisition
        # site; a disable comment above every inner acquisition covers
        # whichever edge anchors the report
        fixed = self.BAD.replace(
            "        with lock_b:\n                    pass",
            "        # tpulint: disable=TPL007\n"
            "                with lock_b:\n                    pass"
        ).replace(
            "        with lock_a:\n                    pass",
            "        # tpulint: disable=TPL007\n"
            "                with lock_a:\n                    pass")
        res = run_lint(tmp_path, {"bad.py": fixed})
        assert "TPL007" not in rules_fired(res), res.findings


# ------------------------------------------- TPL008 atomicity violations
class TestTPL008Atomicity:
    BAD = """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._pages = {}  # tpulint: guard=self._lock

            def grow(self, k):
                with self._lock:
                    n = len(self._pages)
                with self._lock:
                    self._pages[k] = n
    """

    CLEAN = """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._pages = {}  # tpulint: guard=self._lock

            def grow(self, k):
                with self._lock:
                    n = len(self._pages)
                    self._pages[k] = n
    """

    def test_fires_on_split_critical_section(self, tmp_path):
        res = run_lint(tmp_path, {"bad.py": self.BAD})
        found = [f for f in res.findings if f.rule == "TPL008"]
        assert len(found) == 1, res.findings
        msg = found[0].message
        assert "check-then-act" in msg and "`n`" in msg
        assert "atomic-ok" in msg            # the fix is in the message

    def test_silent_on_merged_block(self, tmp_path):
        res = run_lint(tmp_path, {"clean.py": self.CLEAN})
        assert "TPL008" not in rules_fired(res), res.findings

    def test_atomic_ok_annotation(self, tmp_path):
        body = self.BAD.replace(
            "self._pages[k] = n",
            "self._pages[k] = n  # tpulint: atomic-ok (snapshot by design)")
        res = run_lint(tmp_path, {"ok.py": body})
        assert "TPL008" not in rules_fired(res), res.findings

    def test_unrelated_write_is_silent(self, tmp_path):
        # the second block writes a value NOT derived from the guarded
        # read — plain two critical sections, not check-then-act
        body = self.BAD.replace("self._pages[k] = n",
                                "self._pages[k] = 0")
        res = run_lint(tmp_path, {"mod.py": body})
        assert "TPL008" not in rules_fired(res), res.findings


# --------------------------------------------- TPL009 blocking under lock
class TestTPL009BlockingUnderLock:
    BAD_DIRECT = """
        import threading
        import time

        lock_a = threading.Lock()  # tpulint: lock=a

        def slow():
            with lock_a:
                time.sleep(1.0)
    """

    BAD_INTERPROCEDURAL = """
        import threading

        lock_a = threading.Lock()  # tpulint: lock=a

        def outer():
            with lock_a:
                helper()

        def helper():
            return open("/tmp/x").read()
    """

    CLEAN = """
        import threading
        import time

        lock_a = threading.Lock()  # tpulint: lock=a
        _items = []

        def copy_then_sleep():
            with lock_a:
                snap = list(_items)
            time.sleep(0.01)      # slow work OUTSIDE the lock
            return snap

        def string_join_is_fine():
            with lock_a:
                return ", ".join(["a", "b"])   # not a thread join
    """

    def test_direct_blocking_fires(self, tmp_path):
        res = run_lint(tmp_path, {"bad.py": self.BAD_DIRECT})
        found = [f for f in res.findings if f.rule == "TPL009"]
        assert len(found) == 1, res.findings
        msg = found[0].message
        assert "time.sleep" in msg and "`a`" in msg
        assert "copy under the lock" in msg

    def test_interprocedural_blocking_fires(self, tmp_path):
        res = run_lint(tmp_path, {"ip.py": self.BAD_INTERPROCEDURAL})
        found = [f for f in res.findings if f.rule == "TPL009"]
        assert len(found) == 1, res.findings
        msg = found[0].message
        assert "helper" in msg and "open()" in msg and "`a`" in msg

    def test_silent_on_copy_under_lock(self, tmp_path):
        res = run_lint(tmp_path, {"clean.py": self.CLEAN})
        assert "TPL009" not in rules_fired(res), res.findings


# ------------------------------------------- TPL010 trace-event parity
_OBS_WITH_EVENT = ("# O\n\n| event | when |\n|---|---|\n"
                   "| `req.fixture` | on fixture |\n")


class TestTPL010TraceEventParity:
    EMIT = """
        from paddle_tpu.serving import tracing

        tracer = tracing.get_tracer()
        tracer.emit("req.fixture", "r1", arg=1.0)
    """

    def test_uncataloged_event_fails(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": self.EMIT},
                       metric_doc_scope="")
        msgs = [f.message for f in res.findings if f.rule == "TPL010"]
        assert any("req.fixture" in m and "not cataloged" in m
                   for m in msgs), res.findings

    def test_cataloging_it_passes(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": self.EMIT},
                       obs_doc=_OBS_WITH_EVENT, metric_doc_scope="")
        assert "TPL010" not in rules_fired(res), res.findings

    def test_cataloged_but_absent_event_fails(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": "x = 1\n"},
                       obs_doc=_OBS_WITH_EVENT)
        msgs = [f.message for f in res.findings if f.rule == "TPL010"]
        assert any("req.fixture" in m and "no literal emit site" in m
                   for m in msgs), res.findings

    def test_self_trace_attribute_counts(self, tmp_path):
        # the production shape: an engine emitting via self._trace
        res = run_lint(tmp_path, {"mod.py": """
            class Engine:
                def __init__(self, trace):
                    self._trace = trace

                def step(self):
                    self._trace.emit("req.fixture", "r1")
        """}, obs_doc=_OBS_WITH_EVENT, metric_doc_scope="")
        assert "TPL010" not in rules_fired(res), res.findings

    @pytest.mark.parametrize("site, name", [
        ('sp = self._trace.begin("step", "e")', "step"),
        ('sp = self._trace.next("step.fixture", sp)', "step.fixture"),
        ('sp = self._trace.begin("sweep", "router")', "sweep")])
    def test_span_sites_are_cataloged_like_emit_sites(self, tmp_path,
                                                      site, name):
        # spans (tracer.begin / tracer.next) name themselves at the call
        # site as events do, bare ``step`` / ``sweep`` included
        src = f"""
            class Engine:
                def step(self, sp=None):
                    {site}
        """
        res = run_lint(tmp_path, {"mod.py": src}, metric_doc_scope="")
        msgs = [f.message for f in res.findings if f.rule == "TPL010"]
        assert any(f"`{name}`" in m and "not cataloged" in m
                   for m in msgs), res.findings
        obs = ("# O\n\n| event | when |\n|---|---|\n"
               f"| `{name}` | span |\n")
        res = run_lint(tmp_path, {"mod.py": src}, obs_doc=obs,
                       metric_doc_scope="")
        assert "TPL010" not in rules_fired(res), res.findings

    def test_unrelated_emit_api_is_ignored(self, tmp_path):
        # the ONNX node builder's self.emit("Sqrt", ...) must not be
        # mistaken for a trace site: the receiver is not tracer-shaped
        res = run_lint(tmp_path, {"mod.py": """
            class Converter:
                def emit(self, op, *a):
                    pass

                def convert(self):
                    self.emit("Sqrt", "x")
                    self.emit("req.looking_name", "y")
        """}, metric_doc_scope="")
        assert "TPL010" not in rules_fired(res), res.findings


# ------------------------------------------------- suppressions + baseline
class TestSuppressionAndBaseline:
    SNIPPET = """
        import jax

        def step_fn(x):
            return float(x)

        prog = jax.jit(step_fn)
    """

    def test_same_line_suppression(self, tmp_path):
        body = self.SNIPPET.replace(
            "return float(x)",
            "return float(x)  # tpulint: disable=TPL001")
        res = run_lint(tmp_path, {"mod.py": body})
        assert "TPL001" not in rules_fired(res)
        assert res.suppressed == 1

    def test_previous_line_suppression(self, tmp_path):
        body = textwrap.dedent(self.SNIPPET).replace(
            "    return float(x)",
            "    # tpulint: disable=all\n    return float(x)")
        res = run_lint(tmp_path, {"mod.py": body})
        assert "TPL001" not in rules_fired(res)
        assert res.suppressed == 1

    def test_disable_string_in_literal_does_not_arm(self, tmp_path):
        body = self.SNIPPET.replace(
            "return float(x)",
            'return float(x), "# tpulint: disable=TPL001"')
        res = run_lint(tmp_path, {"mod.py": body})
        assert "TPL001" in rules_fired(res)

    def test_baseline_round_trip(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": self.SNIPPET})
        assert res.findings
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), res.findings)
        entries = load_baseline(str(bl))
        assert all(e["note"] for e in entries)
        new, old = split_baseline(res.findings, entries)
        assert new == [] and len(old) == len(res.findings)

    def test_write_baseline_preserves_curated_notes(self, tmp_path):
        # regeneration must never destroy justifications: surviving
        # (rule, path, message) keys keep their note, new entries TODO
        res = run_lint(tmp_path, {"mod.py": self.SNIPPET})
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), res.findings)
        entries = load_baseline(str(bl))
        entries[0]["note"] = "accepted: legacy sync, tracked in #42"
        bl.write_text(json.dumps({"version": 1, "entries": entries}))
        body = self.SNIPPET.replace("return float(x)",
                                    "return float(x) + int(x)")
        res2 = run_lint(tmp_path, {"mod.py": body})
        write_baseline(str(bl), res2.findings)
        notes = {e["message"]: e["note"] for e in load_baseline(str(bl))}
        assert any(n == "accepted: legacy sync, tracked in #42"
                   for n in notes.values()), notes
        assert any(n.startswith("TODO") for n in notes.values()), notes

    def test_baseline_does_not_hide_new_findings(self, tmp_path):
        res = run_lint(tmp_path, {"mod.py": self.SNIPPET})
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), res.findings)
        entries = load_baseline(str(bl))
        body = self.SNIPPET.replace("return float(x)",
                                    "return float(x) + int(x)")
        res2 = run_lint(tmp_path, {"mod.py": body})
        new, old = split_baseline(res2.findings, entries)
        assert len(old) == len(res.findings)
        assert len(new) == 1 and "int()" in new[0].message


# ----------------------------------------------------------- CLI behavior
class TestCLI:
    def _write_fixture(self, tmp_path):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "OBSERVABILITY.md").write_text(_EMPTY_OBS)
        (tmp_path / "docs" / "RESILIENCE.md").write_text(_EMPTY_RES)
        (tmp_path / "mod.py").write_text(textwrap.dedent(
            TestSuppressionAndBaseline.SNIPPET))

    def _run(self, *args):
        return subprocess.run([sys.executable, TPULINT, *args],
                              capture_output=True, text=True)

    def test_exit_codes_and_json_stability(self, tmp_path):
        self._write_fixture(tmp_path)
        args = ("--root", str(tmp_path), "--no-baseline", "--json",
                str(tmp_path / "mod.py"))
        r1, r2 = self._run(*args), self._run(*args)
        assert r1.returncode == 1
        assert r1.stdout == r2.stdout          # stable, diffable
        payload = json.loads(r1.stdout)
        assert payload["version"] == 1
        assert [f["rule"] for f in payload["findings"]] == ["TPL001"]

    def test_write_baseline_then_clean(self, tmp_path):
        self._write_fixture(tmp_path)
        bl = str(tmp_path / "bl.json")
        r = self._run("--root", str(tmp_path), "--baseline", bl,
                      "--write-baseline", str(tmp_path / "mod.py"))
        assert r.returncode == 0, r.stderr
        r = self._run("--root", str(tmp_path), "--baseline", bl,
                      str(tmp_path / "mod.py"))
        assert r.returncode == 0, r.stdout
        assert "1 baselined" in r.stdout

    def test_explicit_non_py_path_fails_loudly(self, tmp_path):
        # a lane misconfigured with a .pyi/doc path must exit 2, not
        # "pass" by linting nothing
        self._write_fixture(tmp_path)
        stub = tmp_path / "mod.pyi"
        stub.write_text("x: int\n")
        r = self._run("--root", str(tmp_path), "--no-baseline", str(stub))
        assert r.returncode == 2, (r.stdout, r.stderr)
        assert "not a .py file" in r.stderr

    def test_malformed_baseline_entry_exits_2(self, tmp_path):
        # a bad merge leaving a non-object entry is "bad baseline"
        # (exit 2), never an AttributeError read as exit-1 findings
        self._write_fixture(tmp_path)
        bl = tmp_path / "bl.json"
        bl.write_text('{"version": 1, "entries": ["oops"]}')
        r = self._run("--root", str(tmp_path), "--baseline", str(bl),
                      str(tmp_path / "mod.py"))
        assert r.returncode == 2, (r.stdout, r.stderr)
        assert "entries[0]" in r.stderr

    def test_internal_error_exits_2(self, tmp_path, monkeypatch):
        # a rule crash must stay distinguishable from "findings
        # present" (exit 1) for CI lanes branching on the code
        self._write_fixture(tmp_path)
        spec = importlib.util.spec_from_file_location(
            "_tpulint_cli", TPULINT)
        cli = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cli)
        analysis = cli._load_analysis()

        def boom(paths, config):
            raise RuntimeError("rule crashed")
        monkeypatch.setattr(analysis, "lint_paths", boom)
        rc = cli.main(["--root", str(tmp_path), "--no-baseline",
                       str(tmp_path / "mod.py")])
        assert rc == 2

    def _write_lock_fixture(self, tmp_path, body):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "OBSERVABILITY.md").write_text(_EMPTY_OBS)
        (tmp_path / "docs" / "RESILIENCE.md").write_text(_EMPTY_RES)
        (tmp_path / "mod.py").write_text(textwrap.dedent(body))

    def test_lock_graph_dot_output(self, tmp_path):
        self._write_lock_fixture(tmp_path, TestTPL007LockOrderCycle.CLEAN)
        r = self._run("--root", str(tmp_path), "--no-baseline",
                      "--lock-graph", str(tmp_path / "mod.py"))
        assert r.returncode == 0, (r.stdout, r.stderr)
        assert r.stdout.startswith("digraph lock_order {")
        assert '"a" -> "b"' in r.stdout
        assert "color=red" not in r.stdout     # acyclic: no red edges

    def test_lock_graph_cycle_is_red_and_exits_1(self, tmp_path):
        # a red edge in the SVG and a green CI lane must not disagree
        self._write_lock_fixture(tmp_path, TestTPL007LockOrderCycle.BAD)
        r = self._run("--root", str(tmp_path), "--no-baseline",
                      "--lock-graph", str(tmp_path / "mod.py"))
        assert r.returncode == 1, (r.stdout, r.stderr)
        assert "color=red" in r.stdout

    def test_json_includes_lock_graph(self, tmp_path):
        self._write_lock_fixture(tmp_path, TestTPL007LockOrderCycle.CLEAN)
        r = self._run("--root", str(tmp_path), "--no-baseline", "--json",
                      str(tmp_path / "mod.py"))
        assert r.returncode == 0, (r.stdout, r.stderr)
        g = json.loads(r.stdout)["lock_graph"]
        assert g["nodes"] == ["a", "b"]
        assert [(e["from"], e["to"]) for e in g["edges"]] == [("a", "b")]
        assert all(e["witness"] for e in g["edges"])
        assert g["cycles"] == []

    def test_cli_loads_without_importing_paddle_tpu(self, tmp_path):
        self._write_fixture(tmp_path)
        probe = ("import sys, runpy; sys.argv=[%r, '--root', %r, "
                 "'--no-baseline', %r]; "
                 "rc = 0\n"
                 "try: runpy.run_path(%r, run_name='__main__')\n"
                 "except SystemExit as e: rc = e.code\n"
                 "assert 'paddle_tpu' not in sys.modules, "
                 "'CLI must not import the package under analysis'\n"
                 "assert 'jax' not in sys.modules, 'CLI must stay jax-free'\n"
                 "sys.exit(rc)") % (TPULINT, str(tmp_path),
                                    str(tmp_path / "mod.py"), TPULINT)
        r = subprocess.run([sys.executable, "-c", probe],
                           capture_output=True, text=True)
        assert r.returncode == 1, (r.stdout, r.stderr)


# ------------------------------------------------------------ doc parsers
class TestCatalogParsers:
    def test_real_observability_catalog(self):
        docs = parse_metric_doc(os.path.join(REPO, "docs",
                                             "OBSERVABILITY.md"))
        assert len(docs) >= 50
        assert "paddle_tpu_serving_ttft_seconds" in docs
        assert "paddle_tpu_jit_compiles_total" in docs
        # {eng} shorthand expands to the per-engine label pair
        _line, labels = docs["paddle_tpu_serving_ttft_seconds"]
        assert labels == ("engine_id", "model_id")

    def test_real_resilience_catalog(self):
        docs = parse_fault_doc(os.path.join(REPO, "docs", "RESILIENCE.md"))
        assert "serving.decode_step" in docs and "ckpt.commit" in docs

    def test_fenced_code_is_excluded(self, tmp_path):
        doc = tmp_path / "d.md"
        doc.write_text("```\n| `paddle_tpu_fake_total` | counter | x |\n"
                       "```\n")
        assert parse_metric_doc(str(doc)) == {}

    def test_prose_backticks_are_excluded(self, tmp_path):
        doc = tmp_path / "d.md"
        doc.write_text("| `reg.get(\"paddle_tpu_x_total\").value` | is "
                       "prose | not a catalog row token |\n")
        assert parse_metric_doc(str(doc)) == {}

    def test_only_first_cell_documents(self, tmp_path):
        # a cross-reference in another row's MEANING cell must not
        # satisfy parity after the real catalog row is deleted
        doc = tmp_path / "d.md"
        doc.write_text("| `paddle_tpu_a_total` | counter | see also "
                       "`paddle_tpu_b_total` |\n")
        assert set(parse_metric_doc(str(doc))) == {"paddle_tpu_a_total"}

    def test_sanitize_parity_with_registry(self):
        from paddle_tpu.metrics.registry import (
            sanitize_metric_name as registry_sanitize)
        for raw in ("serving.queue_depth", "a b/c", "paddle_tpu_ok",
                    "9starts_bad", "Weird-Name!"):
            assert sanitize_metric_name(raw) == registry_sanitize(raw)


# ------------------------------------------------------- compiled scopes
class TestCompiledScopeDetection:
    def test_engine_step_fns_are_detected(self):
        from paddle_tpu.analysis.core import parse_module
        from paddle_tpu.analysis.scopes import CompiledScopes
        mod, err = parse_module(
            os.path.join(REPO, "paddle_tpu", "serving", "engine.py"), REPO)
        assert err is None
        names = {fn.name for fn in CompiledScopes(mod.tree).compiled}
        # the unified step program AND its traced helpers
        assert {"step_fn", "batched_sample", "one_row"} <= names


# -------------------------------------------------- metrics_dump bridge
class TestCheckDocsBridge:
    def _load_metrics_dump(self):
        spec = importlib.util.spec_from_file_location(
            "_metrics_dump", os.path.join(REPO, "tools",
                                          "metrics_dump.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_check_docs_flags_undocumented_live_family(self, capsys):
        md = self._load_metrics_dump()
        rc = md._check_docs(["paddle_tpu_serving_ttft_seconds",
                             "paddle_tpu_bogus_total"], REPO)
        out = capsys.readouterr().out
        assert rc == 1 and "paddle_tpu_bogus_total" in out

    def test_check_docs_passes_on_documented(self, capsys):
        md = self._load_metrics_dump()
        rc = md._check_docs(["paddle_tpu_serving_ttft_seconds"], REPO)
        assert rc == 0

    def test_check_docs_rejects_out(self, capsys):
        # --check-docs prints a report, it can't honor --out: fail
        # loudly instead of silently creating no artifact
        md = self._load_metrics_dump()
        with pytest.raises(SystemExit) as exc:
            md.main(["--demo", "--check-docs", "--out", "/tmp/x.json"])
        assert exc.value.code == 2
        with pytest.raises(SystemExit) as exc:
            md.main(["--demo", "--check-docs", "--prometheus"])
        assert exc.value.code == 2

    def test_check_docs_empty_registry_fails(self, capsys):
        # a parity gate that checked zero families must not pass green
        md = self._load_metrics_dump()
        rc = md._check_docs([], REPO)
        out = capsys.readouterr().out
        assert rc == 1 and "empty" in out

    def test_check_docs_is_jax_free(self):
        # the --url scrape path runs on monitoring hosts without jax:
        # _check_docs must not import paddle_tpu (which pulls it)
        probe = (
            "import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location("
            "'_md', %r)\n"
            "md = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(md)\n"
            "rc = md._check_docs(['paddle_tpu_serving_ttft_seconds'], %r)\n"
            "assert rc == 0, rc\n"
            "assert 'paddle_tpu' not in sys.modules\n"
            "assert 'jax' not in sys.modules\n"
        ) % (os.path.join(REPO, "tools", "metrics_dump.py"), REPO)
        r = subprocess.run([sys.executable, "-c", probe],
                           capture_output=True, text=True)
        assert r.returncode == 0, (r.stdout, r.stderr)


# ------------------------------------------------------------- full repo
class TestFullRepo:
    def test_repo_is_clean_modulo_baseline(self):
        """THE gate: paddle_tpu + tools + examples lint clean against
        the committed baseline. A new host sync, recompile hazard,
        undocumented metric/fault point, unseeded RNG, or unguarded
        mutation fails tier-1 here — not in a production drill."""
        config = LintConfig(root=REPO)
        result = lint_paths([os.path.join(REPO, p)
                             for p in ("paddle_tpu", "tools", "examples")],
                            config)
        entries = load_baseline(BASELINE)
        new, _old = split_baseline(result.findings, entries)
        assert result.files > 200      # the walk really saw the repo
        assert new == [], "\n".join(f.render() for f in new)

    def test_baseline_entries_are_justified(self):
        for e in load_baseline(BASELINE):
            assert e.get("note", "").strip(), (
                f"baseline entry {e} has no justification note")
            assert not e["note"].startswith("TODO"), (
                f"baseline entry {e} still carries the TODO note")


# --------------------------------------- runtime half: sanitized control
class TestLockSanitizerRegression:
    def test_scrape_step_reload_concurrently_clean(self, tmp_path):
        """The runtime twin of the TPL007-009 gate: a /metrics scraper,
        a health()/states() prober and the single driver thread
        (step + rolling reload) race over a live 2-replica router with
        the router / registry / watchdog locks under LockSanitizer —
        zero ordering or reentrancy violations, every request completes.
        (Scenario 13 in tools/chaos_serve.py is the 200-iteration slow
        version; this is the tier-1 smoke.)"""
        import threading
        import urllib.request

        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import faults, metrics
        from paddle_tpu.checkpoint import CheckpointManager
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny
        from paddle_tpu.serving import Router

        def model(seed=0):
            paddle.seed(seed)
            return LlamaForCausalLM(llama_tiny(
                vocab_size=32, hidden_size=16, num_layers=1, num_heads=1,
                num_key_value_heads=1, max_position_embeddings=32))

        CheckpointManager(str(tmp_path)).save(
            1, {"model": model(seed=1).state_dict()})
        registry = metrics.get_registry()
        san = faults.LockSanitizer(order=("router",),
                                   leaves=("metrics.registry",))
        r = Router()
        r.add_model("m", [model(), model()], page_size=4,
                    max_batch_slots=1)
        san.attach(r, "_lock", "router")
        orig_reg_lock = san.attach(registry, "_lock", "metrics.registry")
        try:
            stop, errors = threading.Event(), []

            def spin(fn):
                try:
                    while not stop.is_set():
                        fn()
                except Exception as e:   # noqa: BLE001 — surfaced below
                    errors.append(e)

            with metrics.MetricsServer(health_cb=r.health, port=0) as srv:
                threads = [
                    threading.Thread(target=spin, args=(lambda: (
                        urllib.request.urlopen(srv.url + "/metrics",
                                               timeout=10).read()),)),
                    threading.Thread(target=spin, args=(lambda: (
                        r.health(), r.states()),)),
                ]
                for t in threads:
                    t.start()
                # the driver half: live traffic + one rolling reload
                live = [r.submit(np.arange(3), model="m",
                                 max_new_tokens=2) for _ in range(3)]
                for _ in range(5):
                    r.step()
                summary = r.reload(str(tmp_path))
                assert all(e["result"] == "ok"
                           for e in summary["engines"]), summary
                outs = r.run()
                stop.set()
                for t in threads:
                    t.join(timeout=60)
                assert not any(t.is_alive() for t in threads)
            assert not errors, errors
            assert sorted(outs) == sorted(live)
            assert all(outs[k].finish_reason == "length" for k in live)
            san.assert_clean()
        finally:
            registry._lock = orig_reg_lock
