"""The serve loop of every family: ``Router.submit`` / ``Router.step`` over
one ``ServingEngine`` (chunked prefill and decode through the paged pool and
the Pallas paged-attention kernel), driven by a closed loop of callers from
one thread, every request greedy. Where the traffic has sessions their
contexts are served in set-up, and the window's prompts reach them through
the program's prefix cache. Every token is stamped on the stream callback,
on the client's side of the router.

What is one model's is the family's (``benchmarks/families/``): the model
with its seeded weights, what its pool has to hold, and the reference's
logits that ``served_gaps`` judges the served tokens by. An entry module
(``entries/<family>_serve.py``) binds the two.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from . import traffic as traffic_mod
from .compare import Compared

KIND = "serve"
MODEL_ID = "m"


class _Req:
    __slots__ = ("caller", "prompt", "cached", "n_out", "t_submit", "tokens",
                 "times", "finish", "t_done", "faults")

    def __init__(self, caller, prompt, cached, n_out, t_submit):
        self.caller, self.prompt, self.n_out = caller, prompt, n_out
        self.cached = cached   # tokens of the session's context in the prompt
        self.t_submit = t_submit
        self.tokens: List[int] = []
        self.times: List[float] = []
        self.finish: Optional[str] = None
        self.t_done: Optional[float] = None
        self.faults = 0   # stream contract breaches (exactly-once, in order)


class ServeRun:
    def __init__(self, ctx, family):
        self.ctx, self.family = ctx, family
        self.cfg = ctx.cell.config
        self.serving = self.cfg["serving"]
        self.traffic = ctx.cell.traffic
        self.reqs: Dict[Any, _Req] = {}
        self.idle: List[int] = []
        self.samples: List[tuple] = []   # (t, engine steps, running, pages)

    # ---------------------------------------------------------- set-up
    def setup(self):
        from paddle_tpu.serving import Router, tracing

        ctx, cfg = self.ctx, self.cfg
        model = self.family.serve_model(cfg, ctx.seed)
        ctx.log("model built, seeded weights loaded")
        pool = self.family.pool_args(model, self.serving)
        self.router = Router()
        self.router.add_model(MODEL_ID, model, **pool)
        self.engine = self.router.engines(MODEL_ID)[0]
        self.num_pages = pool["num_pages"]
        self.tracer = tracing.get_tracer()
        self.vocab = int(cfg["vocab_size"])
        self.streams = [traffic_mod.CallerStream(self.traffic, self.vocab,
                                                 ctx.seed, c)
                        for c in range(int(self.traffic["callers"]))]
        self._warm_buckets()
        self._build_sessions()
        self._ramp()
        self.compiles_before = dict(self.engine.compile_counts())

    def _warm_buckets(self):
        """One lone request per step bucket that this engine can form (the
        slot grid, then powers of two from 16 up to the token budget), so
        that nothing compiles once traffic flows. The engine has no call
        that compiles a bucket without serving one (PERF.md, open
        questions)."""
        slots = int(self.serving["max_batch_slots"])
        budget = int(self.serving["token_budget"])
        rng = np.random.default_rng([int(self.ctx.seed), 3])
        bucket = 16
        while True:
            n_prompt = min(bucket, budget) * 3 // 4
            if n_prompt > slots:
                t0 = time.perf_counter()
                self.router.submit(rng.integers(0, self.vocab, (n_prompt,)),
                                   model=MODEL_ID, max_new_tokens=2,
                                   temperature=0.0)
                self.router.run()
                self.ctx.log(f"bucket {bucket} (and the slot grid) warm: "
                             f"{time.perf_counter() - t0:.1f}s")
                self._recover(rng)
            if bucket >= budget:
                break
            bucket *= 2
        self.ctx.log(f"compiled: {self.engine.compile_counts()}")

    def _recover(self, rng):
        """A bucket's first call that compiles for longer than the engine's
        30 s stall threshold trips its watchdog (the 64-row bucket takes
        27-32 s cold on a v5e), and the router then routes nothing to the
        engine. A few healthy steps on the slot grid bring it back; the
        watchdog stays armed, at its default, through the window."""
        while any(s != "healthy" for s in self.router.states().values()):
            if not self.router.has_work:
                self.engine.add_request(rng.integers(0, self.vocab, (2,)),
                                        max_new_tokens=4, temperature=0.0)
            self.router.step()
        self.router.take_outputs()

    def _build_sessions(self):
        """Serve each session's context once (one token out), all together,
        through the router as any prompt: what the pool then holds for the
        sessions is the program's own chunked prefill, and the window's
        requests read it through the prefix cache."""
        contexts = traffic_mod.session_contexts(self.traffic, self.vocab,
                                                self.ctx.seed)
        if not contexts:
            return
        t0 = time.perf_counter()
        for context in contexts:
            self.router.submit(context, model=MODEL_ID, max_new_tokens=1,
                               temperature=0.0)
        self.router.run()
        self.router.take_outputs()
        held = sum(self.engine.pool.prefix_match_len(
            np.append(c, 0)) for c in contexts)
        self.ctx.log(f"sessions: {len(contexts)} contexts, "
                     f"{sum(c.size for c in contexts)} tokens served in "
                     f"{time.perf_counter() - t0:.1f}s, {held} of them held "
                     f"by the prefix cache")

    def _ramp(self):
        """The same closed loop as the window, until ``ramp_finished``
        requests have finished; each caller's first request has its output
        cut to a share of its own, so phases are scattered as in steady
        state (harness/traffic.py)."""
        t0 = time.perf_counter()
        for c, stream in enumerate(self.streams):
            self._submit(c, *stream.first_ramp())
        want = int(self.traffic["ramp_finished"])
        while self._n_finished() < want:
            self._loop_once()
        self.ctx.log(f"ramp: {self._n_finished()} finished in "
                     f"{time.perf_counter() - t0:.1f}s, "
                     f"{self.engine.stats['steps']} engine steps so far")

    # ------------------------------------------------------- the loop
    def _n_finished(self) -> int:
        return sum(1 for r in self.reqs.values() if r.finish is not None)

    def _submit(self, caller: int, prompt: np.ndarray, n_out: int):
        with jax.profiler.TraceAnnotation("bench.submit"):
            t = time.perf_counter()
            rid = self.router.submit(prompt, model=MODEL_ID,
                                     max_new_tokens=n_out, temperature=0.0,
                                     stream_cb=self._on_token)
        self.reqs[rid] = _Req(caller, prompt,
                              int(self.streams[caller].context.size), n_out, t)

    def _on_token(self, rid, token, finished, seq):
        now = time.perf_counter()
        r = self.reqs[rid]
        if token is not None:
            if seq != len(r.tokens) or r.finish is not None:
                r.faults += 1
            r.tokens.append(int(token))
            r.times.append(now)
        if finished:
            if r.finish is not None or seq != len(r.tokens):
                r.faults += 1
            r.finish, r.t_done = str(finished), now
            self.idle.append(r.caller)

    def _loop_once(self):
        while self.idle:
            c = self.idle.pop()
            self._submit(c, *self.streams[c].next())
        with jax.profiler.TraceAnnotation("bench.router_step"):
            self.router.step()
        eng = self.engine
        self.samples.append((time.perf_counter(), int(eng.stats["steps"]),
                             int(eng.stats["running_seqs"]),
                             int(eng.pool.used_pages)))
        self.router.take_outputs()

    # ---------------------------------------------------------- window
    def window(self, seconds: float) -> Dict[str, Any]:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            self._loop_once()
        t1 = time.perf_counter()
        return self._account(t0, t1)

    def _account(self, t0: float, t1: float) -> Dict[str, Any]:
        inside = lambda t: t0 <= t <= t1   # noqa: E731
        tokens = 0
        ttft, gaps, prefills, cached, decode_ctx = [], [], [], [], []
        finished, failed = [], 0
        for r in self.reqs.values():
            p = int(r.prompt.size)
            for i, t in enumerate(r.times):
                if not inside(t):
                    continue
                if i == 0:   # the new turn counts, its session's context not
                    tokens += p - r.cached + 1
                    ttft.append(t - r.t_submit)
                    prefills.append(p - r.cached)
                    cached.append(r.cached)
                else:
                    tokens += 1
                    decode_ctx.append(p + i)
                    if inside(r.times[i - 1]):
                        gaps.append(t - r.times[i - 1])
            if r.t_done is not None and inside(r.t_done):
                finished.append(r)
                if (r.finish != "length" or r.faults
                        or len(r.tokens) != r.n_out):
                    failed += 1
        steps = [s for s in self.samples if inside(s[0])]
        waits, matched, offered = self._admissions(t0, t1)
        now = self.engine.compile_counts()
        self.finished = finished
        return {
            "kind": KIND, "wall_s": t1 - t0,
            "tokens": tokens, "ttft_s": ttft, "itl_s": gaps,
            "prefill_lens": prefills, "prefill_cached": cached,
            "decode_contexts": decode_ctx,
            "attempted": len(finished), "failed": failed,
            "engine_steps": (steps[-1][1] - steps[0][1] + 1) if steps else 0,
            "running_per_step": [s[2] for s in steps],
            "pages_per_step": [s[3] for s in steps],
            "slots": int(self.serving["max_batch_slots"]),
            "num_pages": self.num_pages, "queue_wait_s": waits,
            "prefix_matched_tokens": matched,
            "session_tokens_offered": offered,
            "compiled_in_window": now["step"] - self.compiles_before["step"],
        }

    def _admissions(self, t0: float, t1: float):
        """From the program's request-trace ring, for the requests admitted
        in the window: submit -> admit waits, the prompt tokens that the
        prefix cache covered, and the session tokens those prompts held."""
        enq: Dict[Any, float] = {}
        waits, matched, offered = [], 0, 0
        for e in self.tracer.events():
            if e["name"] == "req.enqueue":
                enq[e["req_id"]] = e["t"]
            elif e["name"] == "req.admit" and t0 <= e["t"] <= t1 \
                    and e["req_id"] in enq and e["req_id"] in self.reqs:
                waits.append(e["t"] - enq[e["req_id"]])
                matched += int(e.get("arg") or 0)
                offered += self.reqs[e["req_id"]].cached
        return waits, matched, offered

    # --------------------------------------------------------- release
    def release(self):
        self.router = self.engine = self.tracer = None
        gc.collect()

    # ---------------------------------------------------------- verify
    def sample(self) -> List[_Req]:
        """Requests the window finished, drawn from the seed, the longest
        among them, until they hold ``check_tokens`` served tokens."""
        done = [r for r in self.finished if r.finish == "length" and r.tokens]
        if not done:
            return []
        done.sort(key=lambda r: (r.t_done, r.caller))
        rng = np.random.default_rng([int(self.ctx.seed), 4])
        order = [done[i] for i in rng.permutation(len(done))]
        longest = max(done, key=lambda r: r.prompt.size + len(r.tokens))
        picked = [longest] + [r for r in order if r is not longest]
        want, out, n = int(self.traffic["check_tokens"]), [], 0
        for r in picked:
            out.append(r)
            n += len(r.tokens)
            if n >= want:
                break
        return out

    def verify(self, record, limits: Dict[str, float],
               control: Optional[str] = None,
               alter_token: bool = False) -> List[Compared]:
        """Served greedy tokens against one float32 pass of the reference
        over each sampled prompt with its served tokens."""
        picked = self.sample()
        if not picked:
            return [Compared("served_tokens_compared", 0.0, None),
                    Compared("served_gap", float("inf"),
                             limits.get("served_gap", 0.0))]
        weights = self.family.make_weights(self.cfg, self.ctx.seed)
        pairs = [(r.prompt, list(r.tokens)) for r in picked]
        if alter_token:   # the planted fault: one served token is another
            toks = pairs[-1][1]
            toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % self.vocab
        gaps, exact = served_gaps(self.family.logits_at, weights, self.cfg,
                                  pairs, control=control)
        return [Compared("served_tokens_compared", float(gaps.size), None),
                Compared("served_exact_share", exact / gaps.size, None),
                Compared("served_gap", float(gaps.max()),
                         limits.get("served_gap"))]

    def proofs(self, limits: Dict[str, float]) -> Dict[str, List[Compared]]:
        """The controls (at each position of the same prompts and tokens, the
        token that the reference in a lower precision puts first) and the
        planted fault (one served token altered), judged as the served
        tokens are."""
        out = {f"control_{c}": self.verify(None, limits, control=c)
               for c in self.family.CONTROLS}
        out["fault_token_altered"] = self.verify(None, limits,
                                                 alter_token=True)
        return out


def paged_kv_pool_args(model, serving: Dict[str, Any]) -> Dict[str, Any]:
    """``pool_args`` of a family whose only state is keys and values in
    bfloat16 pages: as many pages as ``kv_pool_bytes`` buys for the model's
    own (layers, kv heads, head size), and the rest of the ``serving``
    block as ``Router.add_model`` takes it. A family with other state sizes
    its pool itself."""
    import jax.numpy as jnp
    from paddle_tpu.serving import pages_for_hbm_budget

    if serving["kv_dtype"] != "bfloat16":
        raise ValueError("bfloat16 pages only")
    n_layers, n_kv, head_dim = model._cache_spec()
    num_pages = pages_for_hbm_budget(
        int(serving["kv_pool_bytes"]), int(serving["page_size"]), n_kv,
        head_dim, n_layers, kv_dtype="bfloat16")
    return dict(kv_dtype=jnp.bfloat16, page_size=int(serving["page_size"]),
                max_model_len=int(serving["max_model_len"]),
                num_pages=num_pages,
                max_batch_slots=int(serving["max_batch_slots"]),
                token_budget=int(serving["token_budget"]),
                prefix_cache=bool(serving["prefix_cache"]))


# ------------------------------------------------ served tokens vs reference
def served_gaps(logits_at, weights, cfg,
                requests: Sequence[Tuple[np.ndarray, Sequence[int]]],
                rows_per_block: int = 4, control: Optional[str] = None):
    """For each (prompt, served tokens) run the reference (``logits_at``, the
    family's: float32 logits at given rows of a causal forward) once over
    prompt + tokens and read, at every served position, how far the served
    token's float32 logit lies below the reference's best, in units of that
    row's logit standard deviation. Returns (gaps [n_tokens], exact matches).

    With ``control`` (a lower precision) the token judged at each position is
    the one that precision puts first on the same prefix, not the served one.
    """
    width = max(len(p) + len(t) for p, t in requests)
    width = -(-width // 128) * 128
    gaps: List[np.ndarray] = []
    exact = 0
    for lo in range(0, len(requests), rows_per_block):
        blk = list(requests[lo:lo + rows_per_block])
        while len(blk) < rows_per_block:   # one compiled shape per cell
            blk.append((blk[0][0][:1], []))
        ids = np.zeros((len(blk), width), np.int32)
        rows, toks = [], []
        for r, (prompt, served) in enumerate(blk):
            seq = np.concatenate([np.asarray(prompt, np.int32),
                                  np.asarray(served, np.int32)])
            ids[r, :seq.size] = seq   # right-padded: causal rows never see it
            for j, tok in enumerate(served):
                rows.append((r, len(prompt) + j - 1))
                toks.append(int(tok))
        if not rows:
            continue
        pad = -len(rows) % 64   # few distinct row counts, so few compiles
        rows_p = np.asarray(rows + [rows[0]] * pad, np.int32)
        ref = np.asarray(logits_at(weights, ids, rows_p, cfg, "f32"))
        ref = ref[:len(rows)]
        judged = np.asarray(toks)
        if control is not None:
            low = np.asarray(logits_at(weights, ids, rows_p, cfg, control))
            judged = np.argmax(low[:len(rows)], axis=-1)
        best = ref.max(axis=-1)
        got = ref[np.arange(len(rows)), judged]
        gaps.append((best - got) / ref.std(axis=-1))
        exact += int(np.sum(got == best))
    return np.concatenate(gaps) if gaps else np.zeros(0), exact
