"""Cross-process restart drills: kill a WAL-armed serving fleet, bring
it back, prove nothing was lost (ISSUE 20).

The in-process chaos scenarios can fake an engine death, but the
durability contract — exactly-once streams across PROCESS death — only
means something when the process actually dies. This module is both
halves of that drill:

- **Child** (``python -m paddle_tpu.loadgen.restart ...``): builds a
  deterministic tiny-Llama fleet behind ``Router(wal_dir=...)``, replays
  a seeded :func:`~paddle_tpu.loadgen.trace.generate_trace` workload,
  and appends every delivered stream chunk as one JSON line to a
  ``chunks.jsonl`` file — the file IS the client, and a line in it is a
  delivery (commit-then-emit means the WAL always holds what the file
  holds). ``--recover`` mode rebuilds the fleet (possibly with a
  different replica count), calls :meth:`Router.recover`, re-attaches
  each journaled stream at the parent-supplied ``after_seq`` cursor,
  drains, and writes a timing JSON (replay/readmit latency, time to
  first recovered token, ``jit_compiles_total{source="fresh"}``).
- **Parent** (:func:`run_restart_drill`): spawns the fresh child over a
  shared compile-cache dir, SIGKILLs it once the chunks file shows
  mid-stream progress, restarts with fewer engines, and returns the
  pre/post chunk streams plus an UNINTERRUPTED reference run — the
  assertions (bit-identical concatenation, gapless seqs, zero fresh
  compiles during recovery) live in the callers:
  tools/chaos_serve.py scenario ``kill-serving-process-mid-decode`` and
  ``tools/bench_load.py --restart`` (docs/RESILIENCE.md "Durability").

Determinism across the kill: both processes seed identically
(``paddle.seed`` + per-request ``Request.seed`` from the trace), so the
recovered decode regenerates the exact tokens the dead process would
have produced — the drill compares BYTES, not shapes.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

__all__ = ["SEED", "build_model", "build_router", "serve",
           "spawn_serve", "read_chunks", "read_manifest",
           "cursors_from_chunks", "wait_for_chunk_lines",
           "run_restart_drill", "streams_by_index"]

SEED = 20                       # ISSUE number, like the chaos drills
MODEL_ID = "m"

# trace knobs shared by every process in a drill: small enough for CPU,
# shaped enough to exercise prefix sharing + mixed lengths
_TRACE_KW = dict(seed=SEED, vocab_size=96, num_prompt_families=3,
                 prefix_len=6, max_prompt_len=20, suffix_len_mean=4.0,
                 output_len_mean=6.0, output_len_sigma=0.4,
                 max_output_len=10, temperature=0.8)

_ENGINE_KW = dict(page_size=4, max_batch_slots=2, token_budget=32,
                  watchdog_stall_s=None)


def build_model():
    """The drill model, identical in every process that calls this:
    ``paddle.seed(SEED)`` pins the init stream, the config pins the
    architecture — two processes building it decode bit-identically."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    paddle.seed(SEED)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=96, hidden_size=32, num_layers=2, num_heads=2,
        num_key_value_heads=1, max_position_embeddings=64))


def build_router(wal_dir: Optional[str], replicas: int,
                 compile_cache_dir: Optional[str] = None):
    """A drill fleet: ``replicas`` engines of the deterministic model,
    WAL-armed when ``wal_dir`` is given, sharing one persistent compile
    cache so a restarted process loads XLA programs from disk instead of
    paying fresh compiles mid-recovery."""
    from paddle_tpu.serving import Router
    router = Router(wal_dir=wal_dir)
    router.add_model(MODEL_ID, build_model(), replicas=replicas,
                     compile_cache_dir=compile_cache_dir, **_ENGINE_KW)
    return router


def serve(wal_dir: str, chunks_path: str, manifest_path: str,
          replicas: int, compile_cache_dir: Optional[str] = None,
          num_requests: int = 8, recover: bool = False,
          cursors: Optional[Dict[int, int]] = None,
          timing_path: Optional[str] = None) -> dict:
    """The child body (also callable in-process for unit tests).

    Fresh mode: generate the seeded trace, submit everything through the
    WAL-armed router, drive ``step()`` until drained, sealing via
    :meth:`Router.shutdown`. Every delivered chunk appends one
    line-buffered JSON record ``{"idx", "wal", "tok", "fin", "seq"}`` to
    ``chunks_path``; ``manifest_path`` gets one ``{"idx", "wal"}`` line
    per admission (flushed at submit, so the recovering process can map
    journaled WAL ids back to trace indices even after a SIGKILL).

    Recover mode: rebuild the fleet (``replicas`` may differ from the
    dead process), :meth:`Router.recover`, re-attach each manifest
    stream at ``cursors[wal_id]`` (the last seq the chunks file holds —
    exactly-once replay starts AFTER it), drain, and write
    ``timing_path``: recover/replay latency, time to first recovered
    token, fresh-compile count, per-outcome tallies."""
    import numpy as np
    from paddle_tpu import metrics
    from paddle_tpu.loadgen.trace import TraceConfig, generate_trace

    t_start = time.perf_counter()
    router = build_router(wal_dir, replicas,
                          compile_cache_dir=compile_cache_dir)
    chunks_f = open(chunks_path, "a", buffering=1)
    timing: dict = {"mode": "recover" if recover else "fresh",
                    "replicas": replicas, "first_token_s": None}

    def _cb(idx: int, wal_cell: list):
        def cb(rid, tok, fin, seq):
            if timing["first_token_s"] is None:
                timing["first_token_s"] = time.perf_counter() - t_start
            chunks_f.write(json.dumps(
                {"idx": idx, "wal": wal_cell[0],
                 "tok": None if tok is None else int(tok),
                 "fin": fin if fin else None, "seq": int(seq)}) + "\n")
        return cb

    if not recover:
        trace = generate_trace(TraceConfig(
            num_requests=num_requests, **_TRACE_KW))
        with open(manifest_path, "a", buffering=1) as man:
            for tr in trace.requests:
                cell = [None]
                rid = router.submit(
                    np.asarray(tr.prompt, np.int32), model=MODEL_ID,
                    max_new_tokens=tr.max_new_tokens,
                    temperature=tr.temperature, seed=tr.seed,
                    priority=tr.priority, stream_cb=_cb(tr.index, cell))
                cell[0] = router.wal_id_of(rid)
                man.write(json.dumps(
                    {"idx": tr.index, "wal": cell[0]}) + "\n")
        while router.has_work:
            router.step()
        router.shutdown()
    else:
        cursors = cursors or {}
        res = router.recover()
        timing["recover_s"] = time.perf_counter() - t_start
        timing["outcomes"] = {}
        for r in res.values():
            o = r["outcome"]
            timing["outcomes"][o] = timing["outcomes"].get(o, 0) + 1
        for idx, wal in read_manifest(manifest_path):
            cell = [wal]
            router.attach_stream(wal, _cb(idx, cell),
                                 after_seq=int(cursors.get(wal, -1)))
        while router.has_work:
            router.step()
        router.shutdown()
        fam = metrics.get_registry().get("paddle_tpu_jit_compiles_total")
        timing["fresh_compiles"] = (
            0 if fam is None else int(fam.sum_labels(source="fresh")))
    timing["total_s"] = time.perf_counter() - t_start
    chunks_f.close()
    if timing_path is not None:
        with open(timing_path, "w") as f:
            json.dump(timing, f, indent=2, sort_keys=True)
    return timing


# ---------------------------------------------------------------- parent
def spawn_serve(wal_dir: str, chunks_path: str, manifest_path: str,
                replicas: int, compile_cache_dir: Optional[str] = None,
                num_requests: int = 8, recover: bool = False,
                cursors: Optional[Dict[int, int]] = None,
                timing_path: Optional[str] = None) -> subprocess.Popen:
    """Launch :func:`serve` in a CHILD python (the process the drill
    kills). CPU-pinned like every subprocess lane: a child never needs
    the chip its parent may hold."""
    argv = [sys.executable, "-m", "paddle_tpu.loadgen.restart",
            "--wal-dir", wal_dir, "--chunks", chunks_path,
            "--manifest", manifest_path, "--replicas", str(replicas),
            "--num-requests", str(num_requests)]
    if compile_cache_dir is not None:
        argv += ["--compile-cache-dir", compile_cache_dir]
    if recover:
        argv += ["--recover"]
    if cursors:
        argv += ["--cursors", json.dumps(
            {str(k): v for k, v in cursors.items()})]
    if timing_path is not None:
        argv += ["--timing", timing_path]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)


def read_chunks(path: str) -> List[dict]:
    """Parse a chunks file, tolerating the torn final line a SIGKILL
    mid-``write`` can leave (exactly the torn-tail discipline the WAL
    itself applies)."""
    out: List[dict] = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            try:
                out.append(json.loads(line))
            except ValueError:
                break               # torn tail: everything before it holds
    return out


def read_manifest(path: str) -> List[Tuple[int, int]]:
    """``[(trace index, wal_id), ...]`` — same torn-tail tolerance."""
    return [(c["idx"], c["wal"]) for c in read_chunks(path)]


def cursors_from_chunks(chunks: List[dict]) -> Dict[int, int]:
    """The exactly-once resume cursors: last seq delivered per WAL id."""
    cur: Dict[int, int] = {}
    for c in chunks:
        w = c["wal"]
        if w is not None:
            cur[w] = max(cur.get(w, -1), int(c["seq"]))
    return cur


def wait_for_chunk_lines(path: str, n: int, timeout_s: float = 120.0,
                         proc: Optional[subprocess.Popen] = None) -> int:
    """Poll until ``path`` holds >= n chunk lines (the parent's
    mid-stream trigger); returns the count seen. Raises if the child
    exits first or the timeout lapses — a drill that can't reach
    mid-stream must fail loudly, not hang."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = len(read_chunks(path))
        if got >= n:
            return got
        if proc is not None and proc.poll() is not None:
            tail = proc.stdout.read().decode(errors="replace")[-2000:]
            raise RuntimeError(
                f"child exited rc={proc.returncode} before producing "
                f"{n} chunks (saw {got}):\n{tail}")
        time.sleep(0.05)
    raise TimeoutError(f"no {n} chunks within {timeout_s}s "
                       f"(saw {len(read_chunks(path))})")


def run_restart_drill(workdir: str, replicas_before: int = 2,
                      replicas_after: int = 1, num_requests: int = 6,
                      kill_after_chunks: int = 8,
                      timeout_s: float = 300.0) -> dict:
    """The full kill-the-process drill. Three child runs over one
    ``workdir``:

    1. ``ref/``  — uninterrupted WAL-armed run: the byte truth.
    2. ``live/`` — same workload, SIGKILLed once ``kill_after_chunks``
       chunks landed (mid-decode by construction: the trigger is
       strictly less than the reference total).
    3. ``live/`` recover — ``replicas_after`` engines adopt the WAL,
       resuming each stream after the cursor the chunks file proves
       delivered.

    Returns the raw material for the callers' asserts: per-index
    reference streams, pre-kill + post-recovery streams, the recover
    child's timing JSON, and the parent-measured ``rto_s``
    (SIGKILL instant → first recovered chunk landing in the file)."""
    ref_dir = os.path.join(workdir, "ref")
    live_dir = os.path.join(workdir, "live")
    cache = os.path.join(workdir, "xla-cache")
    for d in (ref_dir, live_dir, cache):
        os.makedirs(d, exist_ok=True)
    paths = {
        tag: {"wal": os.path.join(d, "wal"),
              "chunks": os.path.join(d, "chunks.jsonl"),
              "manifest": os.path.join(d, "manifest.jsonl"),
              "timing": os.path.join(d, "timing.json")}
        for tag, d in (("ref", ref_dir), ("live", live_dir))}
    for p in paths.values():
        os.makedirs(p["wal"], exist_ok=True)

    # 1. the uninterrupted reference (also warms the shared XLA cache)
    ref = paths["ref"]
    proc = spawn_serve(ref["wal"], ref["chunks"], ref["manifest"],
                       replicas=replicas_before,
                       compile_cache_dir=cache,
                       num_requests=num_requests,
                       timing_path=ref["timing"])
    out, _ = proc.communicate(timeout=timeout_s)
    if proc.returncode != 0:
        raise RuntimeError(f"reference run failed rc={proc.returncode}:"
                           f"\n{out.decode(errors='replace')[-2000:]}")
    ref_chunks = read_chunks(ref["chunks"])
    if kill_after_chunks >= len(ref_chunks):
        raise ValueError(
            f"kill_after_chunks={kill_after_chunks} >= reference total "
            f"{len(ref_chunks)}: the kill would not be mid-decode")

    # 2. the doomed run: SIGKILL once mid-stream
    live = paths["live"]
    proc = spawn_serve(live["wal"], live["chunks"], live["manifest"],
                       replicas=replicas_before,
                       compile_cache_dir=cache,
                       num_requests=num_requests)
    wait_for_chunk_lines(live["chunks"], kill_after_chunks,
                         timeout_s=timeout_s, proc=proc)
    t_kill = time.monotonic()
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=60)
    pre_chunks = read_chunks(live["chunks"])

    # 3. recover on a smaller fleet, resuming after the proven cursors
    n_pre = len(pre_chunks)
    proc = spawn_serve(live["wal"], live["chunks"], live["manifest"],
                       replicas=replicas_after,
                       compile_cache_dir=cache,
                       num_requests=num_requests, recover=True,
                       cursors=cursors_from_chunks(pre_chunks),
                       timing_path=live["timing"])
    rto_s = None
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if len(read_chunks(live["chunks"])) > n_pre:
            rto_s = time.monotonic() - t_kill
            break
        if proc.poll() is not None:
            break
        time.sleep(0.02)
    out, _ = proc.communicate(timeout=timeout_s)
    if proc.returncode != 0:
        raise RuntimeError(f"recovery run failed rc={proc.returncode}:"
                           f"\n{out.decode(errors='replace')[-2000:]}")
    all_chunks = read_chunks(live["chunks"])
    with open(live["timing"]) as f:
        timing = json.load(f)
    return {"ref_chunks": ref_chunks, "pre_chunks": pre_chunks,
            "post_chunks": all_chunks[n_pre:], "timing": timing,
            "rto_s": rto_s, "manifest": read_manifest(live["manifest"]),
            "killed_after": n_pre}


def streams_by_index(chunks: List[dict]) -> Dict[int, List[tuple]]:
    """Fold a chunk list into per-trace-index ``(tok, fin, seq)``
    streams, preserving delivery order — the unit the drill compares."""
    out: Dict[int, List[tuple]] = {}
    for c in chunks:
        out.setdefault(c["idx"], []).append(
            (c["tok"], c["fin"], c["seq"]))
    return out


def _main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--wal-dir", required=True)
    ap.add_argument("--chunks", required=True)
    ap.add_argument("--manifest", required=True)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--compile-cache-dir", default=None)
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--recover", action="store_true")
    ap.add_argument("--cursors", default=None,
                    help="JSON {wal_id: last_seq} resume cursors")
    ap.add_argument("--timing", default=None)
    args = ap.parse_args(argv)
    cursors = None
    if args.cursors:
        cursors = {int(k): int(v)
                   for k, v in json.loads(args.cursors).items()}
    serve(args.wal_dir, args.chunks, args.manifest, args.replicas,
          compile_cache_dir=args.compile_cache_dir,
          num_requests=args.num_requests, recover=args.recover,
          cursors=cursors, timing_path=args.timing)
    return 0


if __name__ == "__main__":
    sys.exit(_main())
