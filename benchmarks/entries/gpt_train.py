"""Training entry for GPT configurations: the program's compiled train step
(``jit.StaticFunction`` over ``GPTForCausalLM`` under ``amp`` O2 bfloat16
with AdamW and the fused chunked cross-entropy), driven by seeded batches.

Set-up builds ONE object — the compiled step with its state — drives it
through its first steps (which the reference later follows) and hands the
same object to the window.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..families.gpt import model as gpt_common, reference, weights as W
from ..harness import traffic as traffic_mod
from ..harness.compare import Compared, worst_leaf_gap

KIND = "train"
FOLLOWED_STEPS = 3      # the reference follows these
IN_FLIGHT = 2           # steps dispatched ahead of the one waited for
# The gaps between norms are second order in a random error, so rounding to a
# lower precision barely moves them. One leaf's first gradient is therefore
# also compared element by element: the position embedding's, which sits at
# the bottom of the backward pass and so carries the error of the whole
# forward and backward (8 MB at 1.3 B, kept on the host through the window).
PROBE_LEAF = "wpe"


@jax.jit
def _norms(arrays):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in arrays])


@jax.jit
def _diff_norms(now, start):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        for a, b in zip(now, start)])


class TrainRun:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.hp = dict(self.cfg["training"]["adamw"])
        self.traffic = ctx.cell.traffic
        self.first: Dict[str, Any] = {}

    # ---------------------------------------------------------- set-up
    def setup(self):
        import paddle_tpu as paddle
        from paddle_tpu import amp, jit
        from paddle_tpu.models import GPTForCausalLM

        ctx, cfg, hp = self.ctx, self.cfg, self.hp
        model = GPTForCausalLM(gpt_common.gpt_config(cfg))
        opt = paddle.optimizer.AdamW(
            learning_rate=hp["learning_rate"], beta1=hp["beta1"],
            beta2=hp["beta2"], epsilon=hp["epsilon"],
            weight_decay=hp["weight_decay"], parameters=model.parameters())
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16",
                                  master_weight=False)
        gpt_common.load_weights(model, W.make_weights(cfg, ctx.seed))
        ctx.log(f"model built, seeded weights loaded "
                f"({ctx.since_start():.1f}s)")

        def train_fn(ids, labels):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                _, loss = model(ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        self.paddle = paddle
        self.model, self.opt = model, opt
        self.step = jit.StaticFunction(train_fn, observe=[model, opt],
                                       warmup=False)
        self.feed = traffic_mod.train_batches(self.traffic,
                                              int(cfg["vocab_size"]), ctx.seed)
        self.leaves = list(gpt_common.leaf_parameters(model))
        self.names = [name for name, _ in self.leaves]

        # the first steps, through the window's own call and feed
        losses = []
        for i in range(FOLLOWED_STEPS + 1):
            t0 = time.perf_counter()
            loss = self._one_step()
            jax.block_until_ready(loss)
            losses.append(loss)
            ctx.log(f"step {i + 1}: {time.perf_counter() - t0:.2f}s")
            if i == 0:
                b1 = self.hp["beta1"]
                m1 = [opt._accumulators[p._uid]["moment1"]
                      for _, p in self.leaves]
                self.first["grad_norms"] = np.asarray(_norms(m1)) / (1 - b1)
                self.first["probe_grad"] = np.asarray(
                    m1[self.names.index(PROBE_LEAF)],
                    np.float32) / (1 - b1)
            if i == FOLLOWED_STEPS - 1:
                self.first["change_norms"] = self._change_norms()
        self.first["losses"] = [float(np.asarray(x, np.float32))
                                for x in losses[:FOLLOWED_STEPS]]
        n_compiled = len(self.step._cache)
        if n_compiled != 1:
            raise RuntimeError(f"train step compiled {n_compiled} signatures")
        self.compiles_before = n_compiled

    def _change_norms(self) -> np.ndarray:
        """Per-leaf ‖p_now − p_start‖ in ``self.names`` order. The start is
        made again from the seed a layer at a time, so that set-up's peak
        stays under the step's own."""
        cfg, seed = self.cfg, self.ctx.seed
        now = {name: p._value for name, p in self.leaves}
        out: Dict[str, float] = {}

        def diff(prefix, start):
            keys = sorted(start)
            norms = np.asarray(_diff_norms([now[prefix + k] for k in keys],
                                           [start[k] for k in keys]))
            out.update({prefix + k: float(n) for k, n in zip(keys, norms)})

        diff("", W.make_top(cfg, seed))
        for layer in range(int(cfg["num_hidden_layers"])):
            diff(f"L{layer}.", W.make_layer(cfg, seed, layer))
        return np.asarray([out[n] for n in self.names])

    def _one_step(self):
        ids, labels = next(self.feed)
        with jax.profiler.TraceAnnotation("bench.train_step_dispatch"):
            loss = self.step(self.paddle.to_tensor(ids),
                             self.paddle.to_tensor(labels))
        return loss.value

    # ---------------------------------------------------------- window
    def window(self, seconds: float) -> Dict[str, Any]:
        pending = collections.deque()
        losses: List[Any] = []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            loss = self._one_step()
            losses.append(loss)
            pending.append(loss)
            if len(pending) > IN_FLIGHT:
                with jax.profiler.TraceAnnotation("bench.wait_step"):
                    jax.block_until_ready(pending.popleft())
        with jax.profiler.TraceAnnotation("bench.wait_step"):
            jax.block_until_ready(losses[-1])
        t1 = time.perf_counter()
        vals = [float(np.asarray(x, np.float32)) for x in losses]
        b, s = int(self.traffic["batch"]), int(self.traffic["seq_len"])
        return {"kind": KIND, "wall_s": t1 - t0,
                "steps": len(vals), "tokens": len(vals) * b * s,
                "batch": b, "seq_len": s,
                "attempted": len(vals),
                "failed": int(sum(not np.isfinite(v) for v in vals)),
                "compiled_in_window":
                    len(self.step._cache) - self.compiles_before}

    # --------------------------------------------------------- release
    def release(self):
        self.step = self.model = self.opt = self.leaves = self.feed = None
        gc.collect()

    # ---------------------------------------------------------- verify
    def _follow(self, **kw):
        """A reference (or a control, or a planted fault) through the first
        FOLLOWED_STEPS batches of the window's feed: its losses, the norms of
        its first gradient and of its parameters' change, its probe leaf."""
        ref = reference.TrainReference(self.cfg, self.hp, self.ctx.seed, **kw)
        feed = traffic_mod.train_batches(
            self.traffic, int(self.cfg["vocab_size"]), self.ctx.seed)
        losses = [ref.step(*next(feed)) for _ in range(FOLLOWED_STEPS)]
        return losses, dict(ref.grad_norms), ref.change_norms(), ref.probe_grad

    def verify(self, record, limits: Dict[str, float]) -> List[Compared]:
        """The first FOLLOWED_STEPS steps against the plain reference."""
        self.ref_readings = self._follow()
        return compare_first_steps(self.first, *self.ref_readings,
                                   self.names, limits)

    def proofs(self, limits: Dict[str, float]) -> Dict[str, List[Compared]]:
        """The controls (the reference in int8 and in fp8, put in the
        program's place) and the planted fault (half of the batch left out,
        the mean over the rest), each judged as a run of the program is.
        Not part of a benchmark run: ``benchmarks/proofs.py`` asks for it."""
        out = {}
        for label, kw in (("control_int8", {"precision": "int8"}),
                          ("control_fp8", {"precision": "fp8"}),
                          ("fault_half_batch", {"fault": "half_batch"})):
            losses, grads, change, probe = self._follow(**kw)
            first = {"losses": losses, "probe_grad": probe,
                     "grad_norms": [grads[n] for n in self.names],
                     "change_norms": [change[n] for n in self.names]}
            out[label] = compare_first_steps(first, *self.ref_readings,
                                             self.names, limits)
        return out


def compare_first_steps(first, ref_losses, ref_grads, ref_change, ref_probe,
                        names, limits) -> List[Compared]:
    """The numbers compared, each beside its limit (harness/compare.py has
    the measure). ``first`` holds the program's readings in ``names`` order;
    the reference's are keyed by leaf name."""
    out = []
    for i, (a, b) in enumerate(zip(first["losses"], ref_losses)):
        out.append(Compared(f"loss{i + 1}_rel", abs(a - b) / abs(b),
                            limits.get("loss_rel")))
    g_ref = np.asarray([ref_grads[n] for n in names])
    out.append(Compared("grad_norm_gap", worst_leaf_gap(
        np.asarray(first["grad_norms"]), g_ref), limits.get("grad_norm_gap")))
    probe = np.asarray(first["probe_grad"], np.float64)
    ref_probe = np.asarray(ref_probe, np.float64)
    out.append(Compared(
        f"grad_diff_{PROBE_LEAF}",
        float(np.linalg.norm(probe - ref_probe) / np.linalg.norm(ref_probe)),
        limits.get(f"grad_diff_{PROBE_LEAF}")))
    c_ref = np.asarray([ref_change[n] for n in names])
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: out of the change by a rule on the gradient
    moved = g_ref >= 1e-3 * np.median(g_ref)
    out.append(Compared("change_norm_gap", worst_leaf_gap(
        np.asarray(first["change_norms"])[moved], c_ref[moved]),
        limits.get("change_norm_gap")))
    return out


def build(ctx) -> TrainRun:
    return TrainRun(ctx)
