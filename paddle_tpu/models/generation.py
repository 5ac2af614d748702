"""Autoregressive generation with KV caches — shared by the LLM zoo.

Reference ecosystem parity: PaddleNLP's GenerationMixin.generate (the
reference repo ships only ops; the LLM zoo is first-class here,
models/__init__.py).

TPU-native shape: ONE compiled prefill program (prompt length) and ONE
compiled decode program reused for every step. The cache write position
rides in as DATA (``lax.dynamic_update_slice`` with a tensor index), so
there is no per-position recompilation; greedy (temperature=0) or
temperature/top-k sampling runs inside the compiled step via
``jax.random.categorical`` on a threaded PRNG key.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..ops._apply import apply_op, ensure_tensor
from ..tensor import Tensor

__all__ = ["GenerationMixin"]


class GenerationMixin:
    """Requires on the host class:
    - ``_decode_trunk()`` → trunk module whose forward accepts
      ``(ids, caches=..., cur_len=...)`` and returns (hidden, new_caches)
    - ``logits(hidden)`` → [B, S, V]
    - ``_cache_spec()`` → (num_layers, cached_heads, head_dim)
    - ``config.max_position_embeddings``
    """

    @staticmethod
    def _sample(logits_row, temperature, top_k, key):
        """One sampling step, pure jnp: [B, V] logits -> [B] token ids."""
        if temperature == 0.0:
            return jnp.argmax(logits_row, axis=-1).astype(jnp.int32)
        logits_row = logits_row / jnp.float32(max(temperature, 1e-6))
        if top_k:
            kth = jnp.sort(logits_row, axis=-1)[:, -int(top_k)][:, None]
            logits_row = jnp.where(logits_row < kth, -1e30, logits_row)
        return jax.random.categorical(key, logits_row,
                                      axis=-1).astype(jnp.int32)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 device_loop: Optional[bool] = None,
                 return_stats: bool = False):
        """Returns [B, prompt+generated] token ids (generation stops early
        when every row emitted ``eos_token_id``).

        ``return_stats=True`` returns ``(ids, stats)`` instead, where
        ``stats`` is ``{"n_gen": tokens generated per row (incl. eos
        padding), "stop_reason": "eos" | "length"}`` — "eos" when every
        row finished on ``eos_token_id`` before the token budget ran out.
        The serving engine and the early-stop tests assert on it; the
        default keeps the old single-tensor return shape.

        EOS semantics (both loops, PaddleNLP/HF style): a row that emits
        ``eos_token_id`` is frozen — every later position in that row is
        filled with ``eos_token_id`` — and generation stops once ALL rows
        have finished (or at ``max_new_tokens``).

        ``device_loop``: run the whole decode as ONE compiled program — a
        ``lax.while_loop`` whose carry holds the token buffer, KV caches,
        PRNG key, and per-row done flags — instead of one host-driven
        call per token. On TPU the host loop pays a device↔host round trip per
        token; the device loop pays one. Default: on for TPU backends,
        off elsewhere (the host loop is easier to debug and can stop the
        moment EOS lands instead of at the compiled cond check).
        """
        import time

        import numpy as np

        from .. import jit, metrics
        from ..autograd.engine import no_grad

        _gen_t0 = time.perf_counter()
        cfg = self.config
        trunk = self._decode_trunk()
        n_layers, nh_c, hd = self._cache_spec()
        ids = ensure_tensor(input_ids)
        B, S0 = ids.shape
        total = S0 + max_new_tokens
        if total > cfg.max_position_embeddings:
            raise ValueError(
                f"prompt {S0} + max_new_tokens {max_new_tokens} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        was_training = self.training
        self.eval()
        if device_loop is None:
            device_loop = jax.default_backend() == "tpu"

        def step_fn(tok, cur, key, *flat_caches):
            caches = [(flat_caches[2 * i], flat_caches[2 * i + 1])
                      for i in range(n_layers)]
            with no_grad():
                hidden, ncs = trunk(tok, caches=caches, cur_len=cur)
                logits = self.logits(hidden)
            last = apply_op(lambda lv: lv[:, -1, :].astype(jnp.float32),
                            [ensure_tensor(logits)], name="last_logits")
            nxt = apply_op(
                lambda lv, kv: self._sample(lv, temperature, top_k, kv),
                [last, ensure_tensor(key)], name="sample")
            flat = [t for c in ncs for t in c]
            return (nxt, *flat)

        step_fn.__name__ = "generate_step"  # jit_compiles_total{fn=...}

        # compiled prefill/decode are cached on the model per signature:
        # repeated generate() calls pay tracing+compilation once
        gen_key = (B, S0, total, float(temperature), int(top_k))
        cache_map = getattr(self, "_generation_programs", None)
        if cache_map is None:
            cache_map = self._generation_programs = {}
        progs = cache_map.get(gen_key)
        if progs is None:
            progs = (jit.StaticFunction(step_fn, observe=[self],
                                        warmup=False, dy2static=False),
                     jit.StaticFunction(step_fn, observe=[self],
                                        warmup=False, dy2static=False))
            cache_map[gen_key] = progs
        prefill, decode = progs

        flat = [t for _ in range(n_layers)
                for t in (Tensor(jnp.zeros((B, total, nh_c, hd),
                                           jnp.float32)),
                          Tensor(jnp.zeros((B, total, nh_c, hd),
                                           jnp.float32)))]
        rng_key = jax.random.PRNGKey(seed)
        out = [np.asarray(ids.numpy())]

        k0, rng_key = jax.random.split(rng_key)
        res = prefill(ids, Tensor(jnp.zeros((), jnp.int32)), Tensor(k0),
                      *flat)
        nxt, flat = res[0], list(res[1:])
        tokens = np.asarray(nxt.numpy()).reshape(B, 1)
        out.append(tokens)

        if device_loop and max_new_tokens > 1:
            # eos rides in as DATA (sentinel -1 = none): one compiled
            # program serves every stop id
            loop_key = ("loop",) + gen_key
            loop = cache_map.get(loop_key)
            if loop is None:
                loop = jit.StaticFunction(
                    self._make_device_loop(trunk, n_layers, B, S0,
                                           max_new_tokens, temperature,
                                           top_k),
                    observe=[self], warmup=False, dy2static=False)
                cache_map[loop_key] = loop
            k, rng_key = jax.random.split(rng_key)
            eos_t = Tensor(jnp.int32(eos_token_id
                                     if eos_token_id is not None else -1),
                           stop_gradient=True)
            buf, n_gen, all_done = loop(nxt, Tensor(k), eos_t, *flat)
            # one batched fetch — each host sync is a device round trip
            buf_v, n_v, done_v = jax.device_get(
                (buf._value, n_gen._value, all_done._value))
            out[-1] = np.asarray(buf_v)[:, :int(n_v)]
            stopped_on_eos = bool(done_v)
        else:
            done = (tokens[:, 0] == eos_token_id) if eos_token_id is not None \
                else np.zeros(B, bool)
            for step in range(1, max_new_tokens):
                if eos_token_id is not None and done.all():
                    break
                k, rng_key = jax.random.split(rng_key)
                res = decode(Tensor(jnp.asarray(tokens, jnp.int32)),
                             Tensor(jnp.asarray(S0 + step - 1, jnp.int32)),
                             Tensor(k), *flat)
                nxt, flat = res[0], list(res[1:])
                tokens = np.asarray(nxt.numpy()).reshape(B, 1)
                if eos_token_id is not None:
                    # frozen rows keep emitting eos (HF/PaddleNLP padding)
                    tokens = np.where(done[:, None], eos_token_id, tokens)
                    done = done | (tokens[:, 0] == eos_token_id)
                out.append(tokens)
            stopped_on_eos = bool(eos_token_id is not None and done.all())

        if was_training:
            self.train()
        ids_out = Tensor(jnp.asarray(np.concatenate(out, axis=1)))
        reg = metrics.get_registry()
        reg.histogram(
            "paddle_tpu_generate_seconds",
            "Whole dense generate() call (prefill + all decode steps, "
            "compile included on the first signature)",
        ).observe(time.perf_counter() - _gen_t0)
        reg.counter(
            "paddle_tpu_generate_tokens_total",
            "Tokens emitted by dense generate() across all rows",
        ).inc(B * (int(ids_out.shape[1]) - S0))
        if not return_stats:
            return ids_out
        stats = {"n_gen": int(ids_out.shape[1]) - S0,
                 "stop_reason": "eos" if stopped_on_eos else "length"}
        return ids_out, stats

    def _make_device_loop(self, trunk, n_layers, B, S0, max_new_tokens,
                          temperature, top_k):
        """Build the whole-decode-in-one-program fn: carry = (token buffer
        [B, max_new_tokens], count, PRNG key, stop, *flat KV caches);
        stops at the buffer end or when every row has emitted ``eos``
        (per-row freeze: finished rows pad with eos — the host loop's
        exact semantics). ``eos`` is a data operand (-1 = no stop id) so
        one program serves every stop id."""
        from ..autograd.engine import no_grad

        def loop_fn(first_tok, key, eos, *flat_caches):
            def run(tok0_v, key_v, eos_v, *cache_vals):
                eos_i = eos_v.astype(jnp.int32).reshape(())
                buf0 = jnp.zeros((B, max_new_tokens), jnp.int32)
                z0 = jnp.int32(0)
                buf0 = jax.lax.dynamic_update_slice(
                    buf0, tok0_v.reshape(B, 1).astype(jnp.int32), (z0, z0))

                def cond(carry):
                    i, done = carry[1], carry[3]
                    return (i < max_new_tokens) & ~(
                        (eos_i >= 0) & jnp.all(done))

                def body(carry):
                    buf, i, kv, done = (carry[0], carry[1], carry[2],
                                        carry[3])
                    cvals = carry[4:]
                    z = jnp.int32(0)  # literal ints trace i64 under x64
                    tok = jax.lax.dynamic_slice(buf, (z, i - 1), (B, 1))
                    caches = [(Tensor(cvals[2 * l], stop_gradient=True),
                               Tensor(cvals[2 * l + 1], stop_gradient=True))
                              for l in range(n_layers)]
                    with no_grad():
                        hidden, ncs = trunk(
                            Tensor(tok, stop_gradient=True), caches=caches,
                            cur_len=Tensor(S0 + i - 1, stop_gradient=True))
                        logits = self.logits(hidden)
                    last = logits._value[:, -1, :].astype(jnp.float32)
                    kv, sub = jax.random.split(kv)
                    nxt = self._sample(last, temperature, top_k, sub)
                    # frozen rows keep emitting eos (HF/PaddleNLP padding)
                    nxt = jnp.where((eos_i >= 0) & done, eos_i, nxt)
                    done = done | ((eos_i >= 0) & (nxt == eos_i))
                    buf = jax.lax.dynamic_update_slice(
                        buf, nxt.reshape(B, 1), (z, i))
                    new_cvals = tuple(t._value for c in ncs for t in c)
                    return (buf, i + 1, kv, done) + new_cvals

                done0 = (eos_i >= 0) & (tok0_v.astype(jnp.int32).reshape(B)
                                        == eos_i)
                init = (buf0, jnp.int32(1), key_v, done0, *cache_vals)
                fin = jax.lax.while_loop(cond, body, init)
                # token buffer, count generated, all-rows-hit-eos flag
                return fin[0], fin[1], jnp.all(fin[3])

            return apply_op(run, [ensure_tensor(first_tok),
                                  ensure_tensor(key), ensure_tensor(eos),
                                  *[ensure_tensor(c) for c in flat_caches]],
                            name="generate_device_loop")

        loop_fn.__name__ = "generate_device_loop"
        return loop_fn
