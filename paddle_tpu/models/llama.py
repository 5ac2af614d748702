"""Llama model family — RoPE + RMSNorm + SwiGLU + grouped-query attention.

Reference parity: PaddleNLP's llama modeling (the reference framework's
flagship decoder family; the 4-D-parallel pretraining target in
BASELINE.md). TPU-first construction mirrors models/gpt.py: Megatron
column/row-parallel projections over the 'mp' mesh axis, optional ring
attention over 'sep' for long context, per-block recompute, and a fully
traceable forward so the whole train step compiles to one XLA program.

GQA: ``num_key_value_heads < num_heads`` shrinks the KV projections and
repeats KV per query group — on TPU this is a gather-free
``jnp.repeat`` on the head axis that XLA fuses into the attention
matmuls.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..autograd.engine import apply_op
from ..distributed import topology
from ..nn import functional as F
from ..ops._apply import ensure_tensor
from ..ops.lora import lora_delta
from ..ops.paged_cache import paged_attend
from ..tensor import Tensor
from .generation import GenerationMixin

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_tiny"]


def _mesh_dim(name: str) -> int:
    mesh = topology.get_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def _normal_init(std: float):
    return nn.initializer.Normal(mean=0.0, std=std)


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    num_layers: int = 22
    num_heads: int = 16
    num_key_value_heads: Optional[int] = None  # None → MHA; < heads → GQA
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    sequence_parallel: bool = False
    tie_word_embeddings: bool = False
    recompute: bool = False
    # remat policy (fleet/recompute.py _POLICIES): None/'full' recomputes
    # everything; 'dots' saves matmul outputs, recomputing only elementwise
    recompute_policy: Optional[str] = None
    # chunked linear+CE (ops/fused_loss.py): never materializes the
    # [B·S, V] logits; forward(labels=...) returns (None, loss).
    # mp==1 only — under tensor parallelism the vocab shard math belongs to
    # ParallelCrossEntropy; forward warns and uses the dense path there.
    fused_loss: bool = False

    def __post_init__(self):
        if self.intermediate_size is None:
            # llama convention: 8/3 * h rounded up to a multiple of 256
            self.intermediate_size = ((int(8 * self.hidden_size / 3) + 255)
                                      // 256) * 256
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_heads
        if self.hidden_size % self.num_heads:
            raise ValueError("num_heads must divide hidden_size")
        if self.num_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_heads")


def llama_tiny(**kw) -> LlamaConfig:
    cfg = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
               num_key_value_heads=2, max_position_embeddings=128)
    cfg.update(kw)
    return LlamaConfig(**cfg)


# ------------------------------------------------------------------ RoPE


def _rope_tables(seq: int, dim: int, theta: float):
    """cos/sin tables [S, dim/2] (precomputed per forward; XLA hoists the
    constant computation out of the step)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                / dim))
    t = jnp.arange(seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [S, dim/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def _apply_rope(x, cos, sin):
    """x: [B, S, H, D] — rotate pairs (x_even, x_odd)."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    out_even = x1 * c - x2 * s
    out_odd = x1 * s + x2 * c
    return jnp.stack([out_even, out_odd], axis=-1).reshape(x.shape)


# ------------------------------------------------------------- attention


class LlamaAttention(nn.Layer):
    """RoPE + GQA causal attention; q/k/v column-parallel over 'mp',
    output row-parallel (mp_layers.py layout, like GPTAttention)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.cfg = config
        h = config.hidden_size
        nh, nkv = config.num_heads, config.num_key_value_heads
        self.head_dim = h // nh
        mp = _mesh_dim("mp")
        if nh % mp or nkv % mp:
            raise ValueError(f"heads ({nh}) and kv heads ({nkv}) must be "
                             f"divisible by mp degree {mp}")
        std = config.initializer_range
        proj_std = std / math.sqrt(2 * config.num_layers)
        q_out = nh * self.head_dim
        kv_out = nkv * self.head_dim
        if mp > 1:
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)

            def col(n_out, s):
                return ColumnParallelLinear(
                    h, n_out, gather_output=False, has_bias=False,
                    weight_attr=nn.ParamAttr(initializer=_normal_init(s)))

            self.q_proj = col(q_out, std)
            self.k_proj = col(kv_out, std)
            self.v_proj = col(kv_out, std)
            self.o_proj = RowParallelLinear(
                q_out, h, input_is_parallel=True, has_bias=False,
                weight_attr=nn.ParamAttr(initializer=_normal_init(proj_std)))
        else:
            def lin(n_out, s):
                return nn.Linear(h, n_out, bias_attr=False,
                                 weight_attr=nn.ParamAttr(
                                     initializer=_normal_init(s)))

            self.q_proj = lin(q_out, std)
            self.k_proj = lin(kv_out, std)
            self.v_proj = lin(kv_out, std)
            self.o_proj = nn.Linear(q_out, h, bias_attr=False,
                                    weight_attr=nn.ParamAttr(
                                        initializer=_normal_init(proj_std)))

    def forward_paged(self, x, positions, block_tables, cache,
                      adapters=None, layer_idx=0):
        """Paged-KV ragged step (serving engine): one QUERY TOKEN per
        row — a decode slot's next token, or one token of a prompt
        chunk (the unified step flattens mixed per-slot query lengths
        into rows).

        ``x`` [T, 1, H]; ``positions`` [T] per-row absolute positions.
        What is this trunk's own stays here: the q/k/v projections, the
        rotary embedding at each row's own position (same tables and
        math as the dense cached_attn path, so paged serving is
        token-compatible with ``generate()``), the output projection and
        the LoRA deltas. The rest is ``paged_attend``: ``cache`` — this
        layer's paged cache, an opaque value from the pool — goes to it
        unopened and what it returns is returned. Returns
        (out [T, 1, H], cache).

        ``adapters`` (docs/SERVING.md "Multi-LoRA adapters"): per-row
        gathered LoRA stacks ``{site: (A, B)}`` — each projection adds
        its ``lora_delta`` at ``layer_idx``; rows on adapter slot 0 add
        an exact zero, keeping non-adapter tenants bit-identical.
        """
        cfg = self.cfg
        hd = self.head_dim

        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)
        if adapters is not None:
            q = q + lora_delta(x, *adapters["q_proj"], layer_idx)
            k = k + lora_delta(x, *adapters["k_proj"], layer_idx)
            v = v + lora_delta(x, *adapters["v_proj"], layer_idx)

        def rope_rows(qv, kv, vv, pos):
            # [T, 1, heads * hd] -> [T, heads, hd] (heads of this mp
            # shard), q and k rotated at the row's own position
            pos = pos.astype(jnp.int32).reshape(-1)
            qh, kh, vh = (t.reshape(t.shape[0], -1, hd)
                          for t in (qv, kv, vv))
            cos_f, sin_f = _rope_tables(cfg.max_position_embeddings, hd,
                                        cfg.rope_theta)
            cos = cos_f[pos][:, None, :]  # [T, 1, hd/2] per-row positions
            sin = sin_f[pos][:, None, :]

            def rope(t):
                t1, t2 = t[..., 0::2], t[..., 1::2]
                return jnp.stack([t1 * cos - t2 * sin,
                                  t1 * sin + t2 * cos],
                                 axis=-1).reshape(t.shape)

            return rope(qh), rope(kh), vh

        q, k, v = apply_op(
            rope_rows, [ensure_tensor(q), ensure_tensor(k), ensure_tensor(v),
                        ensure_tensor(positions)], name="llama_rope_rows")
        ctx, cache = paged_attend(cache, q, k, v, block_tables, positions,
                                  1.0 / math.sqrt(hd))
        merged = apply_op(lambda t: t.reshape(t.shape[0], 1, -1), [ctx],
                          name="merge_heads")
        out = self.o_proj(merged)
        if adapters is not None:
            out = out + lora_delta(merged, *adapters["o_proj"], layer_idx)
        return out, cache

    def forward(self, x, cache=None, cur_len=None):
        B, S, _ = x.shape
        cfg = self.cfg
        hd = self.head_dim
        nh, nkv = cfg.num_heads, cfg.num_key_value_heads
        groups = nh // nkv

        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)

        if cache is not None:
            # KV-cache decode: rope at ABSOLUTE positions (tables for the
            # full buffer, sliced at cur_len), write k/v into the buffer,
            # attend with a position mask. See models/generation.py.
            k_buf, v_buf = cache
            L = k_buf.shape[1]
            scale = 1.0 / math.sqrt(hd)

            def cached_attn(qv, kv, vv, kb, vb, cl):
                cl = cl.astype(jnp.int32).reshape(())
                z = jnp.int32(0)
                nh_l = qv.shape[-1] // hd
                nkv_l = kv.shape[-1] // hd
                qh = qv.reshape(B, S, nh_l, hd)
                kh = kv.reshape(B, S, nkv_l, hd)
                vh = vv.reshape(B, S, nkv_l, hd)
                cos_f, sin_f = _rope_tables(L, hd, cfg.rope_theta)
                cos = jax.lax.dynamic_slice(cos_f, (cl, z),
                                            (S, cos_f.shape[1]))
                sin = jax.lax.dynamic_slice(sin_f, (cl, z),
                                            (S, sin_f.shape[1]))
                qh = _apply_rope(qh, cos, sin)
                kh = _apply_rope(kh, cos, sin)
                # cache stores PRE-repeat kv heads (nkv): repeating at read
                # time keeps GQA's memory saving (the whole point of GQA)
                kb = jax.lax.dynamic_update_slice(
                    kb, kh.astype(kb.dtype), (z, cl, z, z))
                vb = jax.lax.dynamic_update_slice(
                    vb, vh.astype(vb.dtype), (z, cl, z, z))
                kr, vr = kb, vb
                if groups > 1:
                    kr = jnp.repeat(kb, groups, axis=2)
                    vr = jnp.repeat(vb, groups, axis=2)
                qt = jnp.swapaxes(qh, 1, 2)
                kt = jnp.swapaxes(kr, 1, 2)
                vt = jnp.swapaxes(vr, 1, 2)
                s = jnp.einsum("bhqd,bhkd->bhqk", qt,
                               kt.astype(qt.dtype)) * scale
                rows = cl + jnp.arange(S)[:, None]
                cols = jnp.arange(L)[None, :]
                s = jnp.where((cols <= rows)[None, None], s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                ctx = jnp.swapaxes(
                    jnp.einsum("bhqk,bhkd->bhqd", p, vt.astype(p.dtype)),
                    1, 2)
                return ctx.reshape(B, S, nh_l * hd), kb, vb

            merged, new_k, new_v = apply_op(
                cached_attn,
                [ensure_tensor(q), ensure_tensor(k), ensure_tensor(v),
                 ensure_tensor(k_buf), ensure_tensor(v_buf),
                 ensure_tensor(cur_len)],
                name="llama_cached_attention")
            return self.o_proj(merged), (new_k, new_v)

        def shape_rope_repeat(qv, kv, vv):
            # per-shard head counts (mp shards the head axis)
            nh_l = qv.shape[-1] // hd
            nkv_l = kv.shape[-1] // hd
            qh = qv.reshape(B, S, nh_l, hd)
            kh = kv.reshape(B, S, nkv_l, hd)
            vh = vv.reshape(B, S, nkv_l, hd)
            cos, sin = _rope_tables(S, hd, cfg.rope_theta)
            qh = _apply_rope(qh, cos, sin)
            kh = _apply_rope(kh, cos, sin)
            if groups > 1:  # GQA: repeat kv heads per query group
                kh = jnp.repeat(kh, groups, axis=2)
                vh = jnp.repeat(vh, groups, axis=2)
            return qh, kh, vh

        q, k, v = apply_op(shape_rope_repeat,
                           [ensure_tensor(q), ensure_tensor(k),
                            ensure_tensor(v)], name="llama_rope_gqa")

        mesh = topology.get_mesh()
        if (cfg.sequence_parallel and mesh is not None
                and "sep" in mesh.axis_names and mesh.shape["sep"] > 1):
            from ..distributed.ring_attention import ring_attention

            ctx = ring_attention(q, k, v, causal=True, mesh=mesh)
        elif cfg.use_flash_attention:
            ctx = F.flash_attention(q, k, v, causal=True)
        else:
            ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        if isinstance(ctx, tuple):
            ctx = ctx[0]
        merged = apply_op(lambda t: t.reshape(B, S, t.shape[2] * hd),
                          [ensure_tensor(ctx)], name="merge_heads")
        return self.o_proj(merged)


class LlamaMLP(nn.Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)); gate/up column-parallel,
    down row-parallel."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, ff = config.hidden_size, config.intermediate_size
        mp = _mesh_dim("mp")
        std = config.initializer_range
        proj_std = std / math.sqrt(2 * config.num_layers)
        if mp > 1:
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)

            self.gate_proj = ColumnParallelLinear(
                h, ff, gather_output=False, has_bias=False,
                weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
            self.up_proj = ColumnParallelLinear(
                h, ff, gather_output=False, has_bias=False,
                weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
            self.down_proj = RowParallelLinear(
                ff, h, input_is_parallel=True, has_bias=False,
                weight_attr=nn.ParamAttr(initializer=_normal_init(proj_std)))
        else:
            self.gate_proj = nn.Linear(h, ff, bias_attr=False,
                                       weight_attr=nn.ParamAttr(
                                           initializer=_normal_init(std)))
            self.up_proj = nn.Linear(h, ff, bias_attr=False,
                                     weight_attr=nn.ParamAttr(
                                         initializer=_normal_init(std)))
            self.down_proj = nn.Linear(ff, h, bias_attr=False,
                                       weight_attr=nn.ParamAttr(
                                           initializer=_normal_init(proj_std)))

    def forward(self, x, adapters=None, layer_idx=0):
        if adapters is None:
            return self.down_proj(F.silu(self.gate_proj(x))
                                  * self.up_proj(x))
        g = self.gate_proj(x) + lora_delta(x, *adapters["gate_proj"],
                                           layer_idx)
        u = self.up_proj(x) + lora_delta(x, *adapters["up_proj"],
                                         layer_idx)
        a = F.silu(g) * u
        return self.down_proj(a) + lora_delta(a, *adapters["down_proj"],
                                              layer_idx)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        eps = config.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cache=None, cur_len=None):
        if cache is not None:
            h, nc = self.self_attn(self.input_layernorm(x), cache=cache,
                                   cur_len=cur_len)
            x = x + h
            return x + self.mlp(self.post_attention_layernorm(x)), nc
        x = x + self.self_attn(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))

    def forward_paged(self, x, positions, block_tables, cache,
                      adapters=None, layer_idx=0):
        with jax.named_scope("attn"):
            h, cache = self.self_attn.forward_paged(
                self.input_layernorm(x), positions, block_tables, cache,
                adapters=adapters, layer_idx=layer_idx)
            x = x + h
        with jax.named_scope("mlp"):
            return x + self.mlp(self.post_attention_layernorm(x),
                                adapters=adapters, layer_idx=layer_idx), cache


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        std = config.initializer_range
        mp = _mesh_dim("mp")
        if mp > 1:
            from ..distributed.fleet import VocabParallelEmbedding

            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size,
                weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
        else:
            self.embed_tokens = nn.Embedding(
                config.vocab_size, config.hidden_size,
                weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def _seq_parallel(self, x):
        """Pin the residual stream's sequence dim to the 'sep' axis (same
        pattern as GPTModel._seq_parallel) — without this, ring attention's
        shard_map boundary would reshard activations every layer."""
        import jax

        mesh = topology.get_mesh()
        if (not self.config.sequence_parallel or mesh is None
                or "sep" not in mesh.axis_names or mesh.shape["sep"] == 1):
            return x

        def fn(v):
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(None, "sep", None)))

        return apply_op(fn, [ensure_tensor(x)],
                        name="seq_parallel_constraint")

    def forward(self, input_ids, caches=None, cur_len=None):
        x = self.embed_tokens(ensure_tensor(input_ids))
        if caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                x, nc = layer(x, cache=cache, cur_len=cur_len)
                new_caches.append(nc)
            return self.norm(x), new_caches
        x = self._seq_parallel(x)
        if self.config.recompute:
            from ..distributed.fleet.recompute import recompute as _rc

            for layer in self.layers:
                x = _rc(layer, x, policy=self.config.recompute_policy)
        else:
            for layer in self.layers:
                x = layer(x)
        return self.norm(x)

    def forward_paged(self, input_ids, positions, block_tables, caches,
                      adapters=None):
        """Paged decode trunk (serving engine): ``input_ids`` [B, 1],
        ``positions`` [B], ``caches`` one paged cache per layer
        (``PagedKVCachePool.layer_caches``), each handed to its layer
        unopened. ``adapters``: per-row gathered LoRA stacks
        ``{site: (A [T, L, r, in], B [T, L, out, r])}`` applied at every
        projection site per layer (zero for slot-0 rows). Returns
        (hidden [B, 1, H], new_caches)."""
        x = self.embed_tokens(ensure_tensor(input_ids))
        new_caches = []
        for li, (layer, cache) in enumerate(zip(self.layers, caches)):
            x, cache = layer.forward_paged(x, positions, block_tables, cache,
                                           adapters=adapters, layer_idx=li)
            new_caches.append(cache)
        return self.norm(x), new_caches


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(
                config.hidden_size, config.vocab_size, bias_attr=False,
                weight_attr=nn.ParamAttr(
                    initializer=_normal_init(config.initializer_range)))

    def logits(self, hidden):
        if self.lm_head is not None:
            return self.lm_head(hidden)
        w = self.llama.embed_tokens.weight
        return apply_op(lambda h, e: h @ e.T,
                        [ensure_tensor(hidden), ensure_tensor(w)],
                        name="tied_lm_head")

    def _decode_trunk(self):
        return self.llama

    def _cache_spec(self):
        cfg = self.config
        # pre-repeat kv heads: GQA's memory saving applies to the cache too
        return (cfg.num_layers, cfg.num_key_value_heads,
                cfg.hidden_size // cfg.num_heads)

    def lora_sites(self):
        """The AdapterStore contract (serving/adapters.py): ordered
        ``(site, in_dim, out_dim)`` triples for every projection the
        paged trunk offers a LoRA delta at, plus the layer count.
        Dims are the UNSHARDED shapes — multi-LoRA serving assumes the
        single-program (mp=1) serving path."""
        cfg = self.config
        hd = cfg.hidden_size // cfg.num_heads
        h = cfg.hidden_size
        q_out = cfg.num_heads * hd
        kv_out = cfg.num_key_value_heads * hd
        ff = cfg.intermediate_size
        sites = [("q_proj", h, q_out), ("k_proj", h, kv_out),
                 ("v_proj", h, kv_out), ("o_proj", q_out, h),
                 ("gate_proj", h, ff), ("up_proj", h, ff),
                 ("down_proj", ff, h)]
        return sites, cfg.num_layers

    def forward(self, input_ids, labels=None):
        hidden = self.llama(input_ids)
        if labels is not None and self.config.fused_loss:
            if _mesh_dim("mp") > 1:
                import warnings

                warnings.warn(
                    "LlamaConfig.fused_loss is mp==1 only (vocab-sharded "
                    "loss is ParallelCrossEntropy's job); using the dense "
                    "path — expect the [B·S, V] logits memory peak",
                    stacklevel=2)
            else:
                from ..ops.fused_loss import fused_linear_cross_entropy

                w = self.lm_head.weight if self.lm_head is not None \
                    else self.llama.embed_tokens.weight
                H = self.config.hidden_size
                # lm_head.weight is [H, V] (Linear layout); fused CE wants
                # [V, H]; the tied embedding is [V, H] already
                needs_t = self.lm_head is not None
                loss = apply_op(
                    lambda h, wv, y: fused_linear_cross_entropy(
                        h.reshape(-1, H), wv.T if needs_t else wv,
                        y.reshape(-1)),
                    [ensure_tensor(hidden), ensure_tensor(w),
                     ensure_tensor(labels)],
                    name="fused_linear_cross_entropy")
                return None, loss
        logits = self.logits(hidden)
        if labels is None:
            return logits
        loss = F.cross_entropy(
            logits.reshape((-1, self.config.vocab_size)),
            ensure_tensor(labels).reshape((-1,)))
        return logits, loss
