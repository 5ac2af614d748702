"""GPT model family — the flagship causal-LM benchmark model.

Reference parity: the GPT pattern models used by the reference's hybrid
-parallel tests (``test/legacy_test/auto_parallel_gpt_model.py``) and the
fused-transformer surface (``incubate/nn/layer/fused_transformer.py:192``).

TPU-native design:
- pre-LN decoder blocks whose matmuls are MXU-shaped (hidden sizes multiples
  of 128); attention via the Pallas flash kernel (ops/pallas/flash_attention)
  with an XLA sdpa fallback;
- tensor parallelism by construction: when the active mesh has mp>1 the QKV /
  MLP projections are Column/RowParallelLinear and the vocab embedding is
  VocabParallelEmbedding — same module code, sharding annotations compiled in;
- sequence parallelism: activations optionally sharded over the 'sep' axis on
  the sequence dim (GSPMD inserts the boundary collectives);
- weight tying between embedding and LM head (SharedLayerDesc semantics —
  single parameter cell, gradients accumulate on one tape leaf).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..distributed import topology
from ..distributed.sharding_api import shard_tensor
from ..ops._apply import apply_op, ensure_tensor
from ..ops.lora import lora_delta
from ..ops.paged_cache import paged_attend
from .generation import GenerationMixin
from ..tensor import Tensor

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_tiny", "gpt3_1_3b"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded to a multiple of 128 (MXU)
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # tanh-approximate gelu (HF gpt2's "gelu_new") — set when loading HF
    # gpt2 checkpoints (models/convert_hf.py) so logits match exactly
    gelu_approximate: bool = False
    use_flash_attention: bool = True
    sequence_parallel: bool = False
    tie_word_embeddings: bool = True
    pp_num_microbatches: Optional[int] = None  # default: pp degree
    # activation recompute per decoder block (fleet.recompute → jax.remat):
    # trades ~1/3 more FLOPs for O(layers) less live activation memory —
    # the standard lever for batching past HBM on one chip
    recompute: bool = False
    # remat policy (fleet/recompute.py _POLICIES): None/'full' recomputes
    # everything; 'dots' saves matmul outputs and recomputes only the cheap
    # VPU elementwise ops — most of the memory for a few % of step time
    recompute_policy: Optional[str] = None
    # fused chunked linear+CE (ops/fused_loss.py): never materializes the
    # [B·S, V] logits — O(N·V) loss memory drops to O(N·chunk), unlocking
    # larger per-chip batches. forward(labels=...) then returns (None, loss)
    # since full logits are deliberately never formed.
    fused_loss: bool = False

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size
        if self.hidden_size % self.num_heads:
            raise ValueError("num_heads must divide hidden_size")


def gpt_tiny(**kw) -> "GPTConfig":
    cfg = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
               max_position_embeddings=128, hidden_dropout_prob=0.0,
               attention_dropout_prob=0.0)
    cfg.update(kw)
    return GPTConfig(**cfg)


def gpt3_1_3b(**kw) -> "GPTConfig":
    """BASELINE.json north-star config: GPT-3 XL 1.3B."""
    cfg = dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
               max_position_embeddings=2048)
    cfg.update(kw)
    return GPTConfig(**cfg)


def _mesh_mp() -> int:
    return topology.axis_size("mp")


def _mesh_pp() -> int:
    return topology.axis_size("pp")


def _normal_init(std):
    from ..nn import initializer as I

    return I.Normal(mean=0.0, std=std)


class GPTAttention(nn.Layer):
    """Causal self-attention. QKV column-parallel (heads sharded over mp),
    output row-parallel — the Megatron layout the reference's
    ColumnParallelLinear/RowParallelLinear exist for (mp_layers.py:173,343).
    """

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.cfg = config
        h, nh = config.hidden_size, config.num_heads
        self.head_dim = h // nh
        mp = _mesh_mp()
        if nh % mp:
            raise ValueError(f"num_heads {nh} not divisible by mp {mp}")
        std = config.initializer_range
        proj_std = std / math.sqrt(2 * config.num_layers)
        if mp > 1:
            from ..distributed.fleet import ColumnParallelLinear, RowParallelLinear

            self.qkv_proj = ColumnParallelLinear(
                h, 3 * h, gather_output=False,
                weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
            self.out_proj = RowParallelLinear(
                h, h, input_is_parallel=True,
                weight_attr=nn.ParamAttr(initializer=_normal_init(proj_std)))
        else:
            self.qkv_proj = nn.Linear(
                h, 3 * h, weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
            self.out_proj = nn.Linear(
                h, h, weight_attr=nn.ParamAttr(initializer=_normal_init(proj_std)))
        self.attn_drop_p = config.attention_dropout_prob

    def forward(self, x, cache=None, cur_len=None):
        B, S, H = x.shape
        nh, hd = self.cfg.num_heads, self.head_dim
        qkv = self.qkv_proj(x)  # [B, S, 3H] (H possibly mp-sharded)

        def split_heads(v):
            # [B, S, 3H] -> 3 x [B, S, nh, hd]; head dim is the sharded one,
            # so reshape keeps shards intact ([..., nh/mp, hd] per shard)
            q, k, v_ = jnp.split(v, 3, axis=-1)
            return tuple(t.reshape(B, S, nh, hd) for t in (q, k, v_))

        q, k, v = apply_op(split_heads, [ensure_tensor(qkv)], name="split_heads")
        if cache is not None:
            # KV-cache decode path (generation): write this call's k/v at
            # cur_len and attend over the whole buffer with a position mask.
            # cur_len is a TENSOR so one compiled step serves every position.
            k_buf, v_buf = cache
            scale = 1.0 / math.sqrt(hd)

            def cached_attn(qv, kv, vv, kb, vb, cl):
                cl = cl.astype(jnp.int32).reshape(())
                z = jnp.int32(0)
                start = (z, cl, z, z)
                kb = jax.lax.dynamic_update_slice(kb, kv.astype(kb.dtype),
                                                  start)
                vb = jax.lax.dynamic_update_slice(vb, vv.astype(vb.dtype),
                                                  start)
                L = kb.shape[1]
                qh = jnp.swapaxes(qv, 1, 2)            # [B, nh, S, hd]
                kh = jnp.swapaxes(kb, 1, 2)            # [B, nh, L, hd]
                vh = jnp.swapaxes(vb, 1, 2)
                s = jnp.einsum("bhqd,bhkd->bhqk", qh,
                               kh.astype(qh.dtype)) * scale
                rows = cl + jnp.arange(S)[:, None]     # absolute q positions
                cols = jnp.arange(L)[None, :]
                mask = cols <= rows                    # causal over buffer
                s = jnp.where(mask[None, None], s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                ctx = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(p.dtype))
                return jnp.swapaxes(ctx, 1, 2), kb, vb

            ctx, new_k, new_v = apply_op(
                cached_attn,
                [q, k, v, ensure_tensor(k_buf), ensure_tensor(v_buf),
                 ensure_tensor(cur_len)],
                name="cached_attention")
            merged = apply_op(lambda t: t.reshape(B, S, nh * hd),
                              [ensure_tensor(ctx)], name="merge_heads")
            return self.out_proj(merged), (new_k, new_v)
        mesh = topology.get_mesh()
        if (self.cfg.sequence_parallel and mesh is not None
                and "sep" in mesh.axis_names and mesh.shape["sep"] > 1
                and not (self.attn_drop_p and self.training)):
            # long-context path: exact ring attention over the 'sep' axis —
            # q stays resident, k/v stream around the ring (ppermute), so
            # no device ever holds the full sequence (SURVEY §7 step 6)
            from ..distributed.ring_attention import ring_attention

            ctx = ring_attention(q, k, v, causal=True, mesh=mesh)
        elif self.cfg.use_flash_attention:
            ctx = F.flash_attention(q, k, v, causal=True,
                                    dropout=self.attn_drop_p if self.training else 0.0)
        else:
            ctx = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=self.attn_drop_p if self.training else 0.0)
        if isinstance(ctx, tuple):
            ctx = ctx[0]
        merged = apply_op(lambda t: t.reshape(B, S, nh * hd),
                          [ensure_tensor(ctx)], name="merge_heads")
        return self.out_proj(merged)

    def forward_paged(self, x, positions, block_tables, cache,
                      adapters=None, layer_idx=0):
        """Paged-KV ragged step (serving engine): one QUERY TOKEN per
        row — decode tokens and prompt-chunk tokens alike (the unified
        step's flattened grid). What is this trunk's own stays here: the
        fused QKV projection and its split into rows ``[T, heads, hd]``
        (position embeddings were added at the trunk level,
        GPTModel.forward_paged), the output projection and the LoRA
        deltas. The rest is ``paged_attend``: ``cache`` — this layer's
        paged cache, an opaque value from the pool — goes to it unopened
        and what it returns is returned. Returns (out [T, 1, H], cache).

        ``adapters`` (docs/SERVING.md "Multi-LoRA adapters"): per-row
        gathered LoRA stacks ``{site: (A, B)}``; GPT's fused QKV takes
        ONE delta on the concatenated [T, 1, 3H] output (the delta
        splits with it), out_proj one on the merged context."""
        hd = self.head_dim
        qkv = self.qkv_proj(x)  # [T, 1, 3H]
        if adapters is not None:
            qkv = qkv + lora_delta(x, *adapters["qkv_proj"], layer_idx)
        # [T, 1, 3H] -> 3 x [T, heads, hd] (heads of this mp shard)
        q, k, v = apply_op(
            lambda t: tuple(r.reshape(r.shape[0], -1, hd)
                            for r in jnp.split(t, 3, axis=-1)),
            [ensure_tensor(qkv)], name="split_heads")
        ctx, cache = paged_attend(cache, q, k, v, block_tables, positions,
                                  1.0 / math.sqrt(hd))
        merged = apply_op(lambda t: t.reshape(t.shape[0], 1, -1), [ctx],
                          name="merge_heads")
        out = self.out_proj(merged)
        if adapters is not None:
            out = out + lora_delta(merged, *adapters["out_proj"],
                                   layer_idx)
        return out, cache


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, ff = config.hidden_size, config.intermediate_size
        mp = _mesh_mp()
        std = config.initializer_range
        proj_std = std / math.sqrt(2 * config.num_layers)
        if mp > 1:
            from ..distributed.fleet import ColumnParallelLinear, RowParallelLinear

            self.fc1 = ColumnParallelLinear(
                h, ff, gather_output=False,
                weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
            self.fc2 = RowParallelLinear(
                ff, h, input_is_parallel=True,
                weight_attr=nn.ParamAttr(initializer=_normal_init(proj_std)))
        else:
            self.fc1 = nn.Linear(h, ff, weight_attr=nn.ParamAttr(
                initializer=_normal_init(std)))
            self.fc2 = nn.Linear(ff, h, weight_attr=nn.ParamAttr(
                initializer=_normal_init(proj_std)))
        self._gelu_approx = config.gelu_approximate

    def forward(self, x, adapters=None, layer_idx=0):
        if adapters is None:
            return self.fc2(F.gelu(self.fc1(x),
                                   approximate=self._gelu_approx))
        h = self.fc1(x) + lora_delta(x, *adapters["fc1"], layer_idx)
        a = F.gelu(h, approximate=self._gelu_approx)
        return self.fc2(a) + lora_delta(a, *adapters["fc2"], layer_idx)


class GPTDecoderLayer(nn.Layer):
    """Pre-LN block: x + attn(ln1(x)); x + mlp(ln2(x)). Each half runs
    under a ``jax.named_scope`` (``attn``, ``mlp``), so a device trace's
    operations say which half they belong to (PERF.md section 3)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        eps = config.layer_norm_epsilon
        self.ln1 = nn.LayerNorm(config.hidden_size, epsilon=eps)
        self.attn = GPTAttention(config)
        self.ln2 = nn.LayerNorm(config.hidden_size, epsilon=eps)
        self.mlp = GPTMLP(config)
        self.drop_p = config.hidden_dropout_prob

    def forward(self, x, cache=None, cur_len=None):
        if cache is not None:
            with jax.named_scope("attn"):
                h, new_cache = self.attn(self.ln1(x), cache=cache,
                                         cur_len=cur_len)
                x = x + h
            with jax.named_scope("mlp"):
                x = x + self.mlp(self.ln2(x))
            return x, new_cache
        with jax.named_scope("attn"):
            h = self.attn(self.ln1(x))
            if self.drop_p and self.training:
                h = F.dropout(h, self.drop_p)
            x = x + h
        with jax.named_scope("mlp"):
            h = self.mlp(self.ln2(x))
            if self.drop_p and self.training:
                h = F.dropout(h, self.drop_p)
            return x + h

    def forward_paged(self, x, positions, block_tables, cache,
                      adapters=None, layer_idx=0):
        with jax.named_scope("attn"):
            h, cache = self.attn.forward_paged(
                self.ln1(x), positions, block_tables, cache,
                adapters=adapters, layer_idx=layer_idx)
            x = x + h
        with jax.named_scope("mlp"):
            return x + self.mlp(self.ln2(x), adapters=adapters,
                                layer_idx=layer_idx), cache


class GPTModel(nn.Layer):
    """Transformer trunk: embeddings → N decoder blocks → final LN."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        mp = _mesh_mp()
        std = config.initializer_range
        if mp > 1:
            from ..distributed.fleet import VocabParallelEmbedding

            self.embeddings = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size,
                weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
        else:
            self.embeddings = nn.Embedding(
                config.vocab_size, config.hidden_size,
                weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=_normal_init(std)))
        pp = _mesh_pp()
        self._pp = pp
        if pp > 1:
            # stage-stacked blocks: the 1F1B scan+ppermute schedule compiles
            # into the forward (distributed/fleet/pipeline_schedule.py)
            if config.hidden_dropout_prob or config.attention_dropout_prob:
                raise ValueError(
                    "pp>1 uses lax.scan-stacked blocks whose dropout would "
                    "reuse one PRNG key per scan; set dropout probs to 0")
            from ..distributed.fleet.pipeline_schedule import (
                StackedPipelineBlocks,
            )

            self.layers = StackedPipelineBlocks(
                lambda: GPTDecoderLayer(config), config.num_layers)
        else:
            self.layers = nn.LayerList([GPTDecoderLayer(config)
                                        for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.drop_p = config.hidden_dropout_prob

    def _embed(self, ids, position_ids):
        with jax.named_scope("embed"):
            return (self.embeddings(ids)
                    + self.position_embeddings(position_ids))

    def _seq_parallel(self, x):
        mesh = topology.get_mesh()
        if (not self.config.sequence_parallel or mesh is None
                or "sep" not in mesh.axis_names or mesh.shape["sep"] == 1):
            return x
        # activations sharded on the sequence dim over 'sep'
        def fn(v):
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(None, "sep", None)))

        return apply_op(fn, [ensure_tensor(x)], name="seq_parallel_constraint")

    def forward(self, input_ids, position_ids=None, caches=None,
                cur_len=None):
        ids = ensure_tensor(input_ids)
        B, S = ids.shape
        if caches is not None:
            if self._pp > 1:
                raise NotImplementedError(
                    "KV-cache decode requires pp=1 (generation is a "
                    "single-program path; pipeline decode is out of scope)")
            # absolute positions: cur_len .. cur_len+S-1 (a tensor, so one
            # compiled decode step serves every position)
            position_ids = apply_op(
                lambda cl: (jnp.arange(S, dtype=jnp.int32)[None, :]
                            + cl.astype(jnp.int32)).repeat(B, axis=0),
                [ensure_tensor(cur_len)], name="decode_positions")
            x = self._embed(ids, position_ids)
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                x, nc = layer(x, cache=cache, cur_len=cur_len)
                new_caches.append(nc)
            return self.ln_f(x), new_caches
        if position_ids is None:
            pos_val = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, axis=0)
            position_ids = Tensor(pos_val, stop_gradient=True)
        x = self._embed(ids, position_ids)
        if self.drop_p and self.training:
            x = F.dropout(x, self.drop_p)
        x = self._seq_parallel(x)
        if self._pp > 1:
            if self.config.recompute:
                import warnings

                warnings.warn(
                    "GPTConfig.recompute is subsumed under pp>1: the "
                    "pipeline schedule already remats each stage block "
                    "(fleet/pipeline_schedule.py); the flag adds nothing",
                    stacklevel=2)
            x = self.layers(
                x, num_microbatches=self.config.pp_num_microbatches or self._pp)
        elif self.config.recompute:
            from ..distributed.fleet.recompute import recompute as _rc

            for layer in self.layers:
                x = _rc(layer, x, policy=self.config.recompute_policy)
        else:
            for layer in self.layers:
                x = layer(x)
        return self.ln_f(x)

    def forward_paged(self, input_ids, positions, block_tables, caches,
                      adapters=None):
        """Paged decode trunk (serving engine): ``input_ids`` [B, 1],
        ``positions`` [B] per-row absolute positions (the learned position
        embedding is gathered per row — the paged counterpart of the
        cur_len-offset decode_positions), ``caches`` one paged cache per
        layer (``PagedKVCachePool.layer_caches``), each handed to its
        layer unopened. ``adapters``: per-row gathered LoRA stacks
        ``{site: (A, B)}`` applied at every projection per layer (zero
        for slot-0 rows). Returns (hidden, new_caches)."""
        if self._pp > 1:
            raise NotImplementedError(
                "paged decode requires pp=1 (same single-program scope as "
                "KV-cache decode)")
        ids = ensure_tensor(input_ids)
        pos_ids = apply_op(
            lambda p: p.astype(jnp.int32).reshape(-1, 1),
            [ensure_tensor(positions)], name="paged_positions")
        x = self._embed(ids, pos_ids)
        new_caches = []
        for li, (layer, cache) in enumerate(zip(self.layers, caches)):
            x, cache = layer.forward_paged(x, positions, block_tables, cache,
                                           adapters=adapters, layer_idx=li)
            new_caches.append(cache)
        return self.ln_f(x), new_caches


class GPTForCausalLM(nn.Layer, GenerationMixin):
    """LM head on the trunk; weight-tied to the input embedding by default
    (one parameter cell — SharedLayerDesc semantics without the allreduce).
    ``generate()`` comes from GenerationMixin (KV-cache decode).
    """

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)
        else:
            self.lm_head = None

    def logits(self, hidden):
        if self.lm_head is not None:
            return self.lm_head(hidden)
        w = self.gpt.embeddings.weight  # [V, H] (possibly mp-sharded on V)
        return apply_op(lambda h, wv: h @ wv.T,
                        [ensure_tensor(hidden), w], name="matmul")

    def forward(self, input_ids, position_ids=None, labels=None):
        hidden = self.gpt(input_ids, position_ids)
        if labels is not None and self.config.fused_loss \
                and self.lm_head is None and _mesh_mp() == 1:
            from ..ops.fused_loss import fused_linear_cross_entropy

            H = self.config.hidden_size
            with jax.named_scope("head_loss"):
                loss = apply_op(
                    lambda h, w, y: fused_linear_cross_entropy(
                        h.reshape(-1, H), w, y.reshape(-1)),
                    [ensure_tensor(hidden), self.gpt.embeddings.weight,
                     ensure_tensor(labels)],
                    name="fused_linear_cross_entropy")
            return None, loss
        logits = self.logits(hidden)
        if labels is None:
            return logits
        mp = _mesh_mp()
        V = self.config.vocab_size
        flat_logits = logits.reshape([-1, V])
        flat_labels = ensure_tensor(labels).reshape([-1])
        if mp > 1:
            from ..distributed.fleet import ParallelCrossEntropy

            loss = ParallelCrossEntropy()(flat_logits, flat_labels)
            from ..ops import math as _math

            return logits, _math.mean(loss)
        loss = F.cross_entropy(flat_logits, flat_labels)
        return logits, loss

    # ------------------------------------------------- generation hooks
    def _decode_trunk(self):
        if self.gpt._pp > 1:
            raise NotImplementedError("generate requires pp=1")
        return self.gpt

    def _cache_spec(self):
        cfg = self.config
        return (cfg.num_layers, cfg.num_heads,
                cfg.hidden_size // cfg.num_heads)

    def lora_sites(self):
        """The AdapterStore contract (serving/adapters.py): ordered
        ``(site, in_dim, out_dim)`` triples plus the layer count. GPT's
        QKV is FUSED, so one ``qkv_proj`` site covers all three with a
        [H → 3H] delta that splits alongside the base projection.
        Dims are unsharded — multi-LoRA serving assumes mp=1."""
        cfg = self.config
        h, ff = cfg.hidden_size, cfg.intermediate_size
        sites = [("qkv_proj", h, 3 * h), ("out_proj", h, h),
                 ("fc1", h, ff), ("fc2", ff, h)]
        return sites, cfg.num_layers
