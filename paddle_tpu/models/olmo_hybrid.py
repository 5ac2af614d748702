"""Olmo-Hybrid — gated delta-rule linear-attention layers and full softmax
attention layers in one stack, served through the paged step.

Layer ``i`` is ``config.layer_types[i]``: ``"linear_attention"`` (a gated
delta-rule layer, Yang et al., arXiv:2412.06464, with the keys of FLA's
``GatedDeltaNet``) or ``"full_attention"``. Both kinds are post-norm, as in
Olmo 2: ``h = x + RMSNorm(mixer(x))``, then ``out = h + RMSNorm(MLP(h))``,
the MLP SwiGLU (``llama.LlamaMLP``); a final RMSNorm comes before the untied
head. Per linear layer and row, per head (``d_k``, ``d_v`` the key and value
head sizes)::

    q, k, v = SiLU(causal_depthwise_conv4(W_q x)), ... (W_k x), ... (W_v x)
    q, k    = q / |q| * d_k ** -0.5, k / |k|
    beta    = sigmoid(W_b x)        (times 2 with allow_neg_eigval)
    g       = -exp(A_log) * softplus(W_a x + dt_bias), alpha = exp(g)
    S_t     = alpha S_{t-1} (I - beta k k^T) + beta v k^T,  o = S_t q
    y       = W_o [RMSNorm_{d_v}(o) * SiLU(W_gate x)]

The state ``S`` and the convolution's tail are per-slot state that the
serving pool keeps beside the full layers' pages (``_cache_layout``), and
``ops/paged_cache.state_attend`` opens them. The full layers have QK-norm
(RMSNorm over the whole q and k projections before the heads split) and no
rotary positions, and call ``paged_attend``. The model serves through
``forward_paged`` only: it has no dense eager forward.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..autograd.engine import apply_op
from ..ops._apply import ensure_tensor
from ..ops.paged_cache import SlotState, paged_attend, state_attend
from .llama import LlamaMLP

__all__ = ["OlmoHybridConfig", "OlmoHybridModel", "OlmoHybridForCausalLM"]

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 30               # full-attention layers
    num_key_value_heads: int = 30
    layer_types: Optional[Sequence[str]] = None   # None: (L, L, L, F) * n
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # parameters are made in float32 and cast layer by layer, so a model
    # served in bfloat16 never holds all of itself in float32
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [FULL if i % 4 == 3 else LINEAR
                                for i in range(self.num_layers)]
        self.layer_types = list(self.layer_types[:self.num_layers])
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(f"layer_types must give {self.num_layers} of "
                             f"{LINEAR!r} / {FULL!r}")
        if self.hidden_size % self.num_heads or \
                self.num_heads % self.num_key_value_heads:
            raise ValueError("heads must divide hidden_size, kv heads heads")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("one key head per value head")


def _linear(n_in: int, n_out: int, std: float):
    return nn.Linear(n_in, n_out, bias_attr=False, weight_attr=nn.ParamAttr(
        initializer=nn.initializer.Normal(mean=0.0, std=std)))


def _in_dtype(layer, dtype):
    """Cast ``layer``'s parameters to the configuration's ``param_dtype``
    as soon as it is built."""
    if jnp.dtype(dtype) != jnp.float32:
        for p in layer.parameters():
            p._set_value(p._value.astype(dtype))
    return layer


def _heads(t, n: int):
    return apply_op(lambda a: a.reshape(a.shape[0], n, -1), [t],
                    name="split_heads")


def _merge(t):
    return apply_op(lambda a: a.reshape(a.shape[0], 1, -1), [t],
                    name="merge_heads")


class OlmoHybridAttention(nn.Layer):
    """Full causal softmax attention over the paged K/V: QK-norm, no rotary
    positions."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        h, nh = config.hidden_size, config.num_heads
        nkv = config.num_key_value_heads
        self.head_dim = h // nh
        self.n_heads, self.n_kv = nh, nkv
        std = config.initializer_range
        proj = std / math.sqrt(2 * config.num_layers)
        self.q_proj = _linear(h, nh * self.head_dim, std)
        self.k_proj = _linear(h, nkv * self.head_dim, std)
        self.v_proj = _linear(h, nkv * self.head_dim, std)
        self.o_proj = _linear(nh * self.head_dim, h, proj)
        eps = config.rms_norm_eps
        self.q_norm = nn.RMSNorm(nh * self.head_dim, epsilon=eps)
        self.k_norm = nn.RMSNorm(nkv * self.head_dim, epsilon=eps)

    def forward_paged(self, x, positions, block_tables, cache):
        """``x`` [T, 1, H], one query token a row. Returns (out [T, 1, H],
        cache)."""
        q = _heads(self.q_norm(self.q_proj(x)), self.n_heads)
        k = _heads(self.k_norm(self.k_proj(x)), self.n_kv)
        v = _heads(self.v_proj(x), self.n_kv)
        ctx, cache = paged_attend(cache, q, k, v, block_tables, positions,
                                  1.0 / math.sqrt(self.head_dim))
        return self.o_proj(_merge(ctx)), cache


class OlmoHybridGatedDeltaNet(nn.Layer):
    """The gated delta-rule layer (module docstring): projections, decay
    and write strength here; the convolution and the recurrence over the
    per-slot state in ``state_attend``; the gated per-head RMSNorm and the
    output projection here."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        h = config.hidden_size
        self.n_heads = nh = config.linear_num_value_heads
        dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
        self.neg_eigval = bool(config.linear_allow_neg_eigval)
        std = config.initializer_range
        proj = std / math.sqrt(2 * config.num_layers)
        self.q_proj = _linear(h, nh * dk, std)
        self.k_proj = _linear(h, nh * dk, std)
        self.v_proj = _linear(h, nh * dv, std)
        self.a_proj = _linear(h, nh, std)
        self.b_proj = _linear(h, nh, std)
        self.g_proj = _linear(h, nh * dv, std)
        self.o_proj = _linear(nh * dv, h, proj)
        K = config.linear_conv_kernel_dim
        conv = nn.ParamAttr(initializer=nn.initializer.Uniform(
            -1.0 / math.sqrt(K), 1.0 / math.sqrt(K)))
        self.q_conv = self.create_parameter([nh * dk, K], attr=conv)
        self.k_conv = self.create_parameter([nh * dk, K], attr=conv)
        self.v_conv = self.create_parameter([nh * dv, K], attr=conv)
        # decays of a trained layer's range: A in [1, 16], dt about 0.01
        self.A_log = self.create_parameter(
            [nh], default_initializer=nn.initializer.Uniform(0.0,
                                                             math.log(16.0)))
        self.dt_bias = self.create_parameter(
            [nh], default_initializer=nn.initializer.Constant(
                math.log(math.expm1(0.01))))
        self.o_norm = nn.RMSNorm(dv, epsilon=config.rms_norm_eps)

    def forward_paged(self, x, positions, slots, cache):
        nh = self.n_heads

        def decay_and_write(a, b, a_log, dt_bias):
            a, b = a.astype(jnp.float32)[:, 0], b.astype(jnp.float32)[:, 0]
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                a + dt_bias.astype(jnp.float32))
            return g, jax.nn.sigmoid(b) * (2.0 if self.neg_eigval else 1.0)

        g, beta = apply_op(decay_and_write,
                           [self.a_proj(x), self.b_proj(x), self.A_log,
                            self.dt_bias], name="gdn_decay")
        o, cache = state_attend(
            cache, _heads(self.q_proj(x), nh), _heads(self.k_proj(x), nh),
            _heads(self.v_proj(x), nh), g, beta,
            (self.q_conv, self.k_conv, self.v_conv), slots, positions)
        eps = self.o_norm._epsilon

        def gated_norm(o, gate, w):
            var = jnp.mean(o * o, axis=-1, keepdims=True)
            y = o * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            gate = gate.astype(jnp.float32).reshape(y.shape)
            return (y * jax.nn.silu(gate)).reshape(y.shape[0], 1, -1) \
                .astype(w.dtype)

        y = apply_op(gated_norm, [o, self.g_proj(x), self.o_norm.weight],
                     name="gdn_gated_norm")
        return self.o_proj(y), cache


class OlmoHybridDecoderLayer(nn.Layer):
    def __init__(self, config: OlmoHybridConfig, kind: str):
        super().__init__()
        eps = config.rms_norm_eps
        self.kind = kind
        self.mixer = (OlmoHybridAttention(config) if kind == FULL
                      else OlmoHybridGatedDeltaNet(config))
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=eps)
        self.mlp = LlamaMLP(config)
        self.post_feedforward_layernorm = nn.RMSNorm(config.hidden_size,
                                                     epsilon=eps)

    def forward_paged(self, x, positions, block_tables, slots, cache):
        if self.kind == FULL:
            with jax.named_scope("attn"):
                h, cache = self.mixer.forward_paged(x, positions,
                                                    block_tables, cache)
        else:
            with jax.named_scope("linear_attn"):
                h, cache = self.mixer.forward_paged(x, positions, slots,
                                                    cache)
        x = x + self.post_attention_layernorm(h)
        with jax.named_scope("mlp"):
            return x + self.post_feedforward_layernorm(self.mlp(x)), cache


class OlmoHybridModel(nn.Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        dt = config.param_dtype
        self.embed_tokens = _in_dtype(nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=nn.initializer.Normal(
                mean=0.0, std=config.initializer_range))), dt)
        self.layers = nn.LayerList([
            _in_dtype(OlmoHybridDecoderLayer(config, kind), dt)
            for kind in config.layer_types])
        self.norm = _in_dtype(nn.RMSNorm(config.hidden_size,
                                         epsilon=config.rms_norm_eps), dt)

    def forward_paged(self, input_ids, positions, block_tables, caches,
                      adapters=None, slots=None):
        """The paged trunk: ``input_ids`` [T, 1], ``positions`` [T],
        ``block_tables`` [T, pages] (the full layers'), ``slots`` [T] each
        row's owner slot (the linear layers'), ``caches`` one per layer
        (``PagedKVCachePool.layer_caches``), handed through unopened. The
        trunk offers no LoRA site (``lora_sites``), so ``adapters`` is
        empty. Returns (hidden [T, 1, H], new_caches)."""
        x = self.embed_tokens(ensure_tensor(input_ids))
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer.forward_paged(x, positions, block_tables, slots,
                                           cache)
            new_caches.append(cache)
        return self.norm(x), new_caches


class OlmoHybridForCausalLM(nn.Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.model = OlmoHybridModel(config)
        self.lm_head = _in_dtype(_linear(config.hidden_size,
                                         config.vocab_size,
                                         config.initializer_range),
                                 config.param_dtype)

    def logits(self, hidden):
        return self.lm_head(hidden)

    def _decode_trunk(self):
        return self.model

    def _cache_spec(self):
        """(layers that take pages, kv heads, head size): the full layers."""
        cfg = self.config
        return (sum(1 for k in cfg.layer_types if k == FULL),
                cfg.num_key_value_heads, cfg.hidden_size // cfg.num_heads)

    def _cache_layout(self) -> List[object]:
        """What each layer keeps, in layer order: ``"kv"`` (pages) for a
        full layer, a :class:`SlotState` for a linear one."""
        cfg = self.config
        nh, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        dv = cfg.linear_value_head_dim
        act = ("bfloat16" if jnp.dtype(cfg.param_dtype) == jnp.bfloat16
               else "float32")
        state = SlotState(heads=nh, key_dim=dk, value_dim=dv,
                          conv_channels=nh * (2 * dk + dv),
                          conv_taps=cfg.linear_conv_kernel_dim - 1,
                          tail_dtype=act)
        return ["kv" if k == FULL else state for k in cfg.layer_types]

    def lora_sites(self):
        """No LoRA site: the engine's adapter store holds none."""
        return [], self.config.num_layers

    def forward(self, input_ids, labels=None):
        raise NotImplementedError(
            "OlmoHybridForCausalLM serves through ServingEngine "
            "(forward_paged); it has no dense eager forward")
