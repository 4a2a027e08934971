"""Shared model-configuration schema + parameter collection utilities.

One ``ArchConfig`` dataclass covers every architecture family (dense / MLA /
MoE / SSM / hybrid / Mamba-2-attention hybrid / enc-dec / VLM-stub /
audio-stub); the per-arch files in ``repro.configs`` instantiate it.

Parameters are plain nested-dict pytrees.  Every leaf is declared through a
``Collector`` with *logical axis names* (e.g. ``("layers", "d_model",
"d_ff")``); ``repro.distributed.sharding`` later maps logical names to mesh
axes — that mapping IS the paper's dimension lifting applied at the mesh
level, kept in one place.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | ssm | moe | vlm | hybrid |
                                     # mamba_hybrid | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # attention flavor
    attention: str = "full"          # full | mla | none (ssm)
    local_window: int = 0            # >0 enables windowed attention layers
    layer_pattern: tuple[str, ...] = ()   # repeating group for hybrids, e.g.
                                          # ("rglru","rglru","local"),
                                          # ("local","local","local","full")
                                          # or, mamba_hybrid, the period's
                                          # "mamba"/"attn" mixers
    rope_theta: float = 10000.0
    rope_pct: float = 1.0            # partial rotary (stablelm: 0.25)

    # MLP
    mlp: str = "swiglu"              # swiglu | geglu | gelu
    use_bias: bool = False
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    parallel_block: bool = False     # attn+mlp in parallel (command-r style)
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    norm_eps: float = 1e-6           # RMSNorm/LayerNorm and Mamba's gated norm
    # published multipliers (granite): 0 / 1 keep the plain model
    embedding_multiplier: float = 0.0   # >0 scales looked-up rows (replaces
                                        # the tied sqrt(d) convention)
    residual_multiplier: float = 1.0    # mamba_hybrid: on every sublayer's
                                        # output before its residual add
    attention_multiplier: float = 0.0   # >0 is the softmax scale (else
                                        # head_dim ** -0.5)
    logits_scaling: float = 1.0         # logits are divided by it

    # MoE
    moe: bool = False
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                # expert hidden size (d_ff used for dense)
    first_dense_layers: int = 0      # deepseek: leading dense layer(s)
    capacity_factor: float = 1.25

    # SSM (mamba-2 SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    conv_width: int = 4

    # RG-LRU (hybrid)
    lru_width: int = 0               # 0 -> d_model

    # encoder-decoder (audio) / VLM stub frontends
    encoder_layers: int = 0
    encoder_seq: int = 0             # whisper: 1500 frames (stub embeddings)
    num_patches: int = 0             # paligemma: 256 patch embeddings (stub)

    train_microbatches: int = 0      # 0 = heuristic (launch.dryrun)
    dtype: Any = "bfloat16"
    remat: bool = True
    remat_policy: str = "full"       # "full" | "dots" (save matmul outputs)
    scan_unroll: bool = False   # unroll lax.scan bodies (dry-run cost extraction)
    attn_chunk_min_seq: int = 8192   # use chunked (flash-style) attention at/above
    attn_chunk: int = 1024           # k chunk length for chunked attention
    attn_q_chunk: int = 0            # q chunk length (0 = whole seq: k-only streaming)
    attn_sharding: str = "sp"        # "sp" (seq-parallel) | "heads" (Megatron)
    attn_impl: str = "xla"           # "xla" (chunked jnp) | "pallas" (flash kernel)

    # ------------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def moe_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> tuple[int, int]:
        """(total, active) parameter counts — analytic, used for MODEL_FLOPS."""
        d, v, hd = self.d_model, self.vocab_size, self.head_dim_
        emb = v * d * (1 if self.tie_embeddings else 2)
        att = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        if self.attention == "mla":
            # q: d->q_rank->h*(nope+rope); kv: d->kv_rank(+rope)->h*(nope+v)
            att = (d * 768 + 768 * self.n_heads * 96
                   + d * (256 + 32) + 256 * self.n_heads * (64 + 64)
                   + self.n_heads * 64 * d)
        mlp_mult = {"swiglu": 3, "geglu": 3, "gelu": 2}[self.mlp]
        dense_mlp = mlp_mult * d * self.d_ff
        total = emb
        active = emb
        n_att_layers = self.n_layers
        if self.family in ("ssm", "mamba_hybrid"):
            d_in = self.ssm_expand * d
            n_h = d_in // self.ssm_head_dim
            per = (d * (2 * d_in + 2 * self.ssm_state * 1 + n_h)  # in_proj-ish
                   + d_in * d)
            if self.family == "ssm":
                total += self.n_layers * per
                return int(total), int(total)
            n_att = self.n_layers * self.layer_pattern.count("attn") \
                // len(self.layer_pattern)
            total += ((self.n_layers - n_att) * per + n_att * att
                      + self.n_layers * dense_mlp)
            return int(total), int(total)
        if self.layer_pattern:
            n_rec = sum(1 for p in self.layer_pattern if p == "rglru")
            frac_rec = n_rec / len(self.layer_pattern)
            lw = self.lru_width or d
            rec_per = 2 * d * lw + lw * d + 2 * lw  # gates + in/out proj
            total += int(self.n_layers * frac_rec) * (rec_per + dense_mlp)
            n_att_layers = self.n_layers - int(self.n_layers * frac_rec)
        if self.moe:
            moe_layers = self.n_layers - self.first_dense_layers
            expert_mlp = mlp_mult * d * self.moe_ff
            shared = self.n_shared_experts * expert_mlp
            router = d * self.n_experts
            total += moe_layers * (att + self.n_experts * expert_mlp + shared + router)
            total += self.first_dense_layers * (att + dense_mlp)
            active += moe_layers * (att + self.top_k * expert_mlp + shared + router)
            active += self.first_dense_layers * (att + dense_mlp)
            return int(total), int(active)
        total += n_att_layers * (att + dense_mlp)
        if self.encoder_layers:
            total += self.encoder_layers * (att + dense_mlp) \
                + self.n_layers * (d * 2 * (self.n_kv_heads * hd) + 0)  # cross kv
        active = total
        return int(total), int(active)


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell from the assignment."""
    name: str                        # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# parameter collection (params pytree + logical-axis pytree, same structure)
# ---------------------------------------------------------------------------

class Collector:
    """Builds a params pytree and a parallel logical-axes pytree.

    ``col.param("attn/wq", (L, d, h, hd), ("layers","d_model","heads","head_dim"),
    scale)`` creates a normal(0, scale)-initialized leaf.  Axes drive both
    sharding (distributed/sharding.py) and documentation.
    """

    def __init__(self, key: jax.Array, dtype=jnp.bfloat16):
        self.key = key
        self.dtype = dtype
        self.params: dict = {}
        self.axes: dict = {}

    def _split(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub

    def _set(self, tree: dict, path: str, value):
        parts = path.split("/")
        for p in parts[:-1]:
            tree = tree.setdefault(p, {})
        assert parts[-1] not in tree, f"duplicate param {path}"
        tree[parts[-1]] = value

    def param(self, path: str, shape: tuple[int, ...], axes: tuple[str, ...],
              scale: float | None = None, init: str = "normal", dtype=None):
        assert len(shape) == len(axes), (path, shape, axes)
        dtype = dtype or self.dtype
        if init == "zeros":
            val = jnp.zeros(shape, dtype)
        elif init == "ones":
            val = jnp.ones(shape, dtype)
        else:
            if scale is None:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                scale = fan_in ** -0.5
            val = (jax.random.normal(self._split(), shape, jnp.float32)
                   * scale).astype(dtype)
        self._set(self.params, path, val)
        self._set(self.axes, path, axes)
        return val

    def done(self) -> tuple[dict, dict]:
        return self.params, self.axes


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
