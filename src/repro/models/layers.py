"""Building-block layers: norms, gated MLPs, rotary embeddings, vocab heads.

Pure functions over param subtrees created via ``common.Collector``.
Norms and softmax run in f32; matmuls accumulate in f32 (bf16 storage).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed import plan as dplan
from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models.common import ArchConfig, Collector


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(col: Collector, path: str, d: int, cfg: ArchConfig,
              stack: tuple[tuple[int, str], ...] = ()):
    lead_shape = tuple(s for s, _ in stack)
    lead_axes = tuple(a for _, a in stack)
    col.param(f"{path}/scale", lead_shape + (d,), lead_axes + ("d_model",),
              init="ones")
    if cfg.norm == "layernorm" and cfg.use_bias:
        col.param(f"{path}/bias", lead_shape + (d,), lead_axes + ("d_model",),
                  init="zeros")


def apply_norm(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32)
        if "bias" in p:
            out = out + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs (dense)
# ---------------------------------------------------------------------------

def init_mlp(col: Collector, path: str, cfg: ArchConfig, d_ff: int | None = None,
             stack: tuple[tuple[int, str], ...] = ()):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    lead = tuple(s for s, _ in stack)
    laxes = tuple(a for _, a in stack)
    if cfg.mlp in ("swiglu", "geglu"):
        col.param(f"{path}/wi", lead + (d, 2 * f), laxes + ("d_model", "d_ff"),
                  scale=d ** -0.5)
    else:
        col.param(f"{path}/wi", lead + (d, f), laxes + ("d_model", "d_ff"),
                  scale=d ** -0.5)
    col.param(f"{path}/wo", lead + (f, d), laxes + ("d_ff", "d_model"),
              scale=f ** -0.5)
    if cfg.use_bias:
        col.param(f"{path}/bi", lead + ((2 * f) if cfg.mlp in ("swiglu", "geglu") else f,),
                  laxes + ("d_ff",), init="zeros")
        col.param(f"{path}/bo", lead + (d,), laxes + ("d_model",), init="zeros")


def _gate_act(cfg: ArchConfig, u: jax.Array) -> jax.Array:
    if cfg.mlp == "swiglu":
        return jax.nn.silu(u)
    if cfg.mlp == "geglu":
        return jax.nn.gelu(u, approximate=True)
    return jax.nn.gelu(u, approximate=True)


def apply_mlp(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    # with a planned mesh active, both GEMMs run through derived
    # DistributedPlans: wi column-sharded over "model" (no collective), wo
    # sigma-sharded over "model" (the TP psum, derived not hand-placed)
    mesh = dplan.current_planned_mesh()
    if mesh is not None:
        h = ops.matmul(x, p["wi"], out_dtype=jnp.float32, mesh=mesh,
                       shard=dplan.tp_matmul_shard(mesh, "col"))
    else:
        h = ops.matmul(x, p["wi"], out_dtype=jnp.float32)
    # NOTE: do NOT with_sharding_constraint the f32 pre-activation — measured
    # to make SPMD replicate the FFN over "model" (7x flops at decode, ~6x at
    # train).  The bf16 post-activation constraint below is sufficient.
    if cfg.use_bias:
        h = h + p["bi"].astype(jnp.float32)
    if cfg.mlp in ("swiglu", "geglu"):
        u, v = jnp.split(h, 2, axis=-1)
        h = _gate_act(cfg, u) * v
    else:
        h = _gate_act(cfg, h)
    h = h.astype(x.dtype)
    h = constrain(h, "batch", None, "d_ff")
    if mesh is not None:
        out = ops.matmul(h, p["wo"], out_dtype=x.dtype, mesh=mesh,
                         shard=dplan.tp_matmul_shard(mesh, "sigma"))
    else:
        out = ops.matmul(h, p["wo"], out_dtype=x.dtype)
    if x.shape[1] > 1:
        # seq-sharded output (train/prefill): the TP partial-sum becomes a
        # reduce-scatter.  NEVER at decode (s=1): forcing a replicated-spec
        # constraint there makes SPMD replicate the whole FFN over "model"
        out = constrain(out, "batch", "seq_sp", None)
    if cfg.use_bias:
        out = out + p["bo"].astype(x.dtype)
    return out


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_tables(positions: jax.Array, dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """sin/cos tables for integer positions (any leading shape) x dim/2."""
    half = dim // 2
    freqs = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array,
               rope_pct: float = 1.0) -> jax.Array:
    """x: (..., seq, heads, head_dim); sin/cos: (..., seq, rot/2) broadcast
    over heads.  Partial rotary (stablelm) rotates the leading rope_pct dims.
    """
    hd = x.shape[-1]
    rot = int(hd * rope_pct)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    s = sin[..., None, :rot // 2]
    c = cos[..., None, :rot // 2]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1) if rot < hd \
        else out.astype(x.dtype)


def sinusoid_positions(positions: jax.Array, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal position encodings."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / (half - 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------

def init_embed(col: Collector, cfg: ArchConfig):
    # d^-1/2 scale: with the sqrt(d) input multiplier (tied/gemma convention)
    # token inputs arrive unit-RMS AND tied logits start ~N(0,1)
    col.param("embed/table", (cfg.vocab_size, cfg.d_model), ("vocab", "d_model"),
              scale=cfg.d_model ** -0.5)
    if not cfg.tie_embeddings:
        col.param("unembed/w", (cfg.d_model, cfg.vocab_size), ("d_model", "vocab"),
                  scale=cfg.d_model ** -0.5)


def embed_tokens(params: dict, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    x = params["embed"]["table"][tokens]
    if cfg.embedding_multiplier:                        # published (granite)
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    elif cfg.tie_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)   # gemma convention
    return constrain(x, "batch", None, None)


# every op of the head, the table's pad in ``ops.matmul`` among them,
# carries ``head`` in its op name, which the device trace reports
@jax.named_scope("head")
def logits_from_hidden(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    # with a planned mesh, the vocab head is column-sharded over "model":
    # the derived plan lands the spec on the right STORED dim of the tied
    # (vocab, d) table automatically (the coefficients know the layout)
    mesh = dplan.current_planned_mesh()
    mesh_kw = (dict(mesh=mesh, shard=dplan.tp_matmul_shard(mesh, "col"))
               if mesh is not None else {})
    if cfg.tie_embeddings:
        # tied head contracts the (vocab, d) table in its STORED layout:
        # matmul(transpose_b=True) lowers to a transposed-operand derived
        # schedule (column-gamma coefficients on the table), so the largest
        # tensor in the model is never transpose-copied.
        logits = ops.matmul(x, params["embed"]["table"], transpose_b=True,
                            out_dtype=jnp.float32, **mesh_kw)
    else:
        logits = ops.matmul(x, params["unembed"]["w"], out_dtype=jnp.float32,
                            **mesh_kw)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    return constrain(logits, "batch", None, "vocab")
