"""Mamba-2 (SSD — state-space duality) block, chunked for training and
recurrent for decode.  [arXiv:2405.21060]

The chunked SSD algorithm is itself a dimension lifting: the sequence axis is
split ``S -> (chunks, chunk_len)`` and the computation decomposes into
block-diagonal (intra-chunk, quadratic-in-q matmuls on the MXU) plus low-rank
(inter-chunk, a carried-state recurrence over chunk states).  This module no
longer hand-rolls that loop: ``ssd_chunked`` is a thin consumer of
``ops.scan_ssd`` — the scan schedule (grid, BlockSpecs, chunk length, the
carried (h, p, n) state scratch and the final-state export) is *derived*
from the lifted recurrent form ``expr.ssd_form`` by the same pipeline as
every GEMM and the flash-attention kernel, with the chunk from
``solve_recurrence_blocks``.

Decode is the dual recurrent form: O(1) state update per token —
state (B, H, p, N);  h' = exp(dt*A) h + dt * x outer B;  y = C . h + D x.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models.common import ArchConfig, Collector


def d_inner(cfg: ArchConfig) -> int:
    return cfg.ssm_expand * cfg.d_model


def n_ssd_heads(cfg: ArchConfig) -> int:
    return d_inner(cfg) // cfg.ssm_head_dim


def conv_dim(cfg: ArchConfig) -> int:
    return d_inner(cfg) + 2 * cfg.ssm_state


def init_mamba2(col: Collector, path: str, cfg: ArchConfig,
                stack: tuple[tuple[int, str], ...] = ()):
    d = cfg.d_model
    din, h, n = d_inner(cfg), n_ssd_heads(cfg), cfg.ssm_state
    lead = tuple(s for s, _ in stack)
    laxes = tuple(a for _, a in stack)
    # in_proj -> [z, x, B, C, dt]
    col.param(f"{path}/w_in", lead + (d, 2 * din + 2 * n + h),
              laxes + ("d_model", "d_inner"), scale=d ** -0.5)
    col.param(f"{path}/conv_w", lead + (cfg.conv_width, conv_dim(cfg)),
              laxes + (None, "d_inner"), scale=cfg.conv_width ** -0.5)
    col.param(f"{path}/conv_b", lead + (conv_dim(cfg),), laxes + ("d_inner",),
              init="zeros")
    col.param(f"{path}/A_log", lead + (h,), laxes + ("ssm_heads",), init="zeros")
    col.param(f"{path}/D", lead + (h,), laxes + ("ssm_heads",), init="ones")
    col.param(f"{path}/dt_bias", lead + (h,), laxes + ("ssm_heads",), init="zeros")
    col.param(f"{path}/norm_scale", lead + (din,), laxes + ("d_inner",), init="ones")
    col.param(f"{path}/w_out", lead + (din, d), laxes + ("d_inner", "d_model"),
              scale=din ** -0.5)


class SSMCache(NamedTuple):
    conv: jax.Array        # (B, conv_width-1, conv_dim) — trailing inputs
    state: jax.Array       # (B, H, p, N) f32


def init_ssm_cache(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16) -> SSMCache:
    h, p, n = n_ssd_heads(cfg), cfg.ssm_head_dim, cfg.ssm_state
    return SSMCache(
        conv=jnp.zeros((batch, cfg.conv_width - 1, conv_dim(cfg)), dtype),
        state=jnp.zeros((batch, h, p, n), jnp.float32))


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv via shifted adds. x: (B,S,C), w: (W,C)."""
    wwidth = w.shape[0]
    out = x * w[-1]
    for i in range(1, wwidth):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i]
        out = out + shifted * w[wwidth - 1 - i]
    return out + b


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, chunk: int | None = None,
                init_state: jax.Array | None = None,
                unroll: bool = False) -> tuple[jax.Array, jax.Array]:
    """SSD over a full sequence.  x: (b,s,h,p), dt: (b,s,h) (post-softplus),
    A: (h,) negative, B,C: (b,s,n).  Returns (y (b,s,h,p), final state
    (b,h,p,n) f32).

    Thin consumer of the derived recurrence subsystem: folds dt into the
    input and the log decay, then hands the carried-state chunked scan to
    ``ops.scan_ssd`` (derived kernel on Pallas backends, chunked-jnp oracle
    on "xla" entries and in the VJP).  ``chunk=None`` lets
    ``solve_recurrence_blocks`` choose the chunk length.
    """
    xf = (x * dt[..., None]).astype(jnp.float32)         # fold dt into x
    dA = (dt * A).astype(jnp.float32)                    # (b,s,h) log decay
    return ops.scan_ssd(xf, dA, B.astype(jnp.float32),
                        C.astype(jnp.float32), init_state=init_state,
                        chunk=chunk, unroll=bool(unroll))


def apply_mamba2(p: dict, x: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, SSMCache]:
    """Full-sequence Mamba-2 block.  Returns output and final cache."""
    b, s, d = x.shape
    din, h, n = d_inner(cfg), n_ssd_heads(cfg), cfg.ssm_state
    hp = cfg.ssm_head_dim
    zxbcdt = ops.matmul(x, p["w_in"], out_dtype=x.dtype)
    z, xbc, dt = jnp.split(zxbcdt, [din, 2 * din + 2 * n], axis=-1)
    xbc = _causal_conv(xbc, p["conv_w"].astype(x.dtype), p["conv_b"].astype(x.dtype))
    xbc = jax.nn.silu(xbc)
    xs, B, C = jnp.split(xbc, [din, din + n], axis=-1)
    xs = constrain(xs, "batch", None, "d_inner")
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(b, s, h, hp)
    y, final = ssd_chunked(xh, dtv, A, B, C,
                           min(cfg.ssm_chunk, s) if cfg.ssm_chunk else None,
                           unroll=bool(cfg.scan_unroll))
    y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(b, s, din).astype(x.dtype)
    # gated RMSNorm (mamba2)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + cfg.norm_eps)
         * p["norm_scale"].astype(jnp.float32)).astype(x.dtype)
    out = ops.matmul(y, p["w_out"], out_dtype=x.dtype)
    # cache: last conv_width-1 pre-conv inputs + final state
    pre = ops.matmul(x[:, -(cfg.conv_width - 1):], p["w_in"],
                     out_dtype=x.dtype)
    conv_tail = pre[..., din:2 * din + 2 * n]
    return out, SSMCache(conv=conv_tail, state=final)


def decode_mamba2(p: dict, x: jax.Array, cache: SSMCache, cfg: ArchConfig
                  ) -> tuple[jax.Array, SSMCache]:
    """One-token recurrent step.  x: (B,1,d)."""
    b, _, d = x.shape
    din, h, n = d_inner(cfg), n_ssd_heads(cfg), cfg.ssm_state
    hp = cfg.ssm_head_dim
    zxbcdt = ops.matmul(x, p["w_in"], out_dtype=x.dtype)
    z, xbc_new, dt = jnp.split(zxbcdt[:, 0], [din, 2 * din + 2 * n], axis=-1)
    # conv over (cached W-1 inputs, new input)
    hist = jnp.concatenate([cache.conv, xbc_new[:, None]], axis=1)  # (B,W,C)
    w = p["conv_w"].astype(x.dtype)
    xbc = jnp.einsum("bwc,wc->bc", hist, w) + p["conv_b"].astype(x.dtype)
    xbc = jax.nn.silu(xbc)
    xs, B, C = jnp.split(xbc, [din, din + n], axis=-1)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(b, h, hp).astype(jnp.float32)
    dA = jnp.exp(dtv * A)                                   # (b,h)
    Bx = jnp.einsum("bhp,bn->bhpn", xh * dtv[..., None], B.astype(jnp.float32))
    state = dA[..., None, None] * cache.state + Bx
    y = jnp.einsum("bhpn,bn->bhp", state, C.astype(jnp.float32))
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(b, din).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + cfg.norm_eps)
         * p["norm_scale"].astype(jnp.float32)).astype(x.dtype)
    out = ops.matmul(y, p["w_out"], out_dtype=x.dtype)[:, None]
    new_conv = hist[:, 1:]
    return out, SSMCache(conv=new_conv, state=state)


def default_ssd_chunk(cfg: ArchConfig) -> int:
    """.. deprecated:: the chunk length is now derived by
    ``solve_recurrence_blocks`` (see ``ops.default_ssd_chunk``) with the
    carried state and chunk intermediates in the VMEM working-set model;
    this config-front wrapper is kept for one release."""
    return ops.default_ssd_chunk(4096, n_ssd_heads(cfg),
                                 cfg.ssm_head_dim, cfg.ssm_state)
