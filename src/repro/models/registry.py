"""Family dispatch: one uniform interface over every architecture.

    init(cfg, key)                  -> (params, logical_axes)
    loss(params, cfg, batch)        -> (loss, metrics)
    prefill_fn / decode_fn          -> serving entry points
    init_cache(cfg, batch, len)     -> decode cache
    input_specs(cfg, shape)         -> ShapeDtypeStruct stand-ins for every
                                       model input of that (arch x shape) cell
                                       (weak-type-correct, no allocation)
    cache_logical_axes(cache)       -> logical-axis pytree for cache sharding
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.common import ArchConfig, ShapeConfig


# ---------------------------------------------------------------------------
# init / loss / serve dispatch
# ---------------------------------------------------------------------------

def init(cfg: ArchConfig, key: jax.Array) -> tuple[dict, dict]:
    if cfg.family == "audio":
        return encdec.init_encdec(cfg, key)
    return transformer.init_lm(cfg, key)


def loss(params: dict, cfg: ArchConfig, batch: dict) -> tuple[jax.Array, dict]:
    if cfg.family == "audio":
        return encdec.encdec_loss(params, cfg, batch["frames"],
                                  batch["tokens"], batch["targets"])
    return transformer.lm_loss(params, cfg, batch["tokens"], batch["targets"],
                               patches=batch.get("patches"))


def prefill(params: dict, cfg: ArchConfig, batch: dict):
    if cfg.family == "audio":
        return encdec.encdec_prefill(params, cfg, batch["frames"],
                                     batch["tokens"])
    return transformer.prefill(params, cfg, batch["tokens"],
                               patches=batch.get("patches"))


def decode_step(params: dict, cfg: ArchConfig, tokens, pos, cache):
    if cfg.family == "audio":
        return encdec.encdec_decode_step(params, cfg, tokens, pos, cache)
    return transformer.decode_step(params, cfg, tokens, pos, cache)


def init_cache(cfg: ArchConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16):
    if cfg.family == "audio":
        return encdec.init_encdec_cache(cfg, batch, cache_len, dtype)
    return transformer.init_cache(cfg, batch, cache_len, dtype)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; the dry-run lowers against these)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """Model inputs for one (arch x shape) cell, as abstract values.

    train/prefill: token batch (+ stub patch/frame embeddings for vlm/audio);
    decode: one new token per row + positions + the full decode cache.
    """
    b, s = shape.global_batch, shape.seq_len
    i32, bf16 = jnp.int32, jnp.bfloat16
    if shape.kind == "train":
        batch = {"tokens": _sds((b, s), i32), "targets": _sds((b, s), i32)}
        if cfg.family == "vlm":
            # patches are part of the 4k budget: text = s - num_patches
            batch["tokens"] = _sds((b, s - cfg.num_patches), i32)
            batch["targets"] = _sds((b, s - cfg.num_patches), i32)
            batch["patches"] = _sds((b, cfg.num_patches, cfg.d_model), bf16)
        if cfg.family == "audio":
            batch["frames"] = _sds((b, cfg.encoder_seq, cfg.d_model), bf16)
        return {"batch": batch}
    if shape.kind == "prefill":
        batch = {"tokens": _sds((b, s), i32)}
        if cfg.family == "vlm":
            batch["tokens"] = _sds((b, s - cfg.num_patches), i32)
            batch["patches"] = _sds((b, cfg.num_patches, cfg.d_model), bf16)
        if cfg.family == "audio":
            batch["frames"] = _sds((b, cfg.encoder_seq, cfg.d_model), bf16)
        return {"batch": batch}
    # decode: KV cache of seq_len, one new token
    cache = jax.eval_shape(lambda: init_cache(cfg, b, s))
    return {"tokens": _sds((b,), i32), "pos": _sds((b,), i32), "cache": cache}


# ---------------------------------------------------------------------------
# cache logical axes (for sharding the decode cache)
# ---------------------------------------------------------------------------

def cache_logical_axes(cache: Any) -> Any:
    """Logical-axis pytree mirroring a decode cache, keyed off leaf paths.

    KV k/v: (..., batch, kv_seq, kv_heads, hd); MLA c_kv/k_pe: (..., batch,
    kv_seq, rank); SSM conv/state, RG-LRU h/conv as documented in the
    respective modules.
    """
    def axes_for(path, leaf) -> tuple:
        names = [getattr(p, "name", getattr(p, "key", None)) for p in path]
        last = names[-1]
        nd = leaf.ndim
        def lead(n_used):
            return (None,) * (nd - n_used)
        if last in ("k", "v"):
            return lead(4) + ("batch", "kv_seq", "kv_heads", None)
        if last in ("c_kv", "k_pe"):
            return lead(3) + ("batch", "kv_seq", None)
        if last == "state":
            return lead(4) + ("batch", "ssm_heads", None, None)
        if last == "conv":
            return lead(3) + ("batch", None, "d_inner")
        if last == "h":
            return lead(2) + ("batch", "lru")
        return (None,) * nd

    return jax.tree_util.tree_map_with_path(axes_for, cache)
