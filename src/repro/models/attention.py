"""Attention variants: MHA / GQA / MQA, windowed (local), and MLA.

Grouped-query attention never materializes repeated K/V heads: queries are
reshaped to (kv_heads, group) and contracted against un-repeated K/V.

Decode paths take a KV cache of static length ``cache_len`` and per-row
positions; masking handles validity.  MLA decode uses the *absorbed* form
with the compressed latent cache (kv_rank + rope_dim per token), which is
the memory story that makes 32k x 128-batch decoding feasible.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models.chunked_attention import chunked_attention
from repro.models.common import ArchConfig, Collector
from repro.models.layers import apply_rope, rope_tables
from repro.core.semiring import MASK_NEG_INF as NEG_INF


def _proj(x: jax.Array, w: jax.Array) -> jax.Array:
    '''bsd,d...->bs... through the unified MoA matmul entry.'''
    return ops.matmul(x, w, out_dtype=x.dtype)


def _out_proj(out: jax.Array, wo: jax.Array, out_dtype) -> jax.Array:
    '''bshk,hkd->bsd: collapse (heads, head_dim), one derived GEMM.'''
    b, s = out.shape[:2]
    return ops.matmul(out.reshape(b, s, -1),
                      wo.reshape(-1, wo.shape[-1]), out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(col: Collector, path: str, cfg: ArchConfig,
                   stack: tuple[tuple[int, str], ...] = (),
                   n_heads: Optional[int] = None,
                   n_kv_heads: Optional[int] = None):
    d, hd = cfg.d_model, cfg.head_dim_
    h = n_heads or cfg.n_heads
    kv = n_kv_heads or cfg.n_kv_heads
    lead = tuple(s for s, _ in stack)
    laxes = tuple(a for _, a in stack)
    col.param(f"{path}/wq", lead + (d, h, hd), laxes + ("d_model", "heads", None),
              scale=d ** -0.5)
    col.param(f"{path}/wk", lead + (d, kv, hd), laxes + ("d_model", "kv_heads", None),
              scale=d ** -0.5)
    col.param(f"{path}/wv", lead + (d, kv, hd), laxes + ("d_model", "kv_heads", None),
              scale=d ** -0.5)
    col.param(f"{path}/wo", lead + (h, hd, d), laxes + ("heads", None, "d_model"),
              scale=(h * hd) ** -0.5)
    if cfg.use_bias:
        col.param(f"{path}/bq", lead + (h, hd), laxes + ("heads", None), init="zeros")
        col.param(f"{path}/bk", lead + (kv, hd), laxes + ("kv_heads", None), init="zeros")
        col.param(f"{path}/bv", lead + (kv, hd), laxes + ("kv_heads", None), init="zeros")
        col.param(f"{path}/bo", lead + (d,), laxes + ("d_model",), init="zeros")


def init_mla(col: Collector, path: str, cfg: ArchConfig,
             stack: tuple[tuple[int, str], ...] = ()):
    d, h = cfg.d_model, cfg.n_heads
    qr, kvr, nope, rope, vd = MLA_DIMS
    lead = tuple(s for s, _ in stack)
    laxes = tuple(a for _, a in stack)
    col.param(f"{path}/wq_a", lead + (d, qr), laxes + ("d_model", None), scale=d ** -0.5)
    col.param(f"{path}/q_norm", lead + (qr,), laxes + (None,), init="ones")
    col.param(f"{path}/wq_b", lead + (qr, h, nope + rope),
              laxes + (None, "heads", None), scale=qr ** -0.5)
    col.param(f"{path}/wkv_a", lead + (d, kvr + rope), laxes + ("d_model", None),
              scale=d ** -0.5)
    col.param(f"{path}/kv_norm", lead + (kvr,), laxes + (None,), init="ones")
    col.param(f"{path}/wkv_b", lead + (kvr, h, nope + vd),
              laxes + (None, "heads", None), scale=kvr ** -0.5)
    col.param(f"{path}/wo", lead + (h, vd, d), laxes + ("heads", None, "d_model"),
              scale=(h * vd) ** -0.5)


# MLA dims (MiniCPM3-4B): q_rank, kv_rank, qk_nope, qk_rope, v_head
MLA_DIMS = (768, 256, 64, 32, 64)


# ---------------------------------------------------------------------------
# core scores/combine (grouped, never repeats KV)
# ---------------------------------------------------------------------------

def softmax_scale(cfg: ArchConfig, hd: int) -> float:
    """The scale on q.k: the published ``attention_multiplier`` where the
    config sets one, else ``hd ** -0.5``."""
    return cfg.attention_multiplier or hd ** -0.5


def _split_groups(q: jax.Array, kv_heads: int) -> jax.Array:
    b, s, h, hd = q.shape
    return q.reshape(b, s, kv_heads, h // kv_heads, hd)


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
            scale: float) -> jax.Array:
    """q: (B,Sq,KV,G,hd); k/v: (B,Sk,KV,hd); mask: (B,1,1,Sq,Sk) or bcastable.
    Returns (B,Sq,KV*G,hd)."""
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v,
                     preferred_element_type=jnp.float32).astype(v.dtype)
    b, sq, kv, g, hd = out.shape
    return out.reshape(b, sq, kv * g, hd)


def _causal_mask(sq: int, sk: int, q_off: jax.Array | int = 0,
                 window: int = 0) -> jax.Array:
    """(sq, sk) boolean mask; query i at absolute pos q_off+i may see keys
    j <= pos, and > pos - window when window > 0."""
    qpos = jnp.arange(sq) + q_off
    kpos = jnp.arange(sk)
    m = kpos[None, :] <= qpos[:, None]
    if window > 0:
        m &= kpos[None, :] > (qpos[:, None] - window)
    return m


# ---------------------------------------------------------------------------
# train/prefill forward (full sequence) — returns per-layer K/V for caching
# ---------------------------------------------------------------------------

class KV(NamedTuple):
    k: jax.Array
    v: jax.Array


def attention_fwd(p: dict, x: jax.Array, cfg: ArchConfig, *,
                  positions: jax.Array, window: int = 0,
                  causal: bool = True, prefix_len: int = 0,
                  kv_override: Optional[KV] = None) -> tuple[jax.Array, KV]:
    """Full-sequence attention.  ``prefix_len``: leading positions attend
    bidirectionally (PaLI-style prefix-LM over image patches).  ``causal``
    False -> fully bidirectional (whisper encoder).  ``kv_override``: use
    given K/V (whisper cross-attention)."""
    if not causal and (window > 0 or prefix_len > 0):
        # mirror _chunk_mask's honor-or-raise contract on EVERY branch —
        # the dense (materialized) path used to silently attend to all keys
        raise ValueError(
            f"window={window} / prefix_len={prefix_len} require causal "
            "attention")
    b, s, d = x.shape
    hd = p["wq"].shape[-1]
    scale = softmax_scale(cfg, hd)
    q = _proj(x, p["wq"])
    q = constrain(q, "batch", "seq_sp", None, None) \
        if cfg.attn_sharding == "sp" else constrain(q, "batch", None, "heads", None)
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
    if kv_override is None:
        k = _proj(x, p["wk"])
        k = constrain(k, "batch", "seq_sp", None, None)
        v = _proj(x, p["wv"])
        v = constrain(v, "batch", "seq_sp", None, None)
        if cfg.use_bias:
            k = k + p["bk"].astype(x.dtype)
            v = v + p["bv"].astype(x.dtype)
        # RoPE is a property of the positions, not of the masking mode:
        # bidirectional/encoder passes with rope_pct > 0 get rotated too
        if cfg.rope_pct > 0:
            sin, cos = rope_tables(positions, int(hd * cfg.rope_pct), cfg.rope_theta)
            q = apply_rope(q, sin, cos, 1.0 if cfg.rope_pct == 1.0 else
                           (hd * cfg.rope_pct) / hd)
            k = apply_rope(k, sin, cos, 1.0 if cfg.rope_pct == 1.0 else
                           (hd * cfg.rope_pct) / hd)
    else:
        k, v = kv_override
    # sequence-parallel attention sharding: q/k/v and the output shard on the
    # seq axis (clean lifting even when kv_heads don't divide the model axis;
    # avoids SPMD involuntary remats on the grouped-head reshape).  "heads"
    # mode is the Megatron-style alternative.
    if cfg.attn_sharding == "sp":
        q = constrain(q, "batch", "seq_sp", None, None)
        k = constrain(k, "batch", "seq_sp", None, None)
        v = constrain(v, "batch", "seq_sp", None, None)
    else:
        q = constrain(q, "batch", None, "heads", None)
        k = constrain(k, "batch", None, "kv_heads", None)
    kvh = k.shape[2]
    qg = _split_groups(q, kvh)
    sk = k.shape[1]
    if cfg.attn_impl == "pallas" and causal:
        # execution path: the flash kernel from the derived recurrent
        # schedule, via the ops-level wrapper whose pad/slice contract
        # accepts ANY sequence length (no silent jnp fallback off block
        # multiples; interpret-mode Pallas on CPU, oracle on "xla").
        # window/prefix_len ride the form as streamed-axis masking metadata
        # — windowed and prefix-LM causal shapes derive their schedules
        # (block-skip included) instead of falling back to the jnp path.
        out = ops.attention(qg, k, v, scale=scale, causal=True,
                            window=window, prefix_len=prefix_len)
    elif s >= cfg.attn_chunk_min_seq and causal:
        out = chunked_attention(qg, k, v, scale=scale, causal=True,
                                window=window, prefix_len=prefix_len,
                                q_chunk=cfg.attn_q_chunk or s,
                                k_chunk=cfg.attn_chunk)
    else:
        if causal:
            m = _causal_mask(s, sk, 0, window)
            if prefix_len > 0:
                bidir = (jnp.arange(s)[:, None] < prefix_len) & \
                        (jnp.arange(sk)[None, :] < prefix_len)
                m = m | bidir
            mask = m[None, None, None]
        else:
            mask = jnp.ones((1, 1, 1, s, sk), bool)
        out = _attend(qg, k, v, mask, scale)
    if cfg.attn_sharding == "sp":
        out = constrain(out, "batch", "seq_sp", None, None)
    else:
        out = constrain(out, "batch", None, "heads", None)
    o = _out_proj(out, p["wo"], x.dtype)
    o = constrain(o, "batch", "seq_sp", None)
    if cfg.use_bias:
        o = o + p["bo"].astype(x.dtype)
    return o, KV(k, v)


def attention_decode(p: dict, x: jax.Array, cache: KV, pos: jax.Array,
                     cfg: ArchConfig, *, window: int = 0
                     ) -> tuple[jax.Array, KV]:
    """One-token decode.  x: (B,1,d); cache k/v: (B,cache_len,KV,hd);
    pos: (B,) absolute position of the new token."""
    b, _, d = x.shape
    hd = p["wq"].shape[-1]
    scale = softmax_scale(cfg, hd)
    q = _proj(x, p["wq"])
    k = _proj(x, p["wk"])
    v = _proj(x, p["wv"])
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.rope_pct > 0:
        sin, cos = rope_tables(pos[:, None], int(hd * cfg.rope_pct), cfg.rope_theta)
        pct = 1.0 if cfg.rope_pct == 1.0 else (hd * cfg.rope_pct) / hd
        q = apply_rope(q, sin, cos, pct)
        k = apply_rope(k, sin, cos, pct)
    # cache update at pos (per-row dynamic index via one-hot to stay static)
    ck = _cache_write(cache.k, k, pos)
    cv = _cache_write(cache.v, v, pos)
    kvh = ck.shape[2]
    qg = _split_groups(q, kvh)
    sk = ck.shape[1]
    kpos = jnp.arange(sk)
    valid = kpos[None, :] <= pos[:, None]
    if window > 0:
        valid &= kpos[None, :] > (pos[:, None] - window)
    mask = valid[:, None, None, None, :]
    out = _attend(qg, ck, cv, mask, scale)
    o = _out_proj(out, p["wo"], x.dtype)
    if cfg.use_bias:
        o = o + p["bo"].astype(x.dtype)
    return o, KV(ck, cv)


def attention_decode_ring(p: dict, x: jax.Array, cache: KV, pos: jax.Array,
                          cfg: ArchConfig) -> tuple[jax.Array, KV]:
    """One-token decode against a RING cache for windowed (local) attention.

    The cache holds exactly the last W tokens: slot j carries the key/value
    of absolute position  kpos_j = pos - ((pos - j) mod W)  (after the write
    at slot pos % W).  This keeps local-attention decode O(W) in both memory
    and compute — the property that makes the 500k-token cells tractable.
    """
    b, _, d = x.shape
    hd = p["wq"].shape[-1]
    scale = softmax_scale(cfg, hd)
    wlen = cache.k.shape[1]
    q = _proj(x, p["wq"])
    k = _proj(x, p["wk"])
    v = _proj(x, p["wv"])
    if cfg.rope_pct > 0:
        sin, cos = rope_tables(pos[:, None], int(hd * cfg.rope_pct), cfg.rope_theta)
        pct = 1.0 if cfg.rope_pct == 1.0 else (hd * cfg.rope_pct) / hd
        q = apply_rope(q, sin, cos, pct)
        k = apply_rope(k, sin, cos, pct)
    slot = pos % wlen
    ck = _cache_write(cache.k, k, slot)
    cv = _cache_write(cache.v, v, slot)
    j = jnp.arange(wlen)[None, :]
    kpos = pos[:, None] - ((pos[:, None] - j) % wlen)
    valid = kpos >= 0
    mask = valid[:, None, None, None, :]
    kvh = ck.shape[2]
    out = _attend(_split_groups(q, kvh), ck, cv, mask, scale)
    o = _out_proj(out, p["wo"], x.dtype)
    if cfg.use_bias:
        o = o + p["bo"].astype(x.dtype)
    return o, KV(ck, cv)


def attention_decode_paged(p: dict, x: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, pos: jax.Array,
                           cfg: ArchConfig, *, page_table: tuple, page: int,
                           window: int = 0, interpret=None
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a PAGED KV cache (one sequence).

    x: (1, 1, d); k_pool/v_pool: (pool_tokens, KV, hd) slab pools; pos: (1,)
    absolute position.  ``page_table`` (static) maps view page -> pool slab;
    the logical cache is the psi view the table describes and the kernel's
    BlockSpec index maps read through it — no gather-copy.  The view starts
    at token 0, so the view-relative position equals ``pos``; with a
    ``window`` the engine may retarget expired view pages at a recycled
    slab, because masking keeps everything outside the window inert.

    The new token's K/V land in the pool by slab arithmetic (a dynamic
    two-step psi index: table[pos // page] picks the slab, pos % page the
    row) — position is runtime data, so this stays one compiled program
    across tokens.  Returns ``(out (1, 1, d), k_pool, v_pool)``.
    """
    hd = p["wq"].shape[-1]
    scale = softmax_scale(cfg, hd)
    q = _proj(x, p["wq"])
    k = _proj(x, p["wk"])
    v = _proj(x, p["wv"])
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.rope_pct > 0:
        sin, cos = rope_tables(pos[:, None], int(hd * cfg.rope_pct),
                               cfg.rope_theta)
        pct = 1.0 if cfg.rope_pct == 1.0 else (hd * cfg.rope_pct) / hd
        q = apply_rope(q, sin, cos, pct)
        k = apply_rope(k, sin, cos, pct)
    table_arr = jnp.asarray(page_table, jnp.int32)
    vpos = pos[0]
    row = table_arr[vpos // page] * page + vpos % page
    k_pool = jax.lax.dynamic_update_slice(
        k_pool, k[0].astype(k_pool.dtype), (row, 0, 0))
    v_pool = jax.lax.dynamic_update_slice(
        v_pool, v[0].astype(v_pool.dtype), (row, 0, 0))
    kvh = k_pool.shape[1]
    h = q.shape[2]
    qg = q[0, 0].reshape(kvh, h // kvh, hd)
    pos_aux = jnp.stack([vpos.astype(jnp.int32), jnp.int32(0)])[None]
    ctx = ops.paged_decode(qg, k_pool, v_pool, pos_aux,
                           page_table=page_table, page=page, scale=scale,
                           window=window, interpret=interpret)
    out = ctx.reshape(1, 1, h, hd).astype(x.dtype)
    o = _out_proj(out, p["wo"], x.dtype)
    if cfg.use_bias:
        o = o + p["bo"].astype(x.dtype)
    return o, k_pool, v_pool


def attention_decode_paged_batched(p: dict, x: jax.Array,
                                   k_pool: jax.Array, v_pool: jax.Array,
                                   pos: jax.Array, cfg: ArchConfig, *,
                                   page_tables: tuple, page: int,
                                   window: int = 0, interpret=None
                                   ) -> tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """One-token decode for EVERY serving slot against the shared slab
    pools — ``attention_decode_paged`` with the slot axis lifted.

    x: (slots, 1, d); pos: (slots,) absolute positions, -1 for a dead
    (padded) slot; ``page_tables`` is the STATIC stacked ``[slot][k]``
    view->slab map.  Each live slot's new K/V land by its own slab
    arithmetic (rows are disjoint across live slots — live tables never
    share a slab).  A dead slot is inert by runtime data, not by its
    table row: its K/V write is routed past the pool and dropped
    (``mode="drop"``), and POS -1 fails every block-skip guard so no key
    it can address ever folds — which is why dead rows may carry ANY
    in-pool entries (stale slabs of a retired slot included) without
    affecting a single live value.  One ``paged_decode_batched`` launch
    serves all slots."""
    hd = p["wq"].shape[-1]
    scale = softmax_scale(cfg, hd)
    q = _proj(x, p["wq"])
    k = _proj(x, p["wk"])
    v = _proj(x, p["wv"])
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.rope_pct > 0:
        sin, cos = rope_tables(pos[:, None], int(hd * cfg.rope_pct),
                               cfg.rope_theta)
        pct = 1.0 if cfg.rope_pct == 1.0 else (hd * cfg.rope_pct) / hd
        q = apply_rope(q, sin, cos, pct)
        k = apply_rope(k, sin, cos, pct)
    slots = x.shape[0]
    table_arr = jnp.asarray(page_tables, jnp.int32)     # (slots, view)
    vpos = pos.astype(jnp.int32)
    rows = table_arr[jnp.arange(slots), vpos // page] * page + vpos % page
    # dead slots (vpos -1) route their write past the pool; drop it there
    rows = jnp.where(vpos >= 0, rows, k_pool.shape[0])
    k_pool = k_pool.at[rows].set(k[:, 0].astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[rows].set(v[:, 0].astype(v_pool.dtype), mode="drop")
    kvh = k_pool.shape[1]
    h = q.shape[2]
    qg = q[:, 0].reshape(slots, kvh, h // kvh, hd)
    pos_aux = jnp.stack([vpos, jnp.zeros_like(vpos)], axis=-1)
    ctx = ops.paged_decode_batched(qg, k_pool, v_pool, pos_aux,
                                   page_tables=page_tables, page=page,
                                   scale=scale, window=window,
                                   interpret=interpret)
    out = ctx.reshape(slots, 1, h, hd).astype(x.dtype)
    o = _out_proj(out, p["wo"], x.dtype)
    if cfg.use_bias:
        o = o + p["bo"].astype(x.dtype)
    return o, k_pool, v_pool


def _cache_write(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write new (B,1,...) into cache (B,S,...) at per-row pos (B,)."""
    b, s = cache.shape[:2]
    oh = jax.nn.one_hot(pos, s, dtype=cache.dtype)          # (B,S)
    oh = oh.reshape(b, s, *([1] * (cache.ndim - 2)))
    return cache * (1 - oh) + new.astype(cache.dtype) * oh


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------

class MLACache(NamedTuple):
    c_kv: jax.Array       # (B, S, kv_rank)
    k_pe: jax.Array       # (B, S, rope_dim)


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def mla_fwd(p: dict, x: jax.Array, cfg: ArchConfig, *, positions: jax.Array
            ) -> tuple[jax.Array, MLACache]:
    """Full-sequence MLA (training/prefill): non-absorbed expansion."""
    qr, kvr, nope, rope, vd = MLA_DIMS
    b, s, d = x.shape
    h = cfg.n_heads
    scale = (nope + rope) ** -0.5
    cq = _rms(_proj(x, p["wq_a"]), p["q_norm"])
    q = _proj(cq, p["wq_b"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_all = _proj(x, p["wkv_a"])
    c_kv = _rms(kv_all[..., :kvr], p["kv_norm"])
    k_pe = kv_all[..., kvr:]
    sin, cos = rope_tables(positions, rope, cfg.rope_theta)
    q_pe = apply_rope(q_pe, sin, cos)
    k_pe = apply_rope(k_pe[:, :, None, :], sin, cos)[:, :, 0, :]
    kv = _proj(c_kv, p["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if s >= cfg.attn_chunk_min_seq:
        # chunked path: fold both score terms into one contraction —
        # q'' = [q_nope, q_pe], k'' = [k_nope, k_pe (broadcast over heads)]
        qq = jnp.concatenate([q_nope, q_pe], axis=-1)        # (b,s,h,nope+rope)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                      k_pe.shape[:2] + (h, rope))], axis=-1)
        qq = constrain(qq, "batch", "seq_sp", None, None)
        kk = constrain(kk, "batch", "seq_sp", None, None)
        out = chunked_attention(qq.reshape(b, s, h, 1, nope + rope),
                                kk, v, scale=scale, causal=True,
                                q_chunk=cfg.attn_q_chunk or s,
                                k_chunk=cfg.attn_chunk)
        out = out.reshape(b, s, h, vd)
    else:
        sc = jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
        sp = jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                        preferred_element_type=jnp.float32)
        scores = (sc + sp) * scale
        mask = _causal_mask(s, s)[None, None]
        scores = jnp.where(mask, scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhn->bqhn", w, v,
                         preferred_element_type=jnp.float32).astype(x.dtype)
    o = _out_proj(out, p["wo"], x.dtype)
    return o, MLACache(c_kv, k_pe)


def mla_decode(p: dict, x: jax.Array, cache: MLACache, pos: jax.Array,
               cfg: ArchConfig) -> tuple[jax.Array, MLACache]:
    """Absorbed one-token MLA decode over the compressed latent cache."""
    qr, kvr, nope, rope, vd = MLA_DIMS
    b, _, d = x.shape
    h = cfg.n_heads
    scale = (nope + rope) ** -0.5
    cq = _rms(_proj(x, p["wq_a"]), p["q_norm"])
    q = _proj(cq, p["wq_b"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_all = _proj(x, p["wkv_a"])
    c_new = _rms(kv_all[..., :kvr], p["kv_norm"])
    kpe_new = kv_all[..., kvr:]
    sin, cos = rope_tables(pos[:, None], rope, cfg.rope_theta)
    q_pe = apply_rope(q_pe, sin, cos)
    kpe_new = apply_rope(kpe_new[:, :, None, :], sin, cos)[:, :, 0, :]
    c_kv = _cache_write(cache.c_kv, c_new, pos)
    k_pe = _cache_write(cache.k_pe, kpe_new, pos)
    # absorb W_UK:  q_tilde[h] = q_nope[h] @ W_UK[:, h, :].T  -> latent
    # space.  The head axis batches independent GEMMs — one more dimension
    # lift, like the expert axis — and ops.head_matmul reads the
    # head-middle (kvr, h, nope) table in its STORED layout through the
    # derived batched-transpose_b schedule: no per-step weight relayout.
    w_uk = p["wkv_b"][..., :nope]                       # (kvr, h, nope)
    w_uv = p["wkv_b"][..., nope:]                       # (kvr, h, vd)
    q_lat = ops.head_matmul(q_nope, w_uk, transpose_b=True,
                            out_dtype=x.dtype)          # (b, s, h, kvr)
    sc = jnp.einsum("bshr,bkr->bhsk", q_lat, c_kv,
                    preferred_element_type=jnp.float32)
    sp = jnp.einsum("bshr,bkr->bhsk", q_pe, k_pe,
                    preferred_element_type=jnp.float32)
    skl = c_kv.shape[1]
    valid = jnp.arange(skl)[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], (sc + sp) * scale, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhsk,bkr->bshr", w, c_kv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    # un-absorb W_UV: the bshr,rhn->bshn contraction is the same per-head
    # batched schedule (no einsum fallback, no relayout of w_uv)
    out = ops.head_matmul(ctx, w_uv, out_dtype=x.dtype)
    o = _out_proj(out, p["wo"], x.dtype)
    return o, MLACache(c_kv, k_pe)
