"""Decoder-LM assembly for all families except enc-dec (see encdec.py).

Layer stacks are *scanned* (params stacked on a leading "layers" axis) so the
HLO stays O(1) in depth; hybrids scan over repeating layer *groups*
(dimension lifting of the layer axis: L -> (groups, pattern)).  The
Mamba-2/attention hybrid (``mamba_hybrid``, granite-4.0-h) keeps that lift
under ``layers/``: every leaf leads with the period axis, then the count of
its kind within the period (mamba mixers, attention mixers, or all layers
for the norms and MLPs), and one scan step runs one period in its
published order.  Bodies are rematerialized (``jax.checkpoint``) and
layer-boundary activations carry a sequence-parallel sharding constraint
so saved activations shard over the "model" axis too.  The ssm and
``mamba_hybrid`` decode bodies scan over layer indices instead of
sliced layers: each GEMM reads its layer of the stacked weight leaf in
place (``ops.layer_slab``), so a decode launch copies no weights.

Entry points (used by train/serve steps and the dry-run):

    init_lm(cfg, key)                       -> (params, logical_axes)
    forward(params, cfg, tokens, patches)   -> (hidden, aux)       train fwd
    prefill(params, cfg, tokens, patches)   -> (logits, cache)
    init_cache(cfg, batch, cache_len)       -> cache pytree
    decode_step(params, cfg, tokens, pos, cache) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ArchConfig, Collector
from repro.models.layers import (apply_mlp, apply_norm, embed_tokens, init_embed,
                                 init_mlp, init_norm, logits_from_hidden)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack(n: int) -> tuple[tuple[int, str], ...]:
    return ((n, "layers"),)


def _scan(cfg: ArchConfig, f, init, xs):
    """lax.scan honoring cfg.scan_unroll (the dry-run cost extraction
    unrolls bodies so XLA cost_analysis counts every layer).  A GEMM in
    the body counts once a step for ``ops.count_gemm_reads``."""
    with ops.gemm_repeats(jax.tree.leaves(xs)[0].shape[0]):
        return jax.lax.scan(f, init, xs, unroll=bool(cfg.scan_unroll))


#: the leaves a decode body hands to its GEMMs whole (``ops.layer_slab``):
#: Mamba's projections, attention's q/k/v/o, the MLP's two
_GEMM_WEIGHTS = frozenset({"w_in", "w_out", "wq", "wk", "wv", "wo", "wi"})


def _decode_layer(tree: dict, j, lead: int = 1) -> dict:
    """Layer ``j`` of a stacked parameter subtree, its ``lead`` leading
    dims flattened into one layer axis, for a decode body: each GEMM
    weight stays the whole stacked leaf, read in place at layer ``j``
    (``ops.layer_slab``); the small leaves (norm scales, conv weights,
    ``A_log``, ``D``, ``dt_bias``, biases) are indexed."""
    def pick(path, t):
        if path[-1].key in _GEMM_WEIGHTS:
            return ops.layer_slab(t, j, lead)
        t = t.reshape((-1,) + t.shape[lead:])
        return jax.lax.dynamic_index_in_dim(t, j, keepdims=False)
    return jax.tree_util.tree_map_with_path(pick, tree)


def init_lm(cfg: ArchConfig, key: jax.Array) -> tuple[dict, dict]:
    col = Collector(key, dtype=jnp.dtype(cfg.dtype))
    init_embed(col, cfg)
    init_norm(col, "final_norm", cfg.d_model, cfg)
    fam = cfg.family
    if fam in ("dense", "vlm"):
        L = cfg.n_layers
        init_norm(col, "layers/ln1", cfg.d_model, cfg, _stack(L))
        if not cfg.parallel_block:
            init_norm(col, "layers/ln2", cfg.d_model, cfg, _stack(L))
        if cfg.attention == "mla":
            attn.init_mla(col, "layers/attn", cfg, _stack(L))
        else:
            attn.init_attention(col, "layers/attn", cfg, _stack(L))
        init_mlp(col, "layers/mlp", cfg, stack=_stack(L))
        if fam == "vlm":
            col.param("frontend/adapter", (cfg.d_model, cfg.d_model),
                      ("d_model", None), scale=cfg.d_model ** -0.5)
    elif fam == "moe":
        if cfg.layer_pattern:                      # llama4: groups of 4 attn
            g = cfg.n_layers // len(cfg.layer_pattern)
            pat = len(cfg.layer_pattern)
            st = ((g, "layers"), (pat, None))
            init_norm(col, "groups/ln1", cfg.d_model, cfg, st)
            init_norm(col, "groups/ln2", cfg.d_model, cfg, st)
            attn.init_attention(col, "groups/attn", cfg, st)
            moe_mod.init_moe(col, "groups/moe", cfg, st)
        else:                                       # deepseek: dense first
            nd = cfg.first_dense_layers
            if nd:
                init_norm(col, "dense_layers/ln1", cfg.d_model, cfg, _stack(nd))
                init_norm(col, "dense_layers/ln2", cfg.d_model, cfg, _stack(nd))
                attn.init_attention(col, "dense_layers/attn", cfg, _stack(nd))
                init_mlp(col, "dense_layers/mlp", cfg, stack=_stack(nd))
            L = cfg.n_layers - nd
            init_norm(col, "layers/ln1", cfg.d_model, cfg, _stack(L))
            init_norm(col, "layers/ln2", cfg.d_model, cfg, _stack(L))
            attn.init_attention(col, "layers/attn", cfg, _stack(L))
            moe_mod.init_moe(col, "layers/moe", cfg, _stack(L))
    elif fam == "ssm":
        L = cfg.n_layers
        init_norm(col, "layers/ln1", cfg.d_model, cfg, _stack(L))
        ssm_mod.init_mamba2(col, "layers/mixer", cfg, _stack(L))
    elif fam == "mamba_hybrid":
        periods, n_mamba, n_attn = _mamba_hybrid_counts(cfg)
        per = len(cfg.layer_pattern)
        st = lambda n: ((periods, "layers"), (n, None))
        init_norm(col, "layers/ln1", cfg.d_model, cfg, st(per))
        init_norm(col, "layers/ln2", cfg.d_model, cfg, st(per))
        ssm_mod.init_mamba2(col, "layers/mamba", cfg, st(n_mamba))
        attn.init_attention(col, "layers/attn", cfg, st(n_attn))
        init_mlp(col, "layers/mlp", cfg, stack=st(per))
    elif fam == "hybrid":
        pat = cfg.layer_pattern                     # e.g. (rglru, rglru, local)
        g = cfg.n_layers // len(pat)
        tail = cfg.n_layers - g * len(pat)
        n_rec = sum(1 for p in pat if p == "rglru")
        n_att = len(pat) - n_rec
        init_norm(col, "groups/rec_ln1", cfg.d_model, cfg, ((g, "layers"), (n_rec, None)))
        init_norm(col, "groups/rec_ln2", cfg.d_model, cfg, ((g, "layers"), (n_rec, None)))
        rglru_mod.init_rglru(col, "groups/rec", cfg, ((g, "layers"), (n_rec, None)))
        init_mlp(col, "groups/rec_mlp", cfg, stack=((g, "layers"), (n_rec, None)))
        init_norm(col, "groups/att_ln1", cfg.d_model, cfg, ((g, "layers"), (n_att, None)))
        init_norm(col, "groups/att_ln2", cfg.d_model, cfg, ((g, "layers"), (n_att, None)))
        attn.init_attention(col, "groups/att", cfg, ((g, "layers"), (n_att, None)))
        init_mlp(col, "groups/att_mlp", cfg, stack=((g, "layers"), (n_att, None)))
        if tail:
            init_norm(col, "tail/ln1", cfg.d_model, cfg, _stack(tail))
            init_norm(col, "tail/ln2", cfg.d_model, cfg, _stack(tail))
            rglru_mod.init_rglru(col, "tail/rec", cfg, _stack(tail))
            init_mlp(col, "tail/mlp", cfg, stack=_stack(tail))
    else:
        raise ValueError(f"init_lm does not handle family {fam!r}")
    return col.done()


# ---------------------------------------------------------------------------
# layer bodies (full-sequence)
# ---------------------------------------------------------------------------

class Aux(NamedTuple):
    moe_aux: jax.Array
    moe_z: jax.Array
    dropped: jax.Array

    @staticmethod
    def zero() -> "Aux":
        z = jnp.zeros((), jnp.float32)
        return Aux(z, z, z)

    def __add__(self, o: "Aux") -> "Aux":
        return Aux(self.moe_aux + o.moe_aux, self.moe_z + o.moe_z,
                   self.dropped + o.dropped)


def _dense_block(lp: dict, x: jax.Array, cfg: ArchConfig, positions,
                 window: int = 0, prefix_len: int = 0):
    h = apply_norm(lp["ln1"], x, cfg)
    if cfg.attention == "mla":
        a_out, kv = attn.mla_fwd(lp["attn"], h, cfg, positions=positions)
    else:
        a_out, kv = attn.attention_fwd(lp["attn"], h, cfg, positions=positions,
                                       window=window, prefix_len=prefix_len)
    if cfg.parallel_block:
        m_out = apply_mlp(lp["mlp"], h, cfg)
        x = x + a_out + m_out
    else:
        x = x + a_out
        h2 = apply_norm(lp["ln2"], x, cfg)
        x = x + apply_mlp(lp["mlp"], h2, cfg)
    x = constrain(x, "batch", "seq_sp", None)
    return x, kv


def _moe_block(lp: dict, x: jax.Array, cfg: ArchConfig, positions,
               window: int = 0):
    h = apply_norm(lp["ln1"], x, cfg)
    a_out, kv = attn.attention_fwd(lp["attn"], h, cfg, positions=positions,
                                   window=window)
    x = x + a_out
    h2 = apply_norm(lp["ln2"], x, cfg)
    m_out, stats = moe_mod.apply_moe(lp["moe"], h2, cfg)
    x = x + m_out
    x = constrain(x, "batch", "seq_sp", None)
    return x, kv, Aux(stats.aux_loss, stats.z_loss, stats.dropped_frac)


def _mamba_hybrid_counts(cfg: ArchConfig) -> tuple[int, int, int]:
    """(periods, mamba mixers a period, attention mixers a period)."""
    pat = cfg.layer_pattern
    if not pat or set(pat) - {"mamba", "attn"} or cfg.n_layers % len(pat):
        raise ValueError(f"mamba_hybrid needs n_layers a multiple of a "
                         f"mamba/attn pattern, not {cfg.n_layers} over {pat}")
    n_attn = pat.count("attn")
    return cfg.n_layers // len(pat), len(pat) - n_attn, n_attn


def _mamba_hybrid_period(layer, x: jax.Array, cfg: ArchConfig, mixer
                         ) -> jax.Array:
    """One period of the Mamba-2/attention hybrid in its published order:
    each layer is ``x + r * mixer(norm(x))`` then ``x + r * mlp(norm(x))``,
    ``r`` the residual multiplier.  ``layer(name, i)`` gives the
    parameters of the period's ``i``-th entry under ``name`` (``ln1``,
    ``ln2``, ``mlp`` or a mixer kind).  ``mixer(kind, i, p, h)`` runs the
    period's ``i``-th mixer of that kind (``p`` its parameters) on ``h``
    and keeps its cache itself."""
    r = cfg.residual_multiplier
    count = {"mamba": 0, "attn": 0}
    for i, kind in enumerate(cfg.layer_pattern):
        j = count[kind]
        with jax.named_scope(kind):
            h = apply_norm(layer("ln1", i), x, cfg)
            x = x + mixer(kind, j, layer(kind, j), h) * r
        count[kind] += 1
        with jax.named_scope("mlp"):
            h = apply_norm(layer("ln2", i), x, cfg)
            x = x + apply_mlp(layer("mlp", i), h, cfg) * r
        x = constrain(x, "batch", "seq_sp", None)
    return x


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill) — returns caches per layer
# ---------------------------------------------------------------------------

def forward(params: dict, cfg: ArchConfig, tokens: jax.Array,
            patches: Optional[jax.Array] = None
            ) -> tuple[jax.Array, Any, Aux]:
    """Returns (hidden (B,S,d), cache pytree (stacked per layer), aux)."""
    x = embed_tokens(params, tokens, cfg)
    prefix_len = 0
    if cfg.family == "vlm":
        assert patches is not None
        pe = jnp.einsum("bpd,de->bpe", patches.astype(x.dtype),
                        params["frontend"]["adapter"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
        prefix_len = patches.shape[1]
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    if not cfg.remat:
        rm = lambda f: f
    elif cfg.remat_policy == "dots":
        rm = functools.partial(
            jax.checkpoint,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    else:
        rm = jax.checkpoint
    fam = cfg.family
    aux = Aux.zero()

    if fam in ("dense", "vlm"):
        @rm
        def body(xc, lp):
            return _dense_block(lp, xc, cfg, positions, cfg.local_window,
                                prefix_len)
        x, kvs = _scan(cfg, lambda xc, lp: body(xc, lp), x, params["layers"])
        cache = kvs
    elif fam == "moe" and cfg.layer_pattern:
        pat = cfg.layer_pattern

        @rm
        def body(xc, lp):
            kvs, auxes = [], Aux.zero()
            for i, kind in enumerate(pat):
                sub = jax.tree.map(lambda t: t[i], lp)
                win = cfg.local_window if kind == "local" else 0
                # nested remat per sublayer: the group body unrolls
                # len(pattern) layers — without this all their backward
                # transients are live at once
                blk = (jax.checkpoint(_moe_block, static_argnums=(2, 4))
                       if cfg.remat else _moe_block)
                xc, kv, a = blk(sub, xc, cfg, positions, win)
                kvs.append(kv)
                auxes = auxes + a
            return xc, (jax.tree.map(lambda *t: jnp.stack(t), *kvs), auxes)
        x, (kvs, auxes) = _scan(cfg, body, x, params["groups"])
        aux = Aux(auxes.moe_aux.sum(), auxes.moe_z.sum(), auxes.dropped.mean())
        cache = kvs
    elif fam == "moe":
        dense_kvs = []
        if cfg.first_dense_layers:
            for i in range(cfg.first_dense_layers):
                lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
                x, kv = _dense_block(lp, x, cfg, positions)
                dense_kvs.append(kv)

        @rm
        def body(xc, lp):
            xc, kv, a = _moe_block(lp, xc, cfg, positions)
            return xc, (kv, a)
        x, (kvs, auxes) = _scan(cfg, body, x, params["layers"])
        aux = Aux(auxes.moe_aux.sum(), auxes.moe_z.sum(), auxes.dropped.mean())
        cache = {"moe": kvs}
        if dense_kvs:
            cache["dense"] = jax.tree.map(lambda *t: jnp.stack(t), *dense_kvs)
    elif fam == "ssm":
        @rm
        def body(xc, lp):
            h = apply_norm(lp["ln1"], xc, cfg)
            out, c = ssm_mod.apply_mamba2(lp["mixer"], h, cfg)
            xc = constrain(xc + out, "batch", "seq_sp", None)
            return xc, c
        x, cache = _scan(cfg, body, x, params["layers"])
    elif fam == "mamba_hybrid":
        @rm
        def body(xc, lp):
            caches = {"mamba": [], "attn": []}

            def mixer(kind, _, p, h):
                out, c = (ssm_mod.apply_mamba2(p, h, cfg) if kind == "mamba"
                          else attn.attention_fwd(p, h, cfg,
                                                  positions=positions))
                caches[kind].append(c)
                return out
            xc = _mamba_hybrid_period(
                lambda name, i: jax.tree.map(lambda t: t[i], lp[name]),
                xc, cfg, mixer)
            return xc, {k: jax.tree.map(lambda *t: jnp.stack(t), *v)
                        for k, v in caches.items()}
        x, cache = _scan(cfg, body, x, params["layers"])
        # cache: {"mamba": SSMCache, "attn": KV}, leaves (periods, count, B, ...)
    elif fam == "hybrid":
        pat = cfg.layer_pattern

        @rm
        def body(xc, lp):
            rec_caches, att_caches = [], []
            ri, ai = 0, 0
            for kind in pat:
                if kind == "rglru":
                    sub = jax.tree.map(lambda t: t[ri], {
                        "ln1": lp["rec_ln1"], "ln2": lp["rec_ln2"],
                        "rec": lp["rec"], "mlp": lp["rec_mlp"]})
                    h = apply_norm(sub["ln1"], xc, cfg)
                    out, c = rglru_mod.apply_rglru(sub["rec"], h, cfg)
                    xc = xc + out
                    h2 = apply_norm(sub["ln2"], xc, cfg)
                    xc = xc + apply_mlp(sub["mlp"], h2, cfg)
                    rec_caches.append(c)
                    ri += 1
                else:
                    sub = jax.tree.map(lambda t: t[ai], {
                        "ln1": lp["att_ln1"], "ln2": lp["att_ln2"],
                        "attn": lp["att"], "mlp": lp["att_mlp"]})
                    h = apply_norm(sub["ln1"], xc, cfg)
                    a_out, kv = attn.attention_fwd(
                        sub["attn"], h, cfg, positions=positions,
                        window=cfg.local_window)
                    xc = xc + a_out
                    h2 = apply_norm(sub["ln2"], xc, cfg)
                    xc = xc + apply_mlp(sub["mlp"], h2, cfg)
                    att_caches.append(kv)
                    ai += 1
                xc = constrain(xc, "batch", "seq_sp", None)
            rc = jax.tree.map(lambda *t: jnp.stack(t), *rec_caches)
            ac = jax.tree.map(lambda *t: jnp.stack(t), *att_caches)
            return xc, (rc, ac)
        x, (rec_c, att_c) = _scan(cfg, body, x, params["groups"])
        tail_caches = []
        if "tail" in params:
            nt = params["tail"]["ln1"]["scale"].shape[0]
            for i in range(nt):
                lp = jax.tree.map(lambda t: t[i], params["tail"])
                h = apply_norm(lp["ln1"], x, cfg)
                out, c = rglru_mod.apply_rglru(lp["rec"], h, cfg)
                x = x + out
                h2 = apply_norm(lp["ln2"], x, cfg)
                x = x + apply_mlp(lp["mlp"], h2, cfg)
                tail_caches.append(c)
        cache = {"rec": rec_c, "att": att_c}
        if tail_caches:
            cache["tail"] = jax.tree.map(lambda *t: jnp.stack(t), *tail_caches)
    else:
        raise ValueError(fam)

    x = apply_norm(params["final_norm"], x, cfg)
    return x, cache, aux


# ---------------------------------------------------------------------------
# caches + decode
# ---------------------------------------------------------------------------

def _kv_cache(shape_lead: tuple[int, ...], b: int, s: int, kv: int, hd: int,
              dtype) -> attn.KV:
    return attn.KV(k=jnp.zeros(shape_lead + (b, s, kv, hd), dtype),
                   v=jnp.zeros(shape_lead + (b, s, kv, hd), dtype))


def init_cache(cfg: ArchConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Decode-cache pytree.  Windowed layers get RING caches of length
    min(window, cache_len); full-attention layers get full-length caches."""
    hd, kv = cfg.head_dim_, cfg.n_kv_heads
    fam = cfg.family
    wlen = min(cfg.local_window, cache_len) if cfg.local_window else cache_len
    if fam in ("dense", "vlm"):
        if cfg.attention == "mla":
            _, kvr, _, rope, _ = attn.MLA_DIMS
            return {"layers": attn.MLACache(
                c_kv=jnp.zeros((cfg.n_layers, batch, cache_len, kvr), dtype),
                k_pe=jnp.zeros((cfg.n_layers, batch, cache_len, rope), dtype))}
        return {"layers": _kv_cache((cfg.n_layers,), batch, cache_len, kv, hd, dtype)}
    if fam == "moe" and cfg.layer_pattern:
        pat = cfg.layer_pattern
        g = cfg.n_layers // len(pat)
        nl = sum(1 for p in pat if p == "local")
        nf = len(pat) - nl
        return {"local": _kv_cache((g, nl), batch, wlen, kv, hd, dtype),
                "full": _kv_cache((g, nf), batch, cache_len, kv, hd, dtype)}
    if fam == "moe":
        nd = cfg.first_dense_layers
        out = {"moe": _kv_cache((cfg.n_layers - nd,), batch, cache_len, kv, hd, dtype)}
        if nd:
            out["dense"] = _kv_cache((nd,), batch, cache_len, kv, hd, dtype)
        return out
    if fam == "ssm":
        c = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return {"layers": jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (cfg.n_layers,) + t.shape), c)}
    if fam == "mamba_hybrid":
        # the slot (batch) axis sits on axis 1 of every leaf, under the
        # layers of its kind, as the engine's stacked cache needs
        periods, n_mamba, n_attn = _mamba_hybrid_counts(cfg)
        c = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return {"mamba": jax.tree.map(lambda t: jnp.broadcast_to(
                    t[None], (periods * n_mamba,) + t.shape), c),
                "attn": _kv_cache((periods * n_attn,), batch, cache_len, kv,
                                  hd, dtype)}
    if fam == "hybrid":
        pat = cfg.layer_pattern
        g = cfg.n_layers // len(pat)
        tail = cfg.n_layers - g * len(pat)
        n_rec = sum(1 for p in pat if p == "rglru")
        n_att = len(pat) - n_rec
        rc = rglru_mod.init_rglru_cache(cfg, batch, dtype)
        out = {
            "rec": jax.tree.map(
                lambda t: jnp.broadcast_to(t[None, None], (g, n_rec) + t.shape), rc),
            "att": _kv_cache((g, n_att), batch, wlen, kv, hd, dtype),
        }
        if tail:
            out["tail"] = jax.tree.map(
                lambda t: jnp.broadcast_to(t[None], (tail,) + t.shape), rc)
        return out
    raise ValueError(fam)


def _dense_decode_block(lp: dict, x: jax.Array, kvc, pos, cfg: ArchConfig,
                        window: int = 0, ring: bool = False):
    h = apply_norm(lp["ln1"], x, cfg)
    if cfg.attention == "mla":
        a_out, kvc = attn.mla_decode(lp["attn"], h, kvc, pos, cfg)
    elif ring:
        a_out, kvc = attn.attention_decode_ring(lp["attn"], h, kvc, pos, cfg)
    else:
        a_out, kvc = attn.attention_decode(lp["attn"], h, kvc, pos, cfg,
                                           window=window)
    if cfg.parallel_block:
        m_out = apply_mlp(lp["mlp"], h, cfg)
        x = x + a_out + m_out
    else:
        x = x + a_out
        h2 = apply_norm(lp["ln2"], x, cfg)
        x = x + apply_mlp(lp["mlp"], h2, cfg)
    return x, kvc


def decode_step(params: dict, cfg: ArchConfig, tokens: jax.Array,
                pos: jax.Array, cache: dict) -> tuple[jax.Array, dict]:
    """One decode step.  tokens: (B,) int32; pos: (B,) absolute positions.
    Returns (logits (B, vocab), new cache)."""
    x = embed_tokens(params, tokens[:, None], cfg)
    fam = cfg.family

    if fam in ("dense", "vlm"):
        ring = bool(cfg.local_window)

        def body(xc, scan_in):
            lp, kvc = scan_in
            xc, kvc = _dense_decode_block(lp, xc, kvc, pos, cfg,
                                          window=cfg.local_window, ring=ring)
            return xc, kvc
        x, new_kv = _scan(cfg, body, x, (params["layers"], cache["layers"]))
        new_cache = {"layers": new_kv}
    elif fam == "moe" and cfg.layer_pattern:
        pat = cfg.layer_pattern

        def body(xc, scan_in):
            lp, cl, cf = scan_in
            li, fi = 0, 0
            new_l, new_f = [], []
            for i, kind in enumerate(pat):
                sub = jax.tree.map(lambda t: t[i], lp)
                h = apply_norm(sub["ln1"], xc, cfg)
                if kind == "local":
                    kvc = jax.tree.map(lambda t: t[li], cl)
                    a_out, kvc = attn.attention_decode_ring(sub["attn"], h,
                                                            kvc, pos, cfg)
                    new_l.append(kvc)
                    li += 1
                else:
                    kvc = jax.tree.map(lambda t: t[fi], cf)
                    a_out, kvc = attn.attention_decode(sub["attn"], h, kvc,
                                                       pos, cfg)
                    new_f.append(kvc)
                    fi += 1
                xc = xc + a_out
                h2 = apply_norm(sub["ln2"], xc, cfg)
                m_out, _ = moe_mod.apply_moe(sub["moe"], h2, cfg)
                xc = xc + m_out
            stk = lambda lst: jax.tree.map(lambda *t: jnp.stack(t), *lst)
            return xc, (stk(new_l), stk(new_f))
        x, (nl, nf) = _scan(
            cfg, body, x, (params["groups"], cache["local"], cache["full"]))
        new_cache = {"local": nl, "full": nf}
    elif fam == "moe":
        new_cache = {}
        if cfg.first_dense_layers:
            nd_kvs = []
            for i in range(cfg.first_dense_layers):
                lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
                kvc = jax.tree.map(lambda t: t[i], cache["dense"])
                x, kvc = _dense_decode_block(lp, x, kvc, pos, cfg)
                nd_kvs.append(kvc)
            new_cache["dense"] = jax.tree.map(lambda *t: jnp.stack(t), *nd_kvs)

        def body(xc, scan_in):
            lp, kvc = scan_in
            h = apply_norm(lp["ln1"], xc, cfg)
            a_out, kvc = attn.attention_decode(lp["attn"], h, kvc, pos, cfg)
            xc = xc + a_out
            h2 = apply_norm(lp["ln2"], xc, cfg)
            m_out, _ = moe_mod.apply_moe(lp["moe"], h2, cfg)
            return xc + m_out, kvc
        x, nm = _scan(cfg, body, x, (params["layers"], cache["moe"]))
        new_cache["moe"] = nm
    elif fam == "ssm":
        # the scan runs over layer indices; the stacked weights stay loop
        # invariants that each GEMM reads in place at its layer
        def body(xc, scan_in):
            c, li = scan_in
            lp = _decode_layer(params["layers"], li)
            h = apply_norm(lp["ln1"], xc, cfg)
            out, c = ssm_mod.decode_mamba2(lp["mixer"], h, c, cfg)
            return xc + out, c
        x, nc = _scan(cfg, body, x, (cache["layers"],
                                     jnp.arange(cfg.n_layers)))
        new_cache = {"layers": nc}
    elif fam == "mamba_hybrid":
        # the scan runs over period indices: the stacked weights stay loop
        # invariants, each GEMM reading its layer ``g * n + i`` in place;
        # the whole cache rides the carry and each layer updates its own
        # index in place: no per-period stack of new states
        periods, n_mamba, n_attn = _mamba_hybrid_counts(cfg)
        per = {"mamba": n_mamba, "attn": n_attn}
        per.update(dict.fromkeys(("ln1", "ln2", "mlp"),
                                 len(cfg.layer_pattern)))

        def body(carry, g):
            xc, cc = carry
            cc = dict(cc)

            def mixer(kind, i, p, h):
                j = g * per[kind] + i
                c = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
                    t, j, keepdims=False), cc[kind])
                out, c = (ssm_mod.decode_mamba2(p, h, c, cfg)
                          if kind == "mamba"
                          else attn.attention_decode(p, h, c, pos, cfg))
                cc[kind] = jax.tree.map(
                    lambda t, u: jax.lax.dynamic_update_index_in_dim(
                        t, u.astype(t.dtype), j, 0), cc[kind], c)
                return out

            def layer(name, i):
                return _decode_layer(params["layers"][name],
                                     g * per[name] + i, lead=2)
            return (_mamba_hybrid_period(layer, xc, cfg, mixer), cc), None
        (x, new_cache), _ = _scan(cfg, body, (x, dict(cache)),
                                  jnp.arange(periods))
    elif fam == "hybrid":
        pat = cfg.layer_pattern

        def body(xc, scan_in):
            lp, crec, catt = scan_in
            ri, ai = 0, 0
            new_r, new_a = [], []
            for kind in pat:
                if kind == "rglru":
                    sub = jax.tree.map(lambda t: t[ri], {
                        "ln1": lp["rec_ln1"], "ln2": lp["rec_ln2"],
                        "rec": lp["rec"], "mlp": lp["rec_mlp"]})
                    c = jax.tree.map(lambda t: t[ri], crec)
                    h = apply_norm(sub["ln1"], xc, cfg)
                    out, c = rglru_mod.decode_rglru(sub["rec"], h, c, cfg)
                    xc = xc + out
                    h2 = apply_norm(sub["ln2"], xc, cfg)
                    xc = xc + apply_mlp(sub["mlp"], h2, cfg)
                    new_r.append(c)
                    ri += 1
                else:
                    sub = jax.tree.map(lambda t: t[ai], {
                        "ln1": lp["att_ln1"], "ln2": lp["att_ln2"],
                        "attn": lp["att"], "mlp": lp["att_mlp"]})
                    c = jax.tree.map(lambda t: t[ai], catt)
                    h = apply_norm(sub["ln1"], xc, cfg)
                    a_out, c = attn.attention_decode_ring(sub["attn"], h, c,
                                                          pos, cfg)
                    xc = xc + a_out
                    h2 = apply_norm(sub["ln2"], xc, cfg)
                    xc = xc + apply_mlp(sub["mlp"], h2, cfg)
                    new_a.append(c)
                    ai += 1
            stk = lambda lst: jax.tree.map(lambda *t: jnp.stack(t), *lst)
            return xc, (stk(new_r), stk(new_a))
        x, (nr, na) = _scan(
            cfg, body, x, (params["groups"], cache["rec"], cache["att"]))
        new_cache = {"rec": nr, "att": na}
        if "tail" in cache:
            nt_list = []
            nt = params["tail"]["ln1"]["scale"].shape[0]
            for i in range(nt):
                lp = jax.tree.map(lambda t: t[i], params["tail"])
                c = jax.tree.map(lambda t: t[i], cache["tail"])
                h = apply_norm(lp["ln1"], x, cfg)
                out, c = rglru_mod.decode_rglru(lp["rec"], h, c, cfg)
                x = x + out
                h2 = apply_norm(lp["ln2"], x, cfg)
                x = x + apply_mlp(lp["mlp"], h2, cfg)
                nt_list.append(c)
            new_cache["tail"] = jax.tree.map(lambda *t: jnp.stack(t), *nt_list)
    else:
        raise ValueError(fam)

    x = apply_norm(params["final_norm"], x, cfg)
    logits = logits_from_hidden(params, x, cfg)[:, 0]
    return logits, new_cache


def prefill(params: dict, cfg: ArchConfig, tokens: jax.Array,
            patches: Optional[jax.Array] = None) -> tuple[jax.Array, Any]:
    """Full-prompt forward; returns (last-position logits (B, vocab), the
    per-layer cache in forward layout)."""
    hidden, cache, _ = forward(params, cfg, tokens, patches)
    logits = logits_from_hidden(params, hidden[:, -1:], cfg)[:, 0]
    return logits, cache


def has_prefill_decode_relayout(cfg: ArchConfig) -> bool:
    """True when ``prefill_cache_to_decode`` can re-lay this family's
    forward cache (the policy is config-only, so callers can decide
    before paying for the prefill pass)."""
    return ((cfg.family == "dense" and not cfg.local_window)
            or cfg.family in ("ssm", "mamba_hybrid"))


def prefill_cache_to_decode(cfg: ArchConfig, cache, cache_len: int):
    """Re-lay a forward-layout prefill cache as a decode cache.

    Returns None for families whose decode cache has no direct forward
    equivalent — ring caches (windowed dense), grouped layer patterns,
    RG-LRU hybrid stacks, vlm (prefill takes patches) — which must keep the
    token-by-token ingestion scan.  Dense full-attention KV/MLA caches pad
    the sequence axis out to ``cache_len`` (later positions are masked
    until written); ssm caches carry forward unchanged — the final state
    IS the decode state.  The Mamba-2/attention hybrid does both, after
    folding its (periods, count) axes into one layer axis."""
    def pad(t):
        return jnp.pad(t, [(0, 0), (0, 0), (0, cache_len - t.shape[2])] +
                       [(0, 0)] * (t.ndim - 3))
    if cfg.family == "dense" and not cfg.local_window:
        return {"layers": jax.tree.map(pad, cache)}
    if cfg.family == "ssm":
        return {"layers": cache}
    if cfg.family == "mamba_hybrid":
        flat = lambda t: t.reshape((-1,) + t.shape[2:])
        return {"mamba": jax.tree.map(flat, cache["mamba"]),
                "attn": jax.tree.map(lambda t: pad(flat(t)), cache["attn"])}
    return None


def init_paged_pools(cfg: ArchConfig, pool_tokens: int,
                     dtype=jnp.float32) -> dict:
    """Per-layer stacked K/V slab pools for paged decode: one sequence's
    logical cache is a psi view over these, described by its page table
    (shared across layers — every layer writes the same positions)."""
    if cfg.family not in ("dense", "vlm") or cfg.attention == "mla":
        raise ValueError(
            f"paged pools cover dense/vlm GQA decode, not "
            f"family={cfg.family!r} attention={cfg.attention!r}")
    shape = (cfg.n_layers, pool_tokens, cfg.n_kv_heads, cfg.head_dim_)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_step_paged(params: dict, cfg: ArchConfig, tokens: jax.Array,
                      pos: jax.Array, pools: dict, *, page_table: tuple,
                      page: int, interpret=None) -> tuple[jax.Array, dict]:
    """One decode step for ONE sequence through its paged KV view.

    tokens/pos: (1,) int32 (position is runtime data — one compiled program
    per page table, not per token).  ``page_table`` is static: it re-keys
    the derived decode kernel only when the engine allocates a page.
    Returns (logits (1, vocab), updated pools).
    """
    if cfg.family not in ("dense", "vlm") or cfg.attention == "mla":
        raise ValueError(f"decode_step_paged does not handle "
                         f"family={cfg.family!r}/{cfg.attention!r}")
    x = embed_tokens(params, tokens[:, None], cfg)

    def body(xc, scan_in):
        lp, kp, vp = scan_in
        h = apply_norm(lp["ln1"], xc, cfg)
        a_out, kp, vp = attn.attention_decode_paged(
            lp["attn"], h, kp, vp, pos, cfg, page_table=page_table,
            page=page, window=cfg.local_window, interpret=interpret)
        if cfg.parallel_block:
            m_out = apply_mlp(lp["mlp"], h, cfg)
            xc = xc + a_out + m_out
        else:
            xc = xc + a_out
            h2 = apply_norm(lp["ln2"], xc, cfg)
            xc = xc + apply_mlp(lp["mlp"], h2, cfg)
        return xc, (kp, vp)

    x, (nk, nv) = _scan(cfg, body, x, (params["layers"],
                                       pools["k"], pools["v"]))
    x = apply_norm(params["final_norm"], x, cfg)
    logits = logits_from_hidden(params, x, cfg)[:, 0]
    return logits, {"k": nk, "v": nv}


def decode_step_paged_batched(params: dict, cfg: ArchConfig,
                              tokens: jax.Array, pos: jax.Array,
                              pools: dict, *, page_tables: tuple, page: int,
                              interpret=None) -> tuple[jax.Array, dict]:
    """One decode step for EVERY serving slot through the stacked paged
    view — one derived kernel launch per layer covers all slots.

    tokens/pos: (slots,) int32; a dead (padded) slot carries pos -1 —
    its K/V write drops and no key folds, whatever its table row says —
    so slot-count changes re-key nothing.  ``page_tables`` is the static
    stacked ``[slot][k]`` map; it re-keys the derived kernel only when
    the engine allocates a page.  Returns (logits (slots, vocab),
    updated pools); dead rows are garbage the engine drops.
    """
    if cfg.family not in ("dense", "vlm") or cfg.attention == "mla":
        raise ValueError(f"decode_step_paged does not handle "
                         f"family={cfg.family!r}/{cfg.attention!r}")
    x = embed_tokens(params, tokens[:, None], cfg)

    def body(xc, scan_in):
        lp, kp, vp = scan_in
        h = apply_norm(lp["ln1"], xc, cfg)
        a_out, kp, vp = attn.attention_decode_paged_batched(
            lp["attn"], h, kp, vp, pos, cfg, page_tables=page_tables,
            page=page, window=cfg.local_window, interpret=interpret)
        if cfg.parallel_block:
            m_out = apply_mlp(lp["mlp"], h, cfg)
            xc = xc + a_out + m_out
        else:
            xc = xc + a_out
            h2 = apply_norm(lp["ln2"], xc, cfg)
            xc = xc + apply_mlp(lp["mlp"], h2, cfg)
        return xc, (kp, vp)

    x, (nk, nv) = _scan(cfg, body, x, (params["layers"],
                                       pools["k"], pools["v"]))
    x = apply_norm(params["final_norm"], x, cfg)
    logits = logits_from_hidden(params, x, cfg)[:, 0]
    return logits, {"k": nk, "v": nv}


def lm_loss(params: dict, cfg: ArchConfig, tokens: jax.Array,
            targets: jax.Array, patches: Optional[jax.Array] = None,
            aux_weight: float = 0.01, z_weight: float = 1e-3
            ) -> tuple[jax.Array, dict]:
    hidden, _, aux = forward(params, cfg, tokens, patches)
    if cfg.family == "vlm":                       # loss on text positions only
        hidden = hidden[:, patches.shape[1]:]
    logits = logits_from_hidden(params, hidden, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    total = loss + aux_weight * aux.moe_aux + z_weight * aux.moe_z
    return total, {"nll": loss, "moe_aux": aux.moe_aux, "moe_z": aux.moe_z,
                   "dropped": aux.dropped}
