"""Mixture-of-Experts FFN: shared + fine-grained routed experts (DeepSeekMoE /
Llama-4 style), with sort-based capacity-padded dispatch.

Dispatch is the MoA story again: the token axis is dimension-lifted
``tokens -> (experts, capacity)`` — a data-dependent lifting realized with a
static-shaped sort + scatter so it pjit-compiles on any mesh.  Expert weights
carry the logical axis "experts", which the sharding rules lift onto the
"model" mesh axis (expert parallelism); the expert GEMM itself is the same
blocked MoA kernel, batched over the lifted expert axis
(``repro.kernels.expert_gemm``).

Aux losses: load-balance (Switch-style) + router z-loss, returned for logging.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models.common import ArchConfig, Collector
from repro.models.layers import _gate_act


def init_moe(col: Collector, path: str, cfg: ArchConfig,
             stack: tuple[tuple[int, str], ...] = ()):
    d, f, e = cfg.d_model, cfg.moe_ff, cfg.n_experts
    lead = tuple(s for s, _ in stack)
    laxes = tuple(a for _, a in stack)
    col.param(f"{path}/router", lead + (d, e), laxes + ("d_model", "experts"),
              scale=d ** -0.5, dtype=jnp.float32)
    col.param(f"{path}/wi", lead + (e, d, 2 * f),
              laxes + ("experts", "d_model", "moe_ff"), scale=d ** -0.5)
    col.param(f"{path}/wo", lead + (e, f, d),
              laxes + ("experts", "moe_ff", "d_model"), scale=f ** -0.5)
    if cfg.n_shared_experts:
        fs = cfg.moe_ff * cfg.n_shared_experts
        col.param(f"{path}/shared_wi", lead + (d, 2 * fs),
                  laxes + ("d_model", "d_ff"), scale=d ** -0.5)
        col.param(f"{path}/shared_wo", lead + (fs, d),
                  laxes + ("d_ff", "d_model"), scale=fs ** -0.5)


class MoEStats(NamedTuple):
    aux_loss: jax.Array
    z_loss: jax.Array
    dropped_frac: jax.Array


def apply_moe(p: dict, x: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, MoEStats]:
    """x: (B, S, d) -> (B, S, d).

    Dispatches to the shard-local (shard_map) implementation whenever a mesh
    with a >1 "model" axis is active: routing is token-local and experts are
    model-sharded, so the only cross-device communication is the same psum
    TP already pays — the global-sort/scatter collectives of the naive pjit
    lowering (which dominated the baseline roofline) disappear.
    """
    from repro.distributed.sharding import _current_mesh
    mesh = _current_mesh()
    if mesh is not None and dict(zip(mesh.axis_names,
                                     mesh.devices.shape)).get("model", 1) > 1:
        return _apply_moe_shardmap(p, x, cfg, mesh)
    return _apply_moe_global(p, x, cfg)


def _apply_moe_global(p: dict, x: jax.Array, cfg: ArchConfig
                      ) -> tuple[jax.Array, MoEStats]:
    """Reference pjit-global dispatch (single-device and baseline path)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = ops.matmul(xt.astype(jnp.float32), p["router"],
                        out_dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                  # (t, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses ----
    me = probs.mean(0)                                        # (e,)
    ce = jnp.zeros(e).at[idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2)

    # ---- sort-based dispatch: lift tokens -> (experts, capacity) ----
    cap = int(max(cfg.capacity_factor * t * k / e, 1))
    cap = -(-cap // 8) * 8                                    # sublane-align
    flat_e = idx.reshape(-1)                                  # (t*k,)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)                               # stable
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.zeros(e, jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[se]
    keep = pos_in_e < cap
    slot = se * cap + jnp.clip(pos_in_e, 0, cap - 1)

    xe = jnp.zeros((e * cap, d), x.dtype)
    xe = xe.at[slot].add(jnp.where(keep[:, None], xt[st], 0))
    xe = xe.reshape(e, cap, d)
    xe = constrain(xe, "experts", None, None)

    # ---- expert FFN (gated) — the derived expert-GEMM schedule, batched
    # over the lifted expert axis (repro.kernels.ops.expert_matmul)
    h = ops.expert_matmul(xe, p["wi"], out_dtype=jnp.float32)
    u, v = jnp.split(h, 2, axis=-1)
    h = (_gate_act(cfg, u) * v).astype(x.dtype)
    h = constrain(h, "experts", None, "moe_ff")
    ye = ops.expert_matmul(h, p["wo"], out_dtype=x.dtype)
    ye = constrain(ye, "experts", None, None)

    # ---- combine ----
    contrib = ye.reshape(e * cap, d)[slot]
    contrib = contrib * (sg * keep).astype(x.dtype)[:, None]
    yt = jnp.zeros((t, d), x.dtype).at[st].add(contrib)
    y = yt.reshape(b, s, d)
    y = constrain(y, "batch", None, None)

    if cfg.n_shared_experts:
        hs = ops.matmul(x, p["shared_wi"], out_dtype=jnp.float32)
        us, vs = jnp.split(hs, 2, axis=-1)
        hs = (_gate_act(cfg, us) * vs).astype(x.dtype)
        y = y + ops.matmul(hs, p["shared_wo"], out_dtype=x.dtype)

    dropped = 1.0 - jnp.sum(keep) / (t * k)
    return y, MoEStats(aux, z, dropped)


# ---------------------------------------------------------------------------
# shard-local dispatch (expert parallelism without global sort collectives)
# ---------------------------------------------------------------------------

def _apply_moe_shardmap(p: dict, x: jax.Array, cfg: ArchConfig, mesh
                        ) -> tuple[jax.Array, MoEStats]:
    """Token-local routing + model-sharded experts via shard_map.

    Per device: route ITS tokens, keep assignments to ITS expert shard,
    sort/scatter locally (static shapes), run the local expert FFNs, combine,
    then one psum over "model" sums each token's expert contributions — the
    same collective TP pays for a dense FFN.  DP axes never exchange tokens.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes["model"]
    e_loc = e // tp
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes and sizes[a] > 1)
    dp_size = _np_prod([sizes[a] for a in dp_axes]) if dp_axes else 1
    if b % max(dp_size, 1):
        dp_axes, dp_size = (), 1
    batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)

    t_loc = (b // max(dp_size, 1)) * s
    cap = int(max(cfg.capacity_factor * t_loc * k / e, 1))
    cap = -(-cap // 8) * 8

    all_axes = tuple(n for n in mesh.axis_names if sizes[n] > 1)

    def body(x_blk, router, wi, wo):
        bl, sl, _ = x_blk.shape
        tl = bl * sl
        xt = x_blk.reshape(tl, d)
        logits = ops.matmul(xt.astype(jnp.float32), router,
                            out_dtype=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(0)
        ce = jnp.zeros(e).at[idx.reshape(-1)].add(1.0) / (tl * k)
        aux = e * jnp.sum(me * ce)
        z = jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2)

        e0 = jax.lax.axis_index("model") * e_loc
        flat_e_all = idx.reshape(-1)
        local = (flat_e_all >= e0) & (flat_e_all < e0 + e_loc)
        flat_e = jnp.where(local, flat_e_all - e0, e_loc)     # e_loc = drop bucket
        flat_t = jnp.repeat(jnp.arange(tl), k)
        flat_g = gate_vals.reshape(-1)
        order = jnp.argsort(flat_e)
        se, st, sg = flat_e[order], flat_t[order], flat_g[order]
        counts = jnp.zeros(e_loc + 1, jnp.int32).at[se].add(1)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(tl * k) - starts[se]
        keep = (se < e_loc) & (pos_in_e < cap)
        slot = jnp.where(keep, se * cap + jnp.clip(pos_in_e, 0, cap - 1),
                         e_loc * cap)                          # overflow slot
        xe = jnp.zeros((e_loc * cap + 1, d), x.dtype)
        xe = xe.at[slot].add(jnp.where(keep[:, None], xt[st], 0))
        xe = xe[:-1].reshape(e_loc, cap, d)

        h = ops.expert_matmul(xe, wi, out_dtype=jnp.float32)
        u, v = jnp.split(h, 2, axis=-1)
        h = (_gate_act(cfg, u) * v).astype(x.dtype)
        ye = ops.expert_matmul(h, wo, out_dtype=x.dtype)

        contrib = jnp.concatenate([ye.reshape(e_loc * cap, d),
                                   jnp.zeros((1, d), x.dtype)])[slot]
        contrib = contrib * (sg * keep).astype(x.dtype)[:, None]
        yt = jnp.zeros((tl, d), x.dtype).at[st].add(contrib)
        yt = jax.lax.psum(yt, "model")
        # drops among THIS rank's local assignments (sorted order throughout)
        dropped_loc = jnp.sum((se < e_loc) & (pos_in_e >= cap)).astype(jnp.float32)
        # aux/z identical across "model"; average over the other axes
        if all_axes:
            denom = _np_prod([sizes[a] for a in all_axes])
            aux = jax.lax.psum(aux, all_axes) / denom
            z = jax.lax.psum(z, all_axes) / denom
            dropped = jax.lax.psum(dropped_loc, all_axes) / (tl * k * max(dp_size, 1))
        else:
            dropped = dropped_loc / (tl * k)
        return yt.reshape(bl, sl, d), aux, z, dropped

    # checkpoint INSIDE the shard_map: outer remat treats the shard_map call
    # as opaque and would otherwise save every internal expert intermediate
    # (measured: 0.94 GiB f32 per layer on llama4-scout)
    y, aux, z, dropped = jax.shard_map(
        jax.checkpoint(body), mesh=mesh,
        in_specs=(P(batch_spec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(batch_spec, None, None), P(), P(), P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wo"])

    if cfg.n_shared_experts:
        hs = ops.matmul(x, p["shared_wi"], out_dtype=jnp.float32)
        us, vs = jnp.split(hs, 2, axis=-1)
        hs = (_gate_act(cfg, us) * vs).astype(x.dtype)
        y = y + ops.matmul(hs, p["shared_wo"], out_dtype=x.dtype)
    return y, MoEStats(aux, z, dropped)


def _np_prod(xs):
    out = 1
    for v in xs:
        out *= int(v)
    return out
