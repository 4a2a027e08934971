"""Mamba-2 in float32 (arXiv:2405.21060): RMSNorm, an input projection to
(z, x, B, C, dt), a causal depthwise convolution with SiLU, the selective
state-space recurrence run step by step, a gated RMSNorm and an output
projection, with one B and C shared by all heads (ngroups 1).

The reference is the published model: the residual stream in float32,
RMSNorm eps from the published values (``published`` in the configuration
file, where the program runs another), and the output head tied to the
embedding table ``E`` of ``vocab_size`` rounded up to
``pad_vocab_size_multiple`` rows.  The benchmark draws the weights in the
program's parametrisation, which scales the looked-up rows by sqrt(d)
and keeps ``E`` unscaled in the head; the published model with table
``sqrt(d) E`` and final-norm scale ``g / sqrt(d)`` computes the same
function, and that is the one written here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import mm

ROW_BLOCK = 256


def dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    din = cfg["expand"] * d
    p = cfg["headdim"]
    n = cfg["d_state"]
    pad = cfg["pad_vocab_size_multiple"]
    eps = cfg.get("published", {}).get("norm_epsilon", cfg["norm_epsilon"])
    return dict(d=d, din=din, p=p, n=n, H=din // p, W=cfg["d_conv"],
                L=cfg["n_layer"], V=-(-cfg["vocab_size"] // pad) * pad,
                eps=float(eps), C=din + 2 * n)


def layout(cfg: dict) -> list:
    m = dims(cfg)
    d, din, n, H, W, L, C = (m[k] for k in ("d", "din", "n", "H", "W", "L", "C"))
    head = [] if cfg["tie_embeddings"] else [
        ("unembed/w", (d, m["V"]), ("normal", d ** -0.5))]
    return sorted(head + [
        ("embed/table", (m["V"], d), ("normal", d ** -0.5)),
        ("final_norm/scale", (d,), ("ones",)),
        ("layers/ln1/scale", (L, d), ("ones",)),
        ("layers/mixer/w_in", (L, d, 2 * din + 2 * n + H), ("normal", d ** -0.5)),
        ("layers/mixer/conv_w", (L, W, C), ("normal", W ** -0.5)),
        ("layers/mixer/conv_b", (L, C), ("zeros",)),
        ("layers/mixer/A_log", (L, H), ("a_log",)),
        ("layers/mixer/D", (L, H), ("ones",)),
        ("layers/mixer/dt_bias", (L, H), ("dt_bias",)),
        ("layers/mixer/norm_scale", (L, din), ("ones",)),
        # drawn sqrt(d) above fan-in scale: the mixers, not the tied
        # table's self-similarity, have to set the logits, or every
        # position's best token is its own input token
        ("layers/mixer/w_out", (L, din, d), ("normal", (d / din) ** 0.5)),
    ])


def _rmsnorm(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _tied(cfg: dict, table, scale):
    """The published model's table and final-norm scale."""
    r = jnp.sqrt(jnp.float32(cfg["d_model"]))
    return table.astype(jnp.float32) * r, scale.astype(jnp.float32) / r


def embed(cfg: dict, table, ids):
    if cfg["tie_embeddings"]:
        table, _ = _tied(cfg, table, jnp.ones(()))
    return table[ids].astype(jnp.float32)


def pad_to(cfg: dict, n: int) -> int:
    return ROW_BLOCK


def layer(cfg: dict, w: dict, x, quant: bool):
    m = dims(cfg)
    t = x.shape[0]
    din, n, H, p, W = m["din"], m["n"], m["H"], m["p"], m["W"]
    f32 = lambda a: a.astype(jnp.float32)
    hn = _rmsnorm(x, w["layers/ln1/scale"], m["eps"])
    zxbcdt = mm(hn, w["layers/mixer/w_in"], quant)
    z, xbc, dt = zxbcdt[:, :din], zxbcdt[:, din:2 * din + 2 * n], zxbcdt[:, 2 * din + 2 * n:]
    cw = f32(w["layers/mixer/conv_w"])                       # (W, C)
    conv = f32(w["layers/mixer/conv_b"])[None, :]
    for j in range(W):                                       # tap j looks j steps back
        shifted = jnp.concatenate([jnp.zeros((j, xbc.shape[1]), jnp.float32),
                                   xbc[:t - j]], 0) if j else xbc
        conv = conv + shifted * cw[W - 1 - j][None, :]
    xbc = jax.nn.silu(conv)
    xs, B, C = xbc[:, :din], xbc[:, din:din + n], xbc[:, din + n:]
    dt = jax.nn.softplus(dt + f32(w["layers/mixer/dt_bias"])[None, :])
    A = -jnp.exp(f32(w["layers/mixer/A_log"]))
    xh = xs.reshape(t, H, p)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hpn,n->hp", state, c_t,
                       precision=jax.lax.Precision.HIGHEST)
        return state, y
    _, y = jax.lax.scan(step, jnp.zeros((H, p, n), jnp.float32),
                        (xh, dt, B, C))
    y = y + f32(w["layers/mixer/D"])[None, :, None] * xh
    y = y.reshape(t, din) * jax.nn.silu(z)
    y = _rmsnorm(y, w["layers/mixer/norm_scale"], m["eps"])
    return x + mm(y, w["layers/mixer/w_out"], quant)


def head(cfg: dict, heads: dict, x, quant: bool):
    m = dims(cfg)
    if cfg["tie_embeddings"]:
        table, scale = _tied(cfg, heads["embed/table"],
                             heads["final_norm/scale"])
        return mm(_rmsnorm(x, scale, m["eps"]), table.T, quant)
    hn = _rmsnorm(x, heads["final_norm/scale"], m["eps"])
    return mm(hn, heads["unembed/w"], quant)


# shapes that bench/core/costs.py counts operations and bytes from

def layer_gemms(cfg: dict, m: int) -> list:
    """The weight GEMMs ``(M, K, N)`` of one layer over ``m`` rows."""
    d = dims(cfg)
    return [(m, d["d"], 2 * d["din"] + 2 * d["n"] + d["H"]),
            (m, d["din"], d["d"])]


def mixer_flops(cfg: dict, ctx: int) -> float:
    """Operations of one token's state update and read-out, all layers."""
    d = dims(cfg)
    return 6.0 * d["H"] * d["p"] * d["n"] * d["L"]


def slot_bytes(cfg: dict, ctx: int) -> float:
    """One live slot's decode reads and writes its float32 state and its
    convolution tail (bfloat16), every layer."""
    d = dims(cfg)
    state = d["H"] * d["p"] * d["n"] * 4 + (d["W"] - 1) * d["C"] * 2
    return 2.0 * state * d["L"]
