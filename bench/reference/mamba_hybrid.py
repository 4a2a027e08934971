"""A Mamba-2/attention hybrid in float32, as granite-4.0-h publishes it
(``model_type`` granitemoehybrid with no experts): ``layer_types`` lists a
Mamba-2 or an attention mixer for every layer, and every layer is

    x = x + r * mixer(rmsnorm(x))
    x = x + r * mlp(rmsnorm(x))

with ``r`` the ``residual_multiplier``.  The looked-up embedding rows are
multiplied by ``embedding_multiplier``; the head is the tied table after a
final RMSNorm, and its logits are divided by ``logits_scaling``.

- Mamba-2 (arXiv:2405.21060): an input projection to (z, x, B, C, dt), a
  causal depthwise convolution with bias and SiLU over (x, B, C), the
  selective state-space recurrence run step by step with one B and C for
  all heads (``mamba_n_groups`` 1), the skip ``D``, a gated RMSNorm of
  ``y * silu(z)`` and an output projection; no projection bias.
- Attention: grouped-query, no positional embedding (``nope``), causal,
  softmax scale ``attention_multiplier``, no bias; computed in blocks of
  queries so that 15k positions fit on one chip.
- MLP: SwiGLU of ``shared_intermediate_size``, gate first, after every
  mixer (``num_local_experts`` 0).
- Every RMSNorm, the gated one included, takes ``rms_norm_eps``.

One ``layer`` call is one whole period of ``layer_types`` (the smallest
prefix whose repeats make the list), and ``dims()["L"]`` counts periods:
the parameters live under ``layers/`` with the period as the leading axis
and then the count of each kind within the period, as the program holds
them.

Departures from the published model: none in the mathematics.  The
weights are random, drawn at the scales ``layout`` states (the
configuration's ``assumed`` says why they are not the published init).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import mm

#: positions a sequence is padded to a multiple of (one compile a length)
ROW_BLOCK = 512
#: queries a block of the attention reference holds against all keys
Q_BLOCK = 256
#: output projections (mixers and MLP) are drawn this many times their
#: fan-in scale, and q and k projections QK_GAIN times theirs (see
#: ``layout``)
OUT_GAIN = 96.0
QK_GAIN = 8.0
#: the tied table is drawn at EMBED_GAIN times d ** -0.5
EMBED_GAIN = 8.0


def period(types: list) -> list:
    """The shortest prefix of ``types`` whose repeats make the list."""
    for p in range(1, len(types)):
        if len(types) % p == 0 and types[:p] * (len(types) // p) == types:
            return types[:p]
    return types


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    din = cfg["mamba_expand"] * d
    if H * p != din or cfg["mamba_n_groups"] != 1:
        raise ValueError("the reference covers mamba_n_heads * mamba_d_head "
                         "== mamba_expand * hidden_size and one group")
    if cfg["position_embedding_type"] != "nope" or cfg["num_local_experts"]:
        raise ValueError("the reference covers NoPE attention and no experts")
    kinds = period(list(cfg["layer_types"]))
    nh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return dict(d=d, din=din, p=p, n=n, H=H, W=cfg["mamba_d_conv"],
                C=din + 2 * n, kinds=kinds, per=len(kinds),
                nm=kinds.count("mamba"), na=kinds.count("attention"),
                L=len(cfg["layer_types"]) // len(kinds), nh=nh, kv=kv,
                hd=d // nh, f=cfg["shared_intermediate_size"],
                V=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]))


def layout(cfg: dict) -> list:
    """Every leaf as ``(path, shape, init)``.  Input projections are drawn
    at fan-in scale.  Output projections are drawn OUT_GAIN above it, so
    that the mixers, and not the embedding row the residual starts from,
    set the logits: with the tied table a position's best token would
    otherwise be its own input token, and a decode that lost its state
    would still read correct.  q and k are drawn QK_GAIN above it, so that
    the scores under the 1/64 softmax scale spread by several units and
    attention picks out a few positions, at 9k of context too, instead of
    averaging them all: then the four attention layers, and the K/V cache
    they read, move the logits by more than the comparison's limit."""
    m = dims(cfg)
    d, din, n, H, W, C = (m[k] for k in ("d", "din", "n", "H", "W", "C"))
    L, per, nm, na = m["L"], m["per"], m["nm"], m["na"]
    nh, kv, hd, f = m["nh"], m["kv"], m["hd"], m["f"]

    def out(fan_in):
        return ("normal", OUT_GAIN * fan_in ** -0.5)
    return sorted([
        ("embed/table", (m["V"], d), ("normal", EMBED_GAIN * d ** -0.5)),
        ("final_norm/scale", (d,), ("ones",)),
        ("layers/ln1/scale", (L, per, d), ("ones",)),
        ("layers/ln2/scale", (L, per, d), ("ones",)),
        ("layers/mamba/w_in", (L, nm, d, 2 * din + 2 * n + H),
         ("normal", d ** -0.5)),
        ("layers/mamba/conv_w", (L, nm, W, C), ("normal", W ** -0.5)),
        ("layers/mamba/conv_b", (L, nm, C), ("zeros",)),
        ("layers/mamba/A_log", (L, nm, H), ("a_log",)),
        ("layers/mamba/D", (L, nm, H), ("ones",)),
        ("layers/mamba/dt_bias", (L, nm, H), ("dt_bias",)),
        ("layers/mamba/norm_scale", (L, nm, din), ("ones",)),
        ("layers/mamba/w_out", (L, nm, din, d), out(din)),
        ("layers/attn/wq", (L, na, d, nh, hd), ("normal", QK_GAIN * d ** -0.5)),
        ("layers/attn/wk", (L, na, d, kv, hd), ("normal", QK_GAIN * d ** -0.5)),
        ("layers/attn/wv", (L, na, d, kv, hd), ("normal", d ** -0.5)),
        ("layers/attn/wo", (L, na, nh, hd, d), out(nh * hd)),
        ("layers/mlp/wi", (L, per, d, 2 * f), ("normal", d ** -0.5)),
        ("layers/mlp/wo", (L, per, f, d), out(f)),
    ])


def _rmsnorm(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def embed(cfg: dict, table, ids):
    return table[ids].astype(jnp.float32) * float(cfg["embedding_multiplier"])


def pad_to(cfg: dict, n: int) -> int:
    return ROW_BLOCK


def _f32(a):
    return a.astype(jnp.float32)


def _mamba(m: dict, w: dict, h, quant: bool):
    """The Mamba-2 mixer over ``h`` (t, d); ``w`` holds one layer's leaves
    by their names under ``layers/mamba/``."""
    t = h.shape[0]
    din, n, H, p, W = m["din"], m["n"], m["H"], m["p"], m["W"]
    zxbcdt = mm(h, w["w_in"], quant)
    z, xbc, dt = (zxbcdt[:, :din], zxbcdt[:, din:2 * din + 2 * n],
                  zxbcdt[:, 2 * din + 2 * n:])
    cw = _f32(w["conv_w"])                                   # (W, C)
    conv = _f32(w["conv_b"])[None, :]
    for j in range(W):                                       # tap j looks j back
        shifted = jnp.concatenate([jnp.zeros((j, xbc.shape[1]), jnp.float32),
                                   xbc[:t - j]], 0) if j else xbc
        conv = conv + shifted * cw[W - 1 - j][None, :]
    xbc = jax.nn.silu(conv)
    xs, B, C = xbc[:, :din], xbc[:, din:din + n], xbc[:, din + n:]
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"])[None, :])
    A = -jnp.exp(_f32(w["A_log"]))
    xh = xs.reshape(t, H, p)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hpn,n->hp", state, c_t,
                       precision=jax.lax.Precision.HIGHEST)
        return state, y
    _, y = jax.lax.scan(step, jnp.zeros((H, p, n), jnp.float32),
                        (xh, dt, B, C), unroll=4)
    y = y + _f32(w["D"])[None, :, None] * xh
    y = y.reshape(t, din) * jax.nn.silu(z)
    y = _rmsnorm(y, w["norm_scale"], m["eps"])
    return mm(y, w["w_out"], quant)


def _attention(cfg: dict, m: dict, w: dict, h, quant: bool):
    """Causal NoPE grouped-query attention over ``h`` (t, d), ``Q_BLOCK``
    queries at a time against every key."""
    t, d = h.shape
    nh, kv, hd = m["nh"], m["kv"], m["hd"]
    g = nh // kv
    scale = float(cfg["attention_multiplier"])
    q = mm(h, w["wq"].reshape(d, nh * hd), quant).reshape(t, kv, g, hd)
    k = mm(h, w["wk"].reshape(d, kv * hd), quant).reshape(t, kv, hd)
    v = mm(h, w["wv"].reshape(d, kv * hd), quant).reshape(t, kv, hd)
    qb = min(Q_BLOCK, t)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k,
                       precision=jax.lax.Precision.HIGHEST) * scale
        seen = jnp.arange(t)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", pr, v,
                       precision=jax.lax.Precision.HIGHEST)
        return o.reshape(qb, nh * hd)
    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, nh * hd)
    return mm(o, w["wo"].reshape(nh * hd, d), quant)


def _mlp(m: dict, w: dict, h, quant: bool):
    gate_up = mm(h, w["wi"], quant)
    f = m["f"]
    return mm(jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:], w["wo"], quant)


def layer(cfg: dict, w: dict, x, quant: bool):
    """One whole period of ``layer_types``, in its published order."""
    m = dims(cfg)
    r = float(cfg["residual_multiplier"])
    kind_w = {k: {p.split("/")[-1]: v for p, v in w.items()
                  if p.startswith(f"layers/{k}/")}
              for k in ("mamba", "attn", "mlp")}
    at = lambda leaves, i: {name: v[i] for name, v in leaves.items()}
    count = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(m["kinds"]):
        h = _rmsnorm(x, w["layers/ln1/scale"][i], m["eps"])
        j = count[kind]
        if kind == "mamba":
            out = _mamba(m, at(kind_w["mamba"], j), h, quant)
        else:
            out = _attention(cfg, m, at(kind_w["attn"], j), h, quant)
        count[kind] += 1
        x = x + r * out
        h = _rmsnorm(x, w["layers/ln2/scale"][i], m["eps"])
        x = x + r * _mlp(m, at(kind_w["mlp"], i), h, quant)
    return x


def head(cfg: dict, heads: dict, x, quant: bool):
    m = dims(cfg)
    hn = _rmsnorm(x, heads["final_norm/scale"], m["eps"])
    return (mm(hn, _f32(heads["embed/table"]).T, quant)
            / float(cfg["logits_scaling"]))


# shapes that bench/core/costs.py counts operations and bytes from

def layer_gemms(cfg: dict, m: int) -> list:
    """The weight GEMMs ``(M, K, N)`` of one period over ``m`` rows."""
    d = dims(cfg)
    mamba = [(m, d["d"], 2 * d["din"] + 2 * d["n"] + d["H"]),
             (m, d["din"], d["d"])]
    q, kv = d["nh"] * d["hd"], d["kv"] * d["hd"]
    attn = [(m, d["d"], q), (m, d["d"], kv), (m, d["d"], kv), (m, q, d["d"])]
    mlp = [(m, d["d"], 2 * d["f"]), (m, d["f"], d["d"])]
    return (d["nm"] * mamba + d["na"] * attn + d["per"] * mlp)


def mixer_flops(cfg: dict, ctx: int) -> float:
    """Operations of one token's mixers, all layers: each Mamba-2 state
    update and read-out, and each attention layer's scores and weighted
    sum over ``ctx`` positions."""
    d = dims(cfg)
    mamba = 6.0 * d["H"] * d["p"] * d["n"] * d["nm"]
    attn = 4.0 * d["nh"] * d["hd"] * ctx * d["na"]
    return (mamba + attn) * d["L"]


def slot_bytes(cfg: dict, ctx: int) -> float:
    """Cache bytes one live slot's decode touches at context ``ctx``: each
    Mamba-2 layer reads and writes its float32 state and its convolution
    tail (bfloat16); each attention layer reads its K and V (bfloat16) up
    to ``ctx`` and writes one position."""
    d = dims(cfg)
    mamba = 2.0 * (d["H"] * d["p"] * d["n"] * 4 + (d["W"] - 1) * d["C"] * 2)
    attn = 2.0 * d["kv"] * d["hd"] * 2 * (ctx + 1)
    return (mamba * d["nm"] + attn * d["na"]) * d["L"]
