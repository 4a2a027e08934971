"""Weights made from the seed, on the device, in the type they are served in.

A layout is a list of ``(path, shape, init)`` leaves, as the reference of
the configuration declares it; leaves under ``layers/`` carry the layer
count as their leading axis.  Each leaf is drawn from its own key,
``fold_in(seed key, leaf index)``, one block of rows at a time, so no
temporary is larger than a block and any single layer can be drawn again,
bit for bit, by :func:`layer` without drawing the rest.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: rows drawn per block: bounds the temporaries of the largest leaf
BLOCK_ROWS = 1024


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def stacked(path: str) -> bool:
    return path.startswith("layers/")


def _block_rows(rows: int) -> int:
    """The largest divisor of ``rows`` that is at most BLOCK_ROWS."""
    for r in range(min(rows, BLOCK_ROWS), 0, -1):
        if rows % r == 0:
            return r
    return 1


def _draw(key, shape, init, dtype):
    kind = init[0]
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "normal":
        return (jax.random.normal(key, shape, jnp.float32)
                * init[1]).astype(dtype)
    if kind == "a_log":              # Mamba-2: A ~ U(1, 16), stored as log A
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)
    if kind == "dt_bias":            # Mamba-2: softplus(dt_bias) ~ logU(1e-3, 0.1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    raise ValueError(f"unknown init {init!r}")


def _one_layer(key, shape, init, dtype):
    """One layer's slice of a leaf (``shape`` without the layer axis),
    drawn block by block of rows."""
    if init[0] in ("ones", "zeros") or len(shape) < 2:
        return _draw(key, shape, init, dtype)
    rows = shape[0]
    r = _block_rows(rows)

    def body(b, out):
        blk = _draw(jax.random.fold_in(key, b), (r,) + tuple(shape[1:]),
                    init, dtype)
        return jax.lax.dynamic_update_slice_in_dim(out, blk, b * r, 0)
    return jax.lax.fori_loop(0, rows // r, body, jnp.zeros(shape, dtype))


def _leaf_key(key, index: int):
    return jax.random.fold_in(key, index)


def make(layout: list, seed: int, dtype) -> dict:
    """Every leaf of ``layout``, in one jitted call; returns the nested
    dict the program takes as its parameters."""
    key = seed_key(seed)

    @jax.jit
    def gen(key):
        flat = {}
        for i, (path, shape, init) in enumerate(layout):
            k = _leaf_key(key, i)
            if stacked(path):
                n = shape[0]

                def body(l, out, k=k, shape=shape, init=init):
                    one = _one_layer(jax.random.fold_in(k, l), shape[1:],
                                     init, dtype)
                    return jax.lax.dynamic_update_index_in_dim(out, one, l, 0)
                flat[path] = jax.lax.fori_loop(0, n, body,
                                               jnp.zeros(shape, dtype))
            else:
                flat[path] = _one_layer(k, shape, init, dtype)
        return flat
    return nest(gen(key))


def layer(layout: list, seed: int, dtype, index: int) -> dict:
    """Layer ``index`` of every stacked leaf, drawn exactly as :func:`make`
    draws it; keys are the paths under ``layers/``."""
    key = seed_key(seed)
    return _layer_jit(tuple((p, tuple(s), tuple(i)) for p, s, i in layout),
                      jnp.dtype(dtype))(key, jnp.int32(index))


def single(layout: list, seed: int, dtype, path: str) -> jax.Array:
    """One unstacked leaf, drawn exactly as :func:`make` draws it."""
    key = seed_key(seed)
    i = [p for p, _, _ in layout].index(path)
    _, shape, init = layout[i]
    return _single_jit(tuple(shape), tuple(init), jnp.dtype(dtype), i)(key)


_cache: dict = {}


def _layer_jit(layout: tuple, dtype):
    ck = ("layer", layout, dtype)
    if ck not in _cache:
        @jax.jit
        def gen(key, l):
            out = {}
            for i, (path, shape, init) in enumerate(layout):
                if stacked(path):
                    k = jax.random.fold_in(_leaf_key(key, i), l)
                    out[path] = _one_layer(k, shape[1:], init, dtype)
            return out
        _cache[ck] = gen
    return _cache[ck]


def _single_jit(shape, init, dtype, index):
    ck = ("single", shape, init, dtype, index)
    if ck not in _cache:
        _cache[ck] = jax.jit(
            lambda key: _one_layer(_leaf_key(key, index), shape, init, dtype))
    return _cache[ck]


def nest(flat: dict) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out
