"""Plain float32 references of the benchmark's configurations, one module
per model family (``bench/reference/<family>.py``).

Written from the published descriptions in ``jax.numpy``, with no kernel,
cache or batching, and importing nothing of the program.  Each module
declares the parameter layout it reads (``layout``), one layer
(``layer``), the embedding (``embed``) and the output head (``head``),
and the shapes that ``bench/core/costs.py`` counts operations and bytes
from (``dims``, ``layer_gemms``, ``mixer_flops``, ``slot_bytes``).
``quant=True`` computes every weight matmul on fp8 (e4m3) operands with
per-row and per-column scales: the control, one precision below bfloat16.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

FP8_MAX = 448.0


def fp8(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded to e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x: jax.Array, w: jax.Array, quant: bool) -> jax.Array:
    """``x (..., k) @ w (k, n)`` in float32 at full precision."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x, w = fp8(x, -1), fp8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def module(family: str):
    """The reference module of a model family, found by its name."""
    return importlib.import_module(f"bench.reference.{family}")
