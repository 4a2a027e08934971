"""Operations and bytes of the served work, computed from the shapes of a
configuration (its reference module's ``dims`` and ``layer_gemms``),
never from the program.  Each GEMM is ``(M, K, N)``: ``2 M K N``
operations; weights are read in bfloat16.
"""
from __future__ import annotations

from bench.reference import module

BF16 = 2


def dims(config: dict) -> dict:
    return module(config["family"]).dims(config)


def weight_params(config: dict) -> int:
    """Parameters every decode iteration reads: the layers' matrices and
    the output head."""
    d = dims(config)
    per = sum(k * n for _, k, n in
              module(config["family"]).layer_gemms(config, 1))
    return per * d["L"] + d["d"] * d["V"]


def token_flops(config: dict, ctx: int) -> float:
    """Model operations of one decoded token whose context is ``ctx``
    positions: the GEMMs and the mixer's own work (attention over the
    context, or the state update and read-out)."""
    ref = module(config["family"])
    return 2.0 * weight_params(config) + ref.mixer_flops(config, ctx)


def prefill_flops(config: dict, p: int) -> float:
    """Model operations of a prefill of ``p`` tokens: every token through
    the layers and its mixer, the head once."""
    d = dims(config)
    ref = module(config["family"])
    layers = weight_params(config) - d["d"] * d["V"]
    return (2.0 * layers * p + 2.0 * d["d"] * d["V"]
            + sum(ref.mixer_flops(config, c) for c in range(p)))


def slot_bytes(config: dict, ctx: int) -> float:
    """Cache bytes one live slot's decode must touch at context ``ctx``."""
    return float(module(config["family"]).slot_bytes(config, ctx))
