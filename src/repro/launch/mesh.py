"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Mesh axes are the outermost level of the paper's
dimension lifting: "pod" (DP across pods), "data" (DP/FSDP within a pod),
"model" (TP/EP/SP).  The v5e pod-slice is 16x16 = 256 chips; multi-pod runs
2 pods = 512 chips.
"""
from __future__ import annotations


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax")
    return _auto_mesh(shape, axes, devices)


def _auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules
    (``distributed.sharding``) place arrays by constraints and leave the
    rest to the partitioner, which ``Explicit`` axes (the default of
    ``make_mesh``) refuse."""
    import jax
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(dp: int = 1, tp: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    import jax
    devices = jax.devices()[:dp * tp]
    return _auto_mesh((dp, tp), ("data", "model"), devices)
