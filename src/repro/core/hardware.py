"""Hardware registry: one place that answers "what machine is this?".

The paper's derivation is parameterized by a hardware *shape* (the resource
hierarchy the lifted axes index).  At runtime the kernels additionally need a
*backend policy* — run compiled Pallas, run interpret-mode Pallas (the CPU
validation path), or fall back to the XLA oracle.  A ``HardwareEntry`` bundles
both, and ``detect_hardware`` probes the jax backend exactly once per process
(replacing the per-call ``jax.default_backend()`` probes the kernel wrappers
used to do), with an ``REPRO_HARDWARE`` env override for forcing an entry.

The registry is open: ``register_hardware`` adds entries for new chips, and
the schedule cache (repro.core.schedule) keys on the entry name, so two
entries never share schedules.
"""
from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass
from functools import lru_cache
from typing import Iterator, Optional

from repro.core.lifting import (GPU_A100, HardwareShape, TPU_V5E,
                                TPU_V5E_2POD, V100)


@dataclass(frozen=True)
class HardwareEntry:
    """A registered machine: the array-view shape + kernel backend policy.

    ``backend``:
      * "pallas"    — compile Pallas kernels for the attached accelerator,
      * "interpret" — run the same kernels through the Pallas interpreter
                      (bit-level validation of the derived schedules on CPU),
      * "xla"       — no Pallas backend; the unified entry points
                      (``ops.matmul`` & co) use the jnp oracle instead.
    """
    name: str
    shape: HardwareShape
    backend: str
    description: str = ""

    def __post_init__(self):
        if self.backend not in ("pallas", "interpret", "xla"):
            raise ValueError(f"unknown backend {self.backend!r}")

    @property
    def interpret(self) -> bool:
        """Whether Pallas kernels should run in interpret mode here."""
        return self.backend != "pallas"

    def xla_options(self) -> dict[str, str]:
        """XLA compiler options for a program that calls this entry's
        kernels.  On a TPU, XLA's own scoped VMEM limit (16 MiB on v5e)
        also bounds a kernel that XLA fuses its neighbours into (a
        scanned layer stack's gradient update, say), so it is raised to
        the capacity the schedules were certified under."""
        if self.backend != "pallas" or not self.name.startswith("tpu"):
            return {}
        kib = self.shape.vmem.capacity_bytes // 1024
        return {"xla_tpu_scoped_vmem_limit_kib": str(kib)}


_REGISTRY: dict[str, HardwareEntry] = {}


def register_hardware(entry: HardwareEntry) -> HardwareEntry:
    _REGISTRY[entry.name] = entry
    return entry


def get_entry(name: str) -> HardwareEntry:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown hardware entry {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def registered_hardware() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


TPU_V5E_ENTRY = register_hardware(HardwareEntry(
    "tpu_v5e", TPU_V5E, "pallas", "TPU v5e pod slice (compiled Pallas)"))
TPU_V5E_2POD_ENTRY = register_hardware(HardwareEntry(
    "tpu_v5e_2pod", TPU_V5E_2POD, "pallas", "2-pod TPU v5e (compiled Pallas)"))
V100_ENTRY = register_hardware(HardwareEntry(
    "v100", V100, "xla", "the paper's V100 — block solver target, XLA exec"))
# The GPU (triton-Pallas) entry: derive_schedule / solve_blocks produce
# CUDA-shaped tiles from the A100 table (shared memory for VMEM, warp for
# the lane tile, tensor-core fragment for the MXU tile) under
# REPRO_HARDWARE=gpu.  CI has no GPU, so this entry is exercised by
# schedule-inspection tests only; execution on a real GPU compiles the
# same derived schedules through the Pallas triton lowering.
GPU_ENTRY = register_hardware(HardwareEntry(
    "gpu", GPU_A100, "pallas", "A100 SMs — triton-Pallas, derived CUDA tiles"))
# The CPU entry deliberately reuses the v5e hardware shape: interpret-mode
# Pallas then executes the *identical* derived schedule a v5e would compile,
# which is what makes CPU runs a bit-level validation of the TPU path.
CPU_ENTRY = register_hardware(HardwareEntry(
    "cpu", TPU_V5E, "interpret", "host CPU; v5e schedules via Pallas interpreter"))


#: TPU ``device_kind`` (as JAX reports it) -> registry entry.  A TPU that
#: is not listed has no measured table here, so detection refuses it
#: instead of compiling another chip's schedules for it.
TPU_KINDS = {"TPU v5 lite": "tpu_v5e"}


def entry_for_device(platform: str, device_kind: str) -> str:
    """The registry entry for a device of ``platform``/``device_kind``."""
    if platform == "tpu":
        try:
            return TPU_KINDS[device_kind]
        except KeyError:
            raise RuntimeError(
                f"no hardware entry for TPU device_kind {device_kind!r}; "
                f"known: {sorted(TPU_KINDS)}") from None
    if platform == "gpu":
        return "v100"
    return "cpu"


@lru_cache(maxsize=1)
def _detected_name() -> str:
    import jax
    dev = jax.devices()[0]
    return entry_for_device(dev.platform, dev.device_kind)


_OVERRIDE: Optional[str] = None


def detect_hardware() -> HardwareEntry:
    """The active entry: explicit override > REPRO_HARDWARE env > probed."""
    if _OVERRIDE is not None:
        return get_entry(_OVERRIDE)
    env = os.environ.get("REPRO_HARDWARE")
    if env:
        return get_entry(env)
    return get_entry(_detected_name())


# ``current_hardware`` is the name the dispatch layer uses; ``detect_hardware``
# is the probing act.  They are the same callable today.
current_hardware = detect_hardware


def set_default_hardware(name: Optional[str]) -> None:
    """Force (or with None, un-force) the process-wide hardware entry."""
    global _OVERRIDE
    if name is not None:
        get_entry(name)                      # fail fast on typos
    _OVERRIDE = name


@contextlib.contextmanager
def use_hardware(name: str) -> Iterator[HardwareEntry]:
    """Scoped override, for tests and benchmarks."""
    prev = _OVERRIDE
    set_default_hardware(name)
    try:
        yield get_entry(name)
    finally:
        set_default_hardware(prev)
