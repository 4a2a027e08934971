"""Architecture registry: ``get_config("<arch-id>")`` for every assigned
architecture (``--arch`` on all launchers).  Plus the paper's own workload
(square GEMMs) as a pseudo-config for the benchmarks."""
from __future__ import annotations

from repro.configs import (command_r_plus_104b, deepseek_moe_16b, gemma_2b,
                           granite_4_0_h_micro, llama4_scout_17b_a16e,
                           mamba2_780m, minicpm3_4b, paligemma_3b,
                           recurrentgemma_9b, stablelm_1_6b, whisper_base)
from repro.models.common import SHAPES, ArchConfig, ShapeConfig  # noqa: F401

_MODULES = (command_r_plus_104b, minicpm3_4b, gemma_2b, stablelm_1_6b,
            mamba2_780m, llama4_scout_17b_a16e, deepseek_moe_16b,
            paligemma_3b, recurrentgemma_9b, whisper_base,
            granite_4_0_h_micro)

ARCHS = {m.ARCH_ID: m for m in _MODULES}
ARCH_IDS = tuple(ARCHS)


def get_config(arch_id: str, reduced: bool = False) -> ArchConfig:
    if arch_id not in ARCHS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(ARCHS)}")
    m = ARCHS[arch_id]
    return m.reduced() if reduced else m.full()


# (arch, shape) applicability: long_500k requires sub-quadratic attention.
SUBQUADRATIC = {"mamba2-780m", "recurrentgemma-9b", "llama4-scout-17b-a16e"}


def cell_applicable(arch_id: str, shape_name: str) -> tuple[bool, str]:
    if shape_name == "long_500k" and arch_id not in SUBQUADRATIC:
        return False, ("full-attention arch: 512k dense-attention decode is "
                       "skipped per task statement (see DESIGN.md)")
    return True, ""


def all_cells() -> list[tuple[str, str]]:
    return [(a, s) for a in ARCH_IDS for s in SHAPES]
