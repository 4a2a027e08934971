"""Dimension lifting: the paper's bridge between data shapes and hardware shapes.

    "Dimension lifting is defined by systematically partitioning each shape
     component into 2, thus lifting the dimension of the problem as each
     partitioned shape is used to identify an architectural resource."
                                                        — Mullin 2023, Def 3.1

The hardware is itself an array.  ``HardwareShape`` declares the resource
hierarchy (axes with sizes, capacities, bandwidths and per-unit energies);
``lift`` splits logical axes so that each new outer axis indexes a resource
level.  A ``LiftedShape`` then *emits* the concrete artifacts each level
needs:

* mesh levels  -> ``jax.sharding.PartitionSpec`` entries (pjit/shard_map),
* vmem level   -> Pallas ``grid`` extents + ``BlockSpec`` block shapes,
* vreg level   -> alignment constraints ((8, 128) sublane×lane tiles).

This file is pure Python + dataclasses (no jax import at module top except
for types used lazily) so importing it never touches device state.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence

from repro.core.moa import pi

# ---------------------------------------------------------------------------
# hardware constants — the "relevant numbers" table (paper Table 1), for TPU
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MemoryLevel:
    name: str
    capacity_bytes: int            # per unit
    bandwidth_Bps: float           # bytes/second into the level below
    energy_pJ_per_byte: float      # access energy (model; relative scale)


@dataclass(frozen=True)
class HardwareShape:
    """An array-view of the machine: hierarchy of resource axes.

    ``mesh_axes`` are the *distribution* levels (lifted axes become named mesh
    axes for pjit); ``grid_axes`` are the on-chip levels (lifted axes become
    Pallas grid dimensions); alignment is the register/MXU tile.
    """
    name: str
    mesh_axes: tuple[tuple[str, int], ...]        # e.g. (("pod",2),("data",16),("model",16))
    vmem: MemoryLevel
    hbm: MemoryLevel
    ici_Bps: float                                # per-link bandwidth
    ici_energy_pJ_per_byte: float
    peak_flops: float                             # per chip, bf16
    flop_energy_pJ: float                         # per FLOP (model)
    mxu_tile: tuple[int, int] = (128, 128)
    vreg_tile: tuple[int, int] = (8, 128)
    sa_power_W: float = 200.0                     # static+active power scale for energy model
    #: accumulation dtypes this part's matrix unit supports (names resolved
    #: through ``core.semiring.accum_def``); every entry keeps f32, and the
    #: MXU-era parts add the bf16 partial-sum and int8->int32 paths.
    acc_dtypes: tuple = ("float32", "bfloat16", "int32")

    @property
    def n_chips(self) -> int:
        return pi([s for _, s in self.mesh_axes])

    def mesh_axis_names(self) -> tuple[str, ...]:
        return tuple(n for n, _ in self.mesh_axes)

    def mesh_shape(self) -> tuple[int, ...]:
        return tuple(s for _, s in self.mesh_axes)


# TPU v5e (Google Cloud "TPU v5e" page): 197 TFLOP/s bf16, 16 GiB HBM at
# 819 GB/s, ~50 GB/s per ICI link.  VMEM is 128 MiB per TensorCore (JAX's
# own ``pallas.mosaic.tpu_info`` table for "TPU v5 lite").  The table
# exposes half of it as the capacity: the block solvers budget fractions of
# this number, the derivation certifies every working set under it, and
# ``emit.compiler_params`` raises Mosaic's scoped VMEM limit (16 MiB by
# default on v5e) to it, which leaves the other half to the compiler.
TPU_V5E = HardwareShape(
    name="tpu_v5e",
    mesh_axes=(("data", 16), ("model", 16)),
    vmem=MemoryLevel("vmem", capacity_bytes=64 * 2**20, bandwidth_Bps=4e12,
                     energy_pJ_per_byte=0.06),
    hbm=MemoryLevel("hbm", capacity_bytes=16 * 2**30, bandwidth_Bps=819e9,
                    energy_pJ_per_byte=5.0),
    ici_Bps=50e9,
    ici_energy_pJ_per_byte=10.0,
    peak_flops=197e12,
    flop_energy_pJ=0.25,
)

TPU_V5E_2POD = dataclasses.replace(
    TPU_V5E, mesh_axes=(("pod", 2), ("data", 16), ("model", 16)))

# A GPU target for the triton-Pallas backend: VMEM's analogue is the SM's
# shared memory (A100: 164 KiB usable per SM, of which we expose the 192 KiB
# carveout's usable slice), the MXU tile's analogue is the tensor-core
# m16n16 fragment, and the (sublane, lane) register tile's analogue is a
# warp of 32 lanes.  The same a-priori solver, pointed at this table,
# produces CUDA-shaped tiles (multiples of 16/32, far smaller than the v5e's
# 512-class blocks) — see tests/test_recurrence.py.
GPU_A100 = HardwareShape(
    name="gpu_a100",
    mesh_axes=(("sm", 108),),
    vmem=MemoryLevel("smem", capacity_bytes=164 * 2**10, bandwidth_Bps=1.9e13,
                     energy_pJ_per_byte=0.09),
    hbm=MemoryLevel("hbm", capacity_bytes=40 * 2**30, bandwidth_Bps=1555e9,
                    energy_pJ_per_byte=4.0),
    ici_Bps=600e9,                # NVLink3 aggregate
    ici_energy_pJ_per_byte=8.0,
    peak_flops=312e12,            # bf16 tensor core
    flop_energy_pJ=0.4,
    mxu_tile=(16, 16),            # tensor-core m16n16k16 fragment
    vreg_tile=(1, 32),            # one warp, coalesced 32-lane accesses
)

# the paper's V100 (Table 1) for cross-validation of the block solver
V100 = HardwareShape(
    name="v100",
    mesh_axes=(("sm", 80),),
    vmem=MemoryLevel("l1", capacity_bytes=32 * 2**10, bandwidth_Bps=1.2e13,
                     energy_pJ_per_byte=0.1),
    hbm=MemoryLevel("global", capacity_bytes=16 * 2**30, bandwidth_Bps=900e9,
                    energy_pJ_per_byte=6.0),
    ici_Bps=32e9,                 # NVLink-ish
    ici_energy_pJ_per_byte=12.0,
    peak_flops=7.8e12,            # fp64
    flop_energy_pJ=6.0,
    mxu_tile=(1, 1),              # no systolic alignment for CUDA cores
    vreg_tile=(1, 8),             # warp-coalesced groups of 8 doubles
    acc_dtypes=("float32",),      # CUDA-core FMA: f32 partial sums only
)


# ---------------------------------------------------------------------------
# lifted shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LiftedAxis:
    """One logical axis after lifting: ordered (outer..inner) factors, each
    tagged with the resource it indexes.  ``None`` resource = stays a plain
    loop/data axis at that level."""
    name: str                       # logical axis name, e.g. "batch", "d_ff"
    size: int
    factors: tuple[tuple[Optional[str], int], ...]   # ((resource|None, extent), ...)

    def __post_init__(self):
        if pi([e for _, e in self.factors]) != self.size:
            raise ValueError(
                f"lifting of {self.name}: factors {self.factors} do not "
                f"multiply to {self.size}")

    def resource_extent(self, resource: str) -> int:
        for r, e in self.factors:
            if r == resource:
                return e
        return 1

    @property
    def innermost(self) -> int:
        return self.factors[-1][1]


@dataclass(frozen=True)
class LiftedShape:
    """A full lifted operand/loop-nest shape + emitters."""
    axes: tuple[LiftedAxis, ...]
    hardware: HardwareShape

    # ---- emitters -------------------------------------------------------
    def partition_spec(self):
        """PartitionSpec naming, per logical axis, the mesh resources it was
        lifted over (outer factors only; grid/loop factors are not sharded)."""
        from jax.sharding import PartitionSpec
        mesh_names = set(self.hardware.mesh_axis_names())
        entries = []
        for ax in self.axes:
            shards = tuple(r for r, _ in ax.factors if r in mesh_names)
            if not shards:
                entries.append(None)
            elif len(shards) == 1:
                entries.append(shards[0])
            else:
                entries.append(shards)
        # trim trailing Nones (canonical form)
        while entries and entries[-1] is None:
            entries.pop()
        return PartitionSpec(*entries)

    def grid(self) -> tuple[int, ...]:
        """Pallas grid extents: product of every 'grid'-tagged factor per axis
        (axes with none contribute nothing)."""
        g = []
        for ax in self.axes:
            e = ax.resource_extent("grid")
            if e > 1:
                g.append(e)
        return tuple(g)

    def block_shape(self) -> tuple[int, ...]:
        """Per-axis innermost (VMEM-resident) extents."""
        return tuple(ax.innermost for ax in self.axes)

    def local_shape(self) -> tuple[int, ...]:
        """Shape of the per-chip shard (after removing mesh factors)."""
        mesh_names = set(self.hardware.mesh_axis_names())
        out = []
        for ax in self.axes:
            s = ax.size
            for r, e in ax.factors:
                if r in mesh_names:
                    s //= e
            out.append(s)
        return tuple(out)


def lift(axis_name: str, size: int, splits: Sequence[tuple[Optional[str], int]],
         ) -> LiftedAxis:
    """Lift one axis: ``splits`` lists (resource, extent) outer-to-inner for
    every factor *except* the innermost remainder, which is computed.

    lift("i", 4096, [("pod", 2), ("data", 16)]) ->
        factors (("pod",2), ("data",16), (None, 128))
    """
    rem = size
    for r, e in splits:
        if rem % e:
            raise ValueError(
                f"cannot lift axis {axis_name}={size}: factor {r}={e} does not "
                f"divide remaining extent {rem}")
        rem //= e
    return LiftedAxis(axis_name, size, tuple(splits) + ((None, rem),))


def lift_shape(hardware: HardwareShape,
               axes: Sequence[tuple[str, int, Sequence[tuple[Optional[str], int]]]]
               ) -> LiftedShape:
    return LiftedShape(tuple(lift(n, s, sp) for n, s, sp in axes), hardware)


# ---------------------------------------------------------------------------
# canonical liftings for the framework's tensors
# ---------------------------------------------------------------------------

def batch_lifting(hardware: HardwareShape, batch: int, *rest: tuple[str, int]
                  ) -> LiftedShape:
    """Lift the batch axis over all data-parallel mesh axes (pod, data);
    remaining axes unlifted.  This is the activation sharding rule."""
    dp_axes = [(n, s) for n, s in hardware.mesh_axes if n in ("pod", "data")]
    axes = [("batch", batch, [(n, s) for n, s in dp_axes])]
    axes += [(n, s, []) for n, s in rest]
    return lift_shape(hardware, axes)


def model_lifting(hardware: HardwareShape, axis_name: str, size: int,
                  *rest: tuple[str, int]) -> LiftedShape:
    """Lift a feature axis over the model mesh axis (tensor parallelism)."""
    tp = dict(hardware.mesh_axes).get("model", 1)
    axes = [(axis_name, size, [("model", tp)] if tp > 1 else [])]
    axes += [(n, s, []) for n, s in rest]
    return lift_shape(hardware, axes)
