"""Mesh-level dimension lifting: logical axis names -> mesh axes.

This is the paper's Definition 3.1 applied at the outermost hardware level:
every tensor axis is (conceptually) split ``size -> (mesh_extent, local)``
and the outer factor is given to a mesh resource.  The table below is the
single source of truth for the whole framework — model code only ever names
*logical* axes; pjit shardings, checkpoint resharding and the elastic
re-mesh all derive from here.

Lifting rules (v5e mesh ("pod", "data", "model")):

    batch        -> ("pod", "data")     data parallelism (+ pod DP)
    seq_sp       -> "model"             sequence parallelism at layer edges
    d_model      -> ("pod", "data")     FSDP: params/optimizer fully sharded
    d_ff/heads/
    vocab/experts/
    d_inner/lru  -> "model"             tensor/expert parallelism
    everything else -> replicated

A mesh axis is used at most once per spec (first logical axis wins), and an
axis is only assigned if it divides the dimension — otherwise it falls back
to replication (e.g. 40 heads on a 16-way model axis).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> candidate mesh axes, in preference order.  Tuple entries
# mean "all together" (e.g. batch over pod AND data).
PARAM_RULES: dict[str, tuple] = {
    "d_ff": ("model",),
    "moe_ff": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "d_inner": ("model",),
    "lru": ("model",),
    "d_model": (("pod", "data"),),          # FSDP axis for parameters
}

ACT_RULES: dict[str, tuple] = {
    "batch": (("pod", "data"),),
    "seq_sp": ("model",),
    "kv_seq": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "d_inner": ("model",),
    "lru": ("model",),
    "ssm_heads": ("model",),
}


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _resolve(rules: dict, axes: Sequence[Optional[str]], shape: Sequence[int],
             mesh: Mesh) -> P:
    if axes is None:
        axes = (None,) * len(shape)
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    entries = []
    for dim, name in zip(shape, axes):
        assigned = None
        for cand in rules.get(name or "", ()):
            group = cand if isinstance(cand, tuple) else (cand,)
            group = tuple(g for g in group if g in sizes)
            if not group or any(g in used for g in group):
                continue
            extent = int(np.prod([sizes[g] for g in group]))
            if extent > 1 and dim % extent == 0:
                assigned = group if len(group) > 1 else group[0]
                used.update(group)
                break
        entries.append(assigned)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_spec(axes: Sequence[Optional[str]], shape: Sequence[int], mesh: Mesh) -> P:
    return _resolve(PARAM_RULES, axes, shape, mesh)


def act_spec(axes: Sequence[Optional[str]], shape: Sequence[int], mesh: Mesh) -> P:
    return _resolve(ACT_RULES, axes, shape, mesh)


def param_shardings(params, axes_tree, mesh: Mesh):
    """NamedSharding pytree for a params pytree + its logical-axes pytree."""
    flat_p, treedef = jax.tree.flatten(params)
    flat_a = treedef.flatten_up_to(axes_tree)
    return treedef.unflatten([
        NamedSharding(mesh, param_spec(a, p.shape, mesh))
        for p, a in zip(flat_p, flat_a)])


def param_pspecs(params, axes_tree, mesh: Mesh):
    flat_p, treedef = jax.tree.flatten(params)
    flat_a = treedef.flatten_up_to(axes_tree)
    return treedef.unflatten([
        param_spec(a, p.shape, mesh) for p, a in zip(flat_p, flat_a)])


# ---------------------------------------------------------------------------
# in-model constraints: no-ops without a mesh, so models run on bare CPU
# ---------------------------------------------------------------------------

def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names (divisibility-checked);
    identity when no mesh is active (smoke tests, single-device runs)."""
    mesh = _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = act_spec(axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh() -> Optional[Mesh]:
    try:
        env = jax._src.mesh.thread_resources.env  # physical mesh ctx manager
        mesh = env.physical_mesh
        if mesh is not None and not mesh.empty:
            return mesh
    except Exception:
        pass
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is not None and not am.empty:  # use_mesh-style context
            return jax._src.mesh.get_concrete_mesh()
    except Exception:
        pass
    return None
