"""Latency-hiding collective matmuls (overlap compute with ICI transfers).

Two schedules, both expressed as ppermute rings inside ``shard_map`` so XLA's
latency-hiding scheduler can overlap each step's transfer with the next
step's matmul (the classic "collective matmul" of Wang et al. / Megatron-TP
on TPU, here derived as one more dimension lifting: the contraction or
gather axis is lifted over the ring position):

* ``ag_matmul(x_shard, w, axis)``   — y = all_gather(x, axis) @ w without
  materializing the gathered x: at ring step t each device multiplies the
  chunk it currently holds into the matching output rows, then rotates the
  chunk.  Peak memory: one chunk instead of the full gather.

* ``psum_matmul(x, w_shard, axis)`` — y = psum_scatter(x @ w_shard) chunked
  over rows: each device's partial rotates around the ring accumulating, so
  reduction transfers hide behind the remaining chunks' matmuls.

Both are thin consumers of derived ``DistributedPlan``s
(``repro.distributed.plan``): the collective choice (all-gather vs psum) and
the ring's shard extents come from ``derive_plan`` over the mesh-lifted
matmul normal form — asserted, not assumed — and the rings are the
latency-hiding *implementations* of the plan's collective steps.

Numerics are validated against the naive forms in subprocess multi-device
tests (tests/test_distributed.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.mesh import MeshShape
from repro.distributed import plan as dplan
from repro.kernels import ops


def ag_matmul(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """Inside shard_map: x (m_shard, k) sharded on rows over ``axis_name``;
    w (k, n) replicated.  Returns y = all_gather(x) @ w, (m_full, n),
    computed as a ppermute ring (no full gather buffer) — the ring being
    the latency-hiding form of the plan's derived all-gather."""
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m_shard, kdim = x.shape
    n = w.shape[1]
    plan = dplan.matmul_plan(m_shard * p, kdim, n,
                             MeshShape(((axis_name, p),)),
                             shard={"m": axis_name}, replicate_out=True)
    assert plan.collective == "all_gather", plan.collective
    rows = plan.local_extent("i")                 # == m_shard, derived
    y = jnp.zeros((rows * p, n), x.dtype)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(t, carry):
        y, chunk = carry
        src = (idx - t) % p                       # whose rows we now hold
        part = ops.matmul(chunk, w, out_dtype=x.dtype)
        y = jax.lax.dynamic_update_slice(y, part, (src * rows, 0))
        chunk = jax.lax.ppermute(chunk, axis_name, perm)
        return (y, chunk)

    y, _ = jax.lax.fori_loop(0, p, body, (y, x))
    return y


def psum_matmul(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """Inside shard_map: x (m, k_shard) column-sharded, w (k_shard, n)
    row-sharded over ``axis_name``.  Returns the *full* y = sum_p x_p @ w_p
    on every device, with the derived psum pipelined as chunked per-row-block
    reductions so transfers overlap the remaining chunks' matmuls."""
    p = jax.lax.axis_size(axis_name)
    m, k_shard = x.shape
    plan = dplan.matmul_plan(m, k_shard * p, w.shape[1],
                             MeshShape(((axis_name, p),)),
                             shard={"k": axis_name})
    assert plan.collective == "psum", plan.collective
    assert plan.local_extent("k") == k_shard
    chunks = min(p, max(m // 8, 1))
    rows = m // chunks

    def chunk_fn(i, acc):
        xi = jax.lax.dynamic_slice_in_dim(x, i * rows, rows, 0)
        part = ops.matmul(xi, w, out_dtype=jnp.float32)
        part = jax.lax.psum(part, axis_name)      # per-chunk reduction
        return jax.lax.dynamic_update_slice(acc, part.astype(x.dtype),
                                            (i * rows, 0))

    y = jnp.zeros((m, w.shape[1]), x.dtype)
    y = jax.lax.fori_loop(0, chunks, chunk_fn, y)
    if m % chunks:
        tail = ops.matmul(x[chunks * rows:], w, out_dtype=jnp.float32)
        y = y.at[chunks * rows:].set(jax.lax.psum(tail, axis_name).astype(x.dtype))
    return y


def reference_ag_matmul(x, w, axis_name):
    return jnp.dot(jax.lax.all_gather(x, axis_name, tiled=True), w,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def reference_psum_matmul(x, w, axis_name):
    return jax.lax.psum(jnp.dot(x, w, preferred_element_type=jnp.float32),
                        axis_name).astype(x.dtype)
