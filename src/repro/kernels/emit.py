"""Generic Pallas emitter: an executable kernel from a derived ``Schedule``.

``emit_pallas(schedule)`` is the single code generator behind every derived
op: the grid, BlockSpecs, dimension semantics and scratch accumulator all
come from the schedule (which in turn was derived from the normalized, lifted
expression), so no kernel hand-writes its layout.  The in-block body is the
schedule's semiring:

* ``(mul, add)`` — the einsum the axis structure implies (a plain MXU dot
  for GEMM, elementwise multiply for Hadamard, a batched dot for the lifted
  expert axis), with f32 accumulation across the sigma (reduce) grid steps;
* any other registered combine/reduce pair (max-plus, min-plus) — operands
  are aligned to (out axes + contracted axes), paired with the combine op,
  folded with the reduce op in-block, and accumulated across sigma steps
  with the same reduce op from its identity element.

The accumulator flushes to the output dtype on the last sigma step.

Psi views ride as index-map offsets: an operand whose Access carried a
constant term gets a leading block-1 dimension whose block index is pinned
at the viewed slab (``OperandSpec.offsets``) — sliced operands run derived
kernels with no materialized copy.

``emit_bundle`` wraps a cached ``ScheduleBundle`` into the full executable
contract the ops layer uses (pad with the semiring's inert element, run,
slice the logical result), and ``emit_shard_map`` stacks the mesh level on
top: the same derived kernel (or the jnp oracle) runs per shard inside
``shard_map`` with a ``DistributedPlan``'s partition specs and collectives.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import semiring
from repro.core import schedule as sched_mod
from repro.core.schedule import Schedule, ScheduleBundle, StreamingSchedule
from repro.core.semiring import MASK_NEG_INF as NEG_INF

def compiler_params(*, dimension_semantics,
                    vmem_limit_bytes: Optional[int] = None
                    ) -> pltpu.CompilerParams:
    """Mosaic parameters for one kernel.  ``vmem_limit_bytes`` is the VMEM
    capacity of the hardware table the schedule was derived against: the
    derivation certifies the working set under it, so the compiler's
    scoped limit (16 MiB by default on v5e) must not be lower."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes)


#: largest page table ``_index_map`` will lower.  The per-page slab lookup
#: unrolls as one ``jnp.where`` select per table entry (Pallas index maps
#: may not capture constant arrays), so the emitted index map grows
#: linearly in the view's page count — past this bound the fold is
#: pathological and the emitter refuses instead of silently producing it.
MAX_PAGE_TABLE_ENTRIES = 1024


def _index_map(grid_dims: tuple[Optional[int], ...],
               offsets: tuple[int, ...] = (),
               page_table: Optional[tuple] = None,
               page_slot_dim: Optional[int] = None,
               n_prefetch: int = 0,
               runtime_slab: bool = False) -> Callable:
    """BlockSpec index map from the operand's grid bindings.

    ``offsets`` add a constant block offset per dimension (a psi view's
    slab).  ``page_table`` generalizes the constant to one-per-grid-step on
    the *leading* dimension: streamed block ``k`` reads stored block
    ``page_table[k]`` — the static lookup that lowers a paged psi view's
    per-page slab offsets without a gather-copy.  The lookup is unrolled
    as a ``jnp.where`` fold over integer literals because Pallas index
    maps may not capture constant arrays; tables past
    ``MAX_PAGE_TABLE_ENTRIES`` raise instead of emitting the fold.

    With ``page_slot_dim`` the table is stacked 2-D ``[slot, k]`` (batched
    multi-slot decode): the fold runs over the row-major flattened table on
    the combined key ``s * n_steps + k``, with ``s`` read from grid axis
    ``page_slot_dim`` — same select-fold, two grid axes keying it.  The
    entry budget applies to the flattened table.

    Under scalar prefetch the map also receives the ``n_prefetch``
    prefetched refs after the grid indices; with ``runtime_slab`` dim 0's
    block index adds the first of them — a psi slab read at run time
    (the layer of a stacked weight)."""
    if page_table is not None and page_slot_dim is not None:
        n_steps = len(page_table[0])
        flat_table = tuple(t for row in page_table for t in row)
    elif page_table is not None:
        n_steps = None
        flat_table = tuple(page_table)
    else:
        n_steps = None
        flat_table = None
    if flat_table is not None and len(flat_table) > MAX_PAGE_TABLE_ENTRIES:
        raise ValueError(
            f"page table with {len(flat_table)} entries: the paged index "
            f"map lowers one jnp.where select per entry, linear in the "
            f"view's page count — past {MAX_PAGE_TABLE_ENTRIES} entries "
            f"the unrolled fold is pathological; split the view or raise "
            f"emit.MAX_PAGE_TABLE_ENTRIES deliberately")
    offs = offsets or (0,) * len(grid_dims)

    def _lookup(i):
        slab = jnp.int32(flat_table[0])
        for k, t in enumerate(flat_table[1:], start=1):
            slab = jnp.where(i == k, jnp.int32(t), slab)
        return slab

    def imap(*args):
        gids = args[:len(args) - n_prefetch]
        idx = []
        for dim, (d, off) in enumerate(zip(grid_dims, offs)):
            i = (gids[d] if d is not None else 0) + off
            if dim == 0 and runtime_slab:
                i = i + args[len(gids)][0]
            if dim == 0 and flat_table is not None:
                if n_steps is not None:
                    i = gids[page_slot_dim] * n_steps + i
                i = _lookup(i)
            idx.append(i)
        return tuple(idx)
    return imap


def _block_origin(spec) -> tuple:
    """Element index of the current grid cell's block origin in ``spec``'s
    operand — what its BlockSpec index map would have pinned."""
    return tuple((pl.program_id(d) if d is not None else 0) * b + off * b
                 for d, b, off in zip(spec.grid_dims, spec.block,
                                      spec.offsets or (0,) * len(spec.block)))


def _in_spec(spec, scalar: bool = False) -> pl.BlockSpec:
    """The BlockSpec of one input operand.  A ``scalar`` operand (the int32
    position of a ``dynamic-pos`` kind, read only as scalars that steer
    control flow) rides whole in SMEM: its per-cell block, e.g. ``(1, 2)``
    of a ``(slots, 2)`` array, breaks the (8, 128) VMEM tiling rule, and
    scalar loads belong in SMEM anyway.  The body reads it at
    ``_block_origin``."""
    if scalar:
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.BlockSpec(spec.block, _index_map(spec.grid_dims, spec.offsets,
                                               spec.page_table,
                                               spec.page_slot_dim))


def _jnp_combine(name: str) -> Callable:
    return getattr(jnp, semiring.combine_def(name).jnp_name)


def _general_combine(schedule: Schedule, combine_fn, reducer, vals):
    """Body for non-(mul, add) semirings: align every block to (out axes +
    contracted axes), pair with ``combine_fn``, fold the contraction with
    the reduce op's axis reducer."""
    joint = tuple(schedule.out.axes) + tuple(schedule.contracted)
    aligned = []
    for opn, v in zip(schedule.ins, vals):
        # squeeze block-1 dims outside the joint axes (the psi slab dim)
        keep = [i for i, ax in enumerate(opn.axes) if ax in joint]
        v = v.reshape(tuple(v.shape[i] for i in keep))
        src = {opn.axes[i]: pos for pos, i in enumerate(keep)}
        v = jnp.transpose(v, [src[ax] for ax in joint if ax in src])
        for pos, ax in enumerate(joint):
            if ax not in src:
                v = jnp.expand_dims(v, pos)
        aligned.append(v.astype(jnp.float32))
    out = functools.reduce(combine_fn, aligned)
    if schedule.contracted:
        red = tuple(range(len(schedule.out.axes), len(joint)))
        out = reducer(out, axis=red)
    return out


def emit_pallas(schedule: Schedule, combine=None, *, out_dtype=None,
                interpret: bool = False,
                acc_dtype=None,
                vmem_limit_bytes: Optional[int] = None) -> Callable:
    """Build the ``pl.pallas_call`` a schedule describes.

    Returns ``fn(*operands) -> out`` over arrays of exactly the schedule's
    (padded) operand shapes.  ``combine`` overrides the schedule's pairing op
    by name (it defaults to ``schedule.combine``, which ``derive_schedule``
    copied from the expression's normal form).  ``acc_dtype`` is the
    accumulator the solver budgeted for — it becomes the MXU
    ``preferred_element_type`` and the sigma scratch dtype; only the
    (mul, add) semiring has non-f32 accumulation paths.
    ``vmem_limit_bytes`` rides to ``compiler_params``.

    An operand with ``runtime_slab`` makes the kernel take its slab as a
    scalar-prefetch operand: ``fn(slab, *operands)``, ``slab`` an int32
    ``(1,)`` array that every such operand's index map adds on dim 0.
    Such operands are bound in place: their non-contracted dims may fall
    short of the schedule's padded extent within the last block.
    """
    ni = len(schedule.ins)
    n_prefetch = int(any(opn.runtime_slab for opn in schedule.ins))
    out_dtype = jnp.dtype(out_dtype or jnp.float32)
    acc_dtype = jnp.dtype(acc_dtype or jnp.float32)
    spec, in_keep = schedule.einsum_plan()
    red = schedule.reduce_grid_dim
    gk = schedule.grid[red].extent if red is not None else 0
    combine_name = combine or schedule.combine
    reduce_name = schedule.reduce_op
    multiplicative = (combine_name, reduce_name) == ("mul", "add")
    if acc_dtype != jnp.float32 and not multiplicative:
        raise ValueError(
            f"acc_dtype={acc_dtype} requires the (mul, add) semiring, got "
            f"({combine_name!r}, {reduce_name!r})")
    out_block = schedule.out.block
    if not multiplicative:
        combine_fn = _jnp_combine(combine_name)
        rdef = semiring.reduce_def(reduce_name)
        reducer = getattr(jnp, rdef.jnp_reducer)
        acc_step = getattr(jnp, rdef.jnp_name)
        identity = rdef.identity

    def body(*refs):
        refs = refs[n_prefetch:]
        o_ref = refs[ni]
        if multiplicative:
            squeezed = [
                refs[i][...].reshape(tuple(opn.block[d] for d in keep))
                for i, (opn, keep) in enumerate(zip(schedule.ins, in_keep))
            ]
            val = jnp.einsum(spec, *squeezed,
                             preferred_element_type=acc_dtype)
        else:
            val = _general_combine(schedule, combine_fn, reducer,
                                   [refs[i][...] for i in range(ni)])
        val = val.reshape(out_block)
        if red is None:
            o_ref[...] = val.astype(out_dtype)
        else:
            acc_ref = refs[ni + 1]
            kk = pl.program_id(red)

            @pl.when(kk == 0)
            def _init():
                if multiplicative:
                    acc_ref[...] = jnp.zeros_like(acc_ref)
                else:
                    acc_ref[...] = jnp.full_like(acc_ref, identity)

            if multiplicative:
                acc_ref[...] += val
            else:
                acc_ref[...] = acc_step(acc_ref[...], val)

            @pl.when(kk == gk - 1)
            def _flush():
                o_ref[...] = acc_ref[...].astype(out_dtype)

    grid = dict(
        grid=schedule.grid_extents,
        in_specs=[pl.BlockSpec(opn.block, _index_map(
            opn.grid_dims, opn.offsets, n_prefetch=n_prefetch,
            runtime_slab=opn.runtime_slab)) for opn in schedule.ins],
        out_specs=pl.BlockSpec(out_block, _index_map(
            schedule.out.grid_dims, schedule.out.offsets,
            n_prefetch=n_prefetch)),
        scratch_shapes=([pltpu.VMEM(out_block, acc_dtype)]
                        if red is not None else []))
    if n_prefetch:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch, **grid))
    call = pl.pallas_call(
        body,
        **grid,
        out_shape=jax.ShapeDtypeStruct(schedule.out.shape, out_dtype),
        compiler_params=compiler_params(
            dimension_semantics=schedule.dimension_semantics,
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )

    def fn(*arrays):
        if len(arrays) != ni + n_prefetch:
            raise ValueError(f"{schedule.name}: expected {ni} operands"
                             + (" after the slab" if n_prefetch else ""))
        for arr, opn in zip(arrays[n_prefetch:], schedule.ins):
            if not _shape_ok(tuple(arr.shape), opn):
                raise ValueError(
                    f"{schedule.name}: operand {opn.array} has shape "
                    f"{arr.shape}, schedule derived {opn.shape} — pad first")
        return call(*arrays)

    return fn


def _shape_ok(shp: tuple[int, ...], opn) -> bool:
    """A psi-view operand may be bound with MORE leading slabs than the
    pinned index needs; every other dim must match the schedule exactly,
    except that an in-place (runtime-slab) operand's dim may end inside
    its last block (the kernel's masked edge)."""
    if len(shp) != len(opn.shape):
        return False
    if shp == opn.shape:
        return True
    if opn.runtime_slab:
        return shp[0] >= 1 and all(
            t - b < s <= t for s, t, b in zip(shp[1:], opn.shape[1:],
                                              opn.block[1:]))
    return (opn.is_psi_view and shp[0] >= opn.shape[0]
            and shp[1:] == opn.shape[1:])


# ---------------------------------------------------------------------------
# recurrent emitter: the sigma accumulator generalized to a typed carried-
# state monoid — online softmax, the SSD chunked scan and the RG-LRU gated
# scan are registered *kinds* sharing one init/step/flush driver
# ---------------------------------------------------------------------------

def _cell_shape(spec) -> tuple[int, ...]:
    """An operand's per-grid-cell block: its block extents on the dims no
    grid axis drives (the derived analogue of squeezing the lifted dims)."""
    return tuple(b for b, d in zip(spec.block, spec.grid_dims) if d is None)


def _softmax_kind(rs: StreamingSchedule, *, scale, causal, logical_stream,
                  out_dtype, acc_dtype):
    """The online-softmax monoid: running max ``m`` + denominator ``l`` per
    output row and the accumulator *rescaled* by ``exp(m_prev - m_new)``
    each streamed step; the flush divides by ``l``.  Masking is positional
    and derived from the schedule's streamed-axis metadata: ``causal``
    keeps keys at or before the query's absolute position, ``window`` drops
    keys more than ``window`` behind it, ``prefix_len`` re-admits the
    bidirectional prefix block (PaLI prefix-LM), and ``logical_stream``
    masks keys the pad added — each with its block-skip, so fully-masked
    streamed blocks never run.
    """
    ni = len(rs.ins)
    bq, bk = rs.row_block, rs.stream_block
    stream_dim = rs.stream_grid_dim
    nk = rs.grid[stream_dim].extent
    row_dim = rs.out.grid_dims[rs.out.axes.index(rs.row_axis)]
    sk_pad = nk * bk
    masked_pad = logical_stream is not None and logical_stream < sk_pad
    window, prefix_len = rs.window, rs.prefix_len
    if (window or prefix_len) and not causal:
        raise ValueError(
            f"window={window} / prefix_len={prefix_len} require causal "
            "attention (the honor-or-raise contract of _chunk_mask)")

    # both in-block contractions as derived einsum plans (the axis structure
    # of the blocks, not a hand-chosen spec)
    scores_plan, scores_keep = rs.stages[0].einsum_plan()
    ctx_plan, ctx_keep = rs.stages[1].einsum_plan()
    acc_block = rs.acc_block

    ns = len(rs.state_outs)           # 0 (plain) or 2 (exported (m, l))

    def body(*refs):
        o_ref = refs[ni]
        m_ref, l_ref, acc_ref = refs[ni + 1 + ns:ni + 4 + ns]
        qi = pl.program_id(row_dim)
        ki = pl.program_id(stream_dim)

        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # skip streamed blocks that are entirely masked: strictly above the
        # causal diagonal, or entirely behind the local window.  A block
        # touching the bidirectional prefix region (some row AND some key
        # below prefix_len) is re-admitted against BOTH skips — prefix
        # blocks sit above the diagonal too.  Key padding always skips.
        admit = (jnp.logical_and(ki * bk < prefix_len, qi * bq < prefix_len)
                 if prefix_len else None)
        run = True
        if causal:
            run = ki * bk <= qi * bq + bq - 1
            if admit is not None:
                run = jnp.logical_or(run, admit)
        if window:
            below = ki * bk + bk - 1 > qi * bq - window
            if admit is not None:
                below = jnp.logical_or(below, admit)
            run = jnp.logical_and(run, below)
        if masked_pad:
            run = jnp.logical_and(run, ki * bk < logical_stream)

        @pl.when(run)
        def _step():
            q, k = (refs[i][...].reshape(
                tuple(opn.block[d] for d in keep))
                for i, (opn, keep) in enumerate(zip(rs.ins[:2], scores_keep)))
            s = jnp.einsum(scores_plan, q, k,
                           preferred_element_type=acc_dtype) * scale
            need_mask = causal or masked_pad
            if need_mask:
                qpos = qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kpos = ki * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                mask = jnp.ones((bq, bk), bool)
                if causal:
                    mask = kpos <= qpos
                    if window:
                        mask = jnp.logical_and(mask, kpos > qpos - window)
                    if prefix_len:
                        mask = jnp.logical_or(
                            mask, jnp.logical_and(qpos < prefix_len,
                                                  kpos < prefix_len))
                if masked_pad:
                    mask = jnp.logical_and(mask, kpos < logical_stream)
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[:, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
            m_ref[:, 0] = m_new
            v = refs[2][...].reshape(
                tuple(rs.ins[2].block[d] for d in ctx_keep[1]))
            acc_ref[...] = (
                acc_ref[...] * corr[:, None]
                + jnp.einsum(ctx_plan, p.astype(v.dtype), v,
                             preferred_element_type=acc_dtype
                             ).reshape(acc_block))

        @pl.when(ki == nk - 1)
        def _flush():
            o_ref[...] = (acc_ref[...] /
                          jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                          ).astype(out_dtype).reshape(rs.out.block)
            if ns:                    # export the final (m, l) statistics
                refs[ni + 1][...] = m_ref[...].reshape(
                    rs.state_outs[0].block)
                refs[ni + 2][...] = l_ref[...].reshape(
                    rs.state_outs[1].block)

    scratch = [
        pltpu.VMEM((bq, 1), acc_dtype),              # running max m
        pltpu.VMEM((bq, 1), acc_dtype),              # denominator l
        pltpu.VMEM(acc_block, acc_dtype),            # rescaled acc
    ]
    return body, scratch


def _tril(q: int) -> jax.Array:
    """The (q, q) causal mask ``i >= j`` of a chunk.  Contracted against
    it on the MXU (as 0/1 values), a row gives its prefix or suffix sums:
    Mosaic has no ``cumsum`` lowering, so the SSD segment sums run so."""
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >=
            jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _dot(a: jax.Array, b: jax.Array, contract) -> jax.Array:
    """2-D ``dot_general`` contracting dims ``contract = (a_dims, b_dims)``
    at full f32 precision (the recurrences carry f32 state)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=a.dtype)


def _ssd_kind(rs: StreamingSchedule, *, scale, causal, logical_stream,
              out_dtype, acc_dtype):
    """The SSD (Mamba-2) monoid: one inter-chunk state ``h`` (head,
    head_dim, state_dim) per grid cell, stepped ``h' = chunk_decay * h +
    B'(decay . x)`` and exported at the last chunk.  Per streamed step the
    two derived stage contractions run on the diagonal chunk — G = C.B'
    and y = P.x — welded through the segsum decay weighting ``P = G . L``
    (the monoid's nonlinearity, exactly where softmax's exp sits), plus the
    monoid's state readout ``C.h`` and state update.  Operand order:
    (C, B, X, dA, H0); outputs (y, h_final)."""
    ni = len(rs.ins)
    stream_dim = rs.stream_grid_dim
    nk = rs.grid[stream_dim].extent
    scores_plan, _ = rs.stages[0].einsum_plan()         # "in,jn->ij"
    c_cell = _cell_shape(rs.ins[0])                     # (q, n)
    b_cell = _cell_shape(rs.ins[1])                     # (q, n)
    x_cell = _cell_shape(rs.ins[2])                     # (q, h, p)
    da_cell = _cell_shape(rs.ins[3])                    # (q, h)
    h_cell = _cell_shape(rs.ins[4])                     # (h, p, n)
    q = da_cell[0]
    n_so = len(rs.state_outs)         # 1 (h only) or 2 (+ per-chunk h_in)

    x_lead = (0,) * (len(rs.ins[2].block) - len(x_cell))
    y_lead = (0,) * (len(rs.out.block) - len(x_cell))
    hdim, p, n = h_cell

    def body(*refs):
        y_ref, hf_ref = refs[ni], refs[ni + 1]
        h_ref, dat_ref = refs[ni + 1 + n_so:ni + 3 + n_so]
        ki = pl.program_id(stream_dim)

        @pl.when(ki == 0)
        def _init():
            h_ref[...] = refs[4][...].reshape(h_cell).astype(acc_dtype)

        if n_so == 2:                 # checkpoint the state entering ki
            refs[ni + 2][...] = h_ref[...].reshape(rs.state_outs[1].block)
        Cb = refs[0][...].reshape(c_cell).astype(acc_dtype)
        Bb = refs[1][...].reshape(b_cell).astype(acc_dtype)
        # the decay head-major, so the loop below reads one head's row
        dat_ref[...] = jnp.transpose(
            refs[3][...].reshape(da_cell).astype(acc_dtype))    # (h, j)
        tril = _tril(q)
        tril_f = jnp.where(tril, jnp.ones((), acc_dtype),
                           jnp.zeros((), acc_dtype))
        ones_n = jnp.ones((q, n), acc_dtype)
        G = jnp.einsum(scores_plan, Cb, Bb,
                       preferred_element_type=acc_dtype)    # (i, j)

        # the context stage and the state update run per head: each is a
        # 2-D MXU contraction over (q, p) / (p, n) tiles, where the fused
        # (h, i, j) form needs 3-D relayouts Mosaic cannot lower.  A loop
        # (not an unrolled one) keeps one head's (q, q) tiles live at once
        def head(hh, carry):
            da = dat_ref[pl.ds(hh, 1), :]                   # (1, j)
            csh = _dot(tril_f, da, ((1,), (1,)))            # (i, 1)
            csh_row = _dot(da, tril_f, ((1,), (1,)))        # (1, i)
            L = jnp.exp(jnp.where(tril, csh - csh_row, NEG_INF))
            xh = refs[2][x_lead + (slice(None), pl.ds(hh, 1), slice(None))
                         ].reshape(q, p).astype(acc_dtype)  # (j, p)
            h_prev = h_ref[hh]                              # (p, n)
            y = _dot(G * L, xh, ((1,), (0,)))               # (i, p)
            y = y + _dot(Cb, h_prev, ((1,), (1,))) * jnp.exp(csh)
            y_ref[y_lead + (slice(None), pl.ds(hh, 1), slice(None))] = \
                y.astype(out_dtype).reshape(q, 1, p)
            total = csh[q - 1:q]                            # (1, 1)
            xd = xh * jnp.exp(total - csh)                  # (j, p)
            # the chunk decay as a (1, n) row (the same sum, contracted
            # against ones): Mosaic cannot broadcast (1, 1) to (p, n)
            total_row = _dot(da, ones_n, ((1,), (0,)))      # (1, n)
            h_ref[hh] = (jnp.exp(total_row) * h_prev
                         + _dot(xd, Bb, ((0,), (0,))))      # (p, n)
            return carry

        jax.lax.fori_loop(0, hdim, head, 0)

        @pl.when(ki == nk - 1)
        def _flush():
            hf_ref[...] = h_ref[...].reshape(rs.state_outs[0].block)

    scratch = [pltpu.VMEM(h_cell, acc_dtype),
               pltpu.VMEM((hdim, q), acc_dtype)]
    return body, scratch


def _gated_kind(rs: StreamingSchedule, *, scale, causal, logical_stream,
                out_dtype, acc_dtype):
    """The gated (RG-LRU) monoid: one state per channel, stepped ``h' = a h
    + b`` — the contraction-free recurrence.  Per streamed chunk the body
    exponentiates the gate log, scans the chunk with the associative gated
    combine, re-bases onto the carried state via the chunk's gate cumprod,
    and exports the final state.  Operand order: (log_a, b, H0); outputs
    (h_seq, h_final)."""
    ni = len(rs.ins)
    stream_dim = rs.stream_grid_dim
    nk = rs.grid[stream_dim].extent
    a_cell = _cell_shape(rs.ins[0])                     # (q, w)
    h_cell = rs.state_blocks()[0]                       # (1, w)

    def body(*refs):
        y_ref, hf_ref = refs[ni], refs[ni + 1]
        h_ref = refs[ni + 2]
        ki = pl.program_id(stream_dim)

        @pl.when(ki == 0)
        def _init():
            h_ref[...] = refs[2][...].reshape(h_cell).astype(acc_dtype)

        a = jnp.exp(refs[0][...].reshape(a_cell).astype(acc_dtype))
        b = refs[1][...].reshape(a_cell).astype(acc_dtype)

        def comb(x, y):
            return (x[0] * y[0], y[0] * x[1] + y[1])

        aa, hh = jax.lax.associative_scan(comb, (a, b), axis=0)
        hh = hh + aa * h_ref[...]                       # re-base on carry
        y_ref[...] = hh.astype(out_dtype).reshape(rs.out.block)
        h_ref[...] = hh[-1:]

        @pl.when(ki == nk - 1)
        def _flush():
            hf_ref[...] = h_ref[...].reshape(rs.state_outs[0].block)

    scratch = [pltpu.VMEM(h_cell, acc_dtype)]
    return body, scratch


def _flash_dq_kind(rs: StreamingSchedule, *, scale, causal, logical_stream,
                   out_dtype, acc_dtype):
    """Flash backward dQ: the same weld orientation as the forward (rows =
    queries, stream = keys) with the carried per-row gradient accumulator.
    Each streamed step recomputes the masked score block from stage 1,
    reconstructs ``p = exp(s - lse)`` from the saved (m, l) statistics,
    forms ``dS = p * (dO.Vᵀ - D)`` and folds stage 2's ``dS . K`` into the
    accumulator; the flush applies the score scale once.  Block-skip and
    in-block masking are byte-for-byte the forward's — the backward visits
    exactly the blocks the forward did.  Operand order:
    (Q, K, K2, dO, V, M, L, D)."""
    ni = len(rs.ins)
    bq, bk = rs.row_block, rs.stream_block
    stream_dim = rs.stream_grid_dim
    nk = rs.grid[stream_dim].extent
    row_dim = rs.out.grid_dims[rs.out.axes.index(rs.row_axis)]
    sk_pad = nk * bk
    masked_pad = logical_stream is not None and logical_stream < sk_pad
    window, prefix_len = rs.window, rs.prefix_len
    if (window or prefix_len) and not causal:
        raise ValueError(
            f"window={window} / prefix_len={prefix_len} require causal "
            "attention (the honor-or-raise contract of _chunk_mask)")
    scores_plan, scores_keep = rs.stages[0].einsum_plan()
    out_plan, out_keep = rs.stages[1].einsum_plan()
    acc_block = rs.acc_block                            # (bq, hd)
    vd = rs.ins[3].block[-1]

    def body(*refs):
        o_ref = refs[ni]
        acc_ref = refs[ni + 1]
        qi = pl.program_id(row_dim)
        ki = pl.program_id(stream_dim)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        admit = (jnp.logical_and(ki * bk < prefix_len, qi * bq < prefix_len)
                 if prefix_len else None)
        run = True
        if causal:
            run = ki * bk <= qi * bq + bq - 1
            if admit is not None:
                run = jnp.logical_or(run, admit)
        if window:
            below = ki * bk + bk - 1 > qi * bq - window
            if admit is not None:
                below = jnp.logical_or(below, admit)
            run = jnp.logical_and(run, below)
        if masked_pad:
            run = jnp.logical_and(run, ki * bk < logical_stream)

        @pl.when(run)
        def _step():
            q, k = (refs[i][...].reshape(
                tuple(opn.block[d] for d in keep))
                for i, (opn, keep) in enumerate(zip(rs.ins[:2], scores_keep)))
            s = jnp.einsum(scores_plan, q, k,
                           preferred_element_type=acc_dtype) * scale
            need_mask = causal or masked_pad
            if need_mask:
                qpos = qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kpos = ki * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                mask = jnp.ones((bq, bk), bool)
                if causal:
                    mask = kpos <= qpos
                    if window:
                        mask = jnp.logical_and(mask, kpos > qpos - window)
                    if prefix_len:
                        mask = jnp.logical_or(
                            mask, jnp.logical_and(qpos < prefix_len,
                                                  kpos < prefix_len))
                if masked_pad:
                    mask = jnp.logical_and(mask, kpos < logical_stream)
                s = jnp.where(mask, s, NEG_INF)
            mv = refs[5][...].reshape((bq,))
            lv = refs[6][...].reshape((bq,))
            dl = refs[7][...].reshape((bq,))
            lse = mv + jnp.log(jnp.maximum(lv, 1e-30))
            p = jnp.exp(s - lse[:, None]).astype(acc_dtype)
            do = refs[3][...].reshape((bq, vd)).astype(acc_dtype)
            vb = refs[4][...].reshape((bk, vd)).astype(acc_dtype)
            dp = jnp.einsum("ad,bd->ab", do, vb,
                            preferred_element_type=acc_dtype)
            ds = p * (dp - dl[:, None]).astype(acc_dtype)
            k2 = refs[2][...].reshape(
                tuple(rs.ins[2].block[d] for d in out_keep[1])
                ).astype(acc_dtype)
            acc_ref[...] += jnp.einsum(
                out_plan, ds, k2,
                preferred_element_type=acc_dtype).reshape(acc_block)

        @pl.when(ki == nk - 1)
        def _flush():
            o_ref[...] = (acc_ref[...] * scale).astype(out_dtype).reshape(
                rs.out.block)

    scratch = [pltpu.VMEM(acc_block, acc_dtype)]
    return body, scratch


def _flash_dkv_kind(rs: StreamingSchedule, *, scale, causal, logical_stream,
                    out_dtype, acc_dtype):
    """Flash backward dK/dV: the *transposed* weld — rows are key
    positions, the stream is query positions.  Each streamed step
    recomputes the transposed score block, reconstructs ``p``, contracts
    ``dSᵀ . Q`` into the dK accumulator (the main output) and folds
    ``pᵀ . dO`` into the carried dV, exported per row block.  The
    block-skip conditions mirror the forward's with the roles swapped, and
    padded query positions are always masked (their saved statistics can
    be degenerate).  Operand order: (K, Q, Q2, dO, V, M, L, D)."""
    ni = len(rs.ins)
    bj, bi = rs.row_block, rs.stream_block
    stream_dim = rs.stream_grid_dim
    nk = rs.grid[stream_dim].extent
    row_dim = rs.out.grid_dims[rs.out.axes.index(rs.row_axis)]
    si_pad = nk * bi
    masked_pad = logical_stream is not None and logical_stream < si_pad
    window, prefix_len = rs.window, rs.prefix_len
    if (window or prefix_len) and not causal:
        raise ValueError(
            f"window={window} / prefix_len={prefix_len} require causal "
            "attention (the honor-or-raise contract of _chunk_mask)")
    scores_plan, scores_keep = rs.stages[0].einsum_plan()
    out_plan, out_keep = rs.stages[1].einsum_plan()
    acc_block = rs.acc_block                            # (bj, hd)
    dv_block = rs.state_blocks()[0]                     # (bj, vd)
    vd = rs.ins[3].block[-1]

    def body(*refs):
        o_ref, dv_out = refs[ni], refs[ni + 1]
        dk_ref, dv_ref = refs[ni + 2], refs[ni + 3]
        ji = pl.program_id(row_dim)
        ki = pl.program_id(stream_dim)

        @pl.when(ki == 0)
        def _init():
            dk_ref[...] = jnp.zeros_like(dk_ref)
            dv_ref[...] = jnp.zeros_like(dv_ref)

        admit = (jnp.logical_and(ji * bj < prefix_len, ki * bi < prefix_len)
                 if prefix_len else None)
        run = True
        if causal:
            run = ji * bj <= ki * bi + bi - 1
            if admit is not None:
                run = jnp.logical_or(run, admit)
        if window:
            below = ji * bj + bj - 1 > ki * bi - window
            if admit is not None:
                below = jnp.logical_or(below, admit)
            run = jnp.logical_and(run, below)
        if masked_pad:
            run = jnp.logical_and(run, ki * bi < logical_stream)

        @pl.when(run)
        def _step():
            k, qb = (refs[i][...].reshape(
                tuple(opn.block[d] for d in keep))
                for i, (opn, keep) in enumerate(zip(rs.ins[:2], scores_keep)))
            s = jnp.einsum(scores_plan, k, qb,
                           preferred_element_type=acc_dtype) * scale
            need_mask = causal or masked_pad
            if need_mask:
                kpos = ji * bj + jax.lax.broadcasted_iota(
                    jnp.int32, (bj, bi), 0)
                qpos = ki * bi + jax.lax.broadcasted_iota(
                    jnp.int32, (bj, bi), 1)
                mask = jnp.ones((bj, bi), bool)
                if causal:
                    mask = kpos <= qpos
                    if window:
                        mask = jnp.logical_and(mask, kpos > qpos - window)
                    if prefix_len:
                        mask = jnp.logical_or(
                            mask, jnp.logical_and(qpos < prefix_len,
                                                  kpos < prefix_len))
                if masked_pad:
                    mask = jnp.logical_and(mask, qpos < logical_stream)
                s = jnp.where(mask, s, NEG_INF)
            mv = refs[5][...].reshape((bi,))
            lv = refs[6][...].reshape((bi,))
            dl = refs[7][...].reshape((bi,))
            lse = mv + jnp.log(jnp.maximum(lv, 1e-30))
            p = jnp.exp(s - lse[None, :]).astype(acc_dtype)   # (bj, bi)
            do = refs[3][...].reshape((bi, vd)).astype(acc_dtype)
            vb = refs[4][...].reshape((bj, vd)).astype(acc_dtype)
            dp = jnp.einsum("ad,bd->ba", do, vb,
                            preferred_element_type=acc_dtype)
            ds = p * (dp - dl[None, :]).astype(acc_dtype)
            q2 = refs[2][...].reshape(
                tuple(rs.ins[2].block[d] for d in out_keep[1])
                ).astype(acc_dtype)
            dk_ref[...] += jnp.einsum(
                out_plan, ds, q2,
                preferred_element_type=acc_dtype).reshape(acc_block)
            dv_ref[...] += jnp.einsum(
                "ab,bd->ad", p, do,
                preferred_element_type=acc_dtype).reshape(dv_block)

        @pl.when(ki == nk - 1)
        def _flush():
            o_ref[...] = (dk_ref[...] * scale).astype(out_dtype).reshape(
                rs.out.block)
            dv_out[...] = dv_ref[...].reshape(rs.state_outs[0].block)

    scratch = [pltpu.VMEM(acc_block, acc_dtype),
               pltpu.VMEM(dv_block, acc_dtype)]
    return body, scratch


def _ssd_backward_kind(rs: StreamingSchedule, *, scale, causal,
                       logical_stream, out_dtype, acc_dtype):
    """The SSD backward monoid over *reversed* chunks (the ops layer flips
    the chunk axis): the carried state is the inter-chunk cotangent ``dh``,
    seeded from the final-state cotangent ``dHf`` at step 0.  Each streamed
    step replays the forward chunk factoring per head, as the forward
    runs it, from the saved state checkpoint ``Hin``, then chains every
    cotangent:
    ``dX`` is the main output, ``dB``/``dC``/``ddA`` export per step,
    ``dh`` steps backward and flushes as ``dh0``.  Operand order:
    (C, B, dY, X, dA, Hin, dHf); outputs (dX, dh0, dB, dC, ddA)."""
    ni = len(rs.ins)
    stream_dim = rs.stream_grid_dim
    nk = rs.grid[stream_dim].extent
    scores_plan, _ = rs.stages[0].einsum_plan()         # "in,jn->ij"
    c_cell = _cell_shape(rs.ins[0])                     # (q, n)
    b_cell = _cell_shape(rs.ins[1])                     # (q, n)
    x_cell = _cell_shape(rs.ins[3])                     # (q, h, p)
    da_cell = _cell_shape(rs.ins[4])                    # (q, h)
    h_cell = _cell_shape(rs.ins[5])                     # (h, p, n)
    q, hdim = da_cell
    p, n = h_cell[1:]
    dy_lead = (0,) * (len(rs.ins[2].block) - len(x_cell))
    x_lead = (0,) * (len(rs.ins[3].block) - len(x_cell))
    h_lead = (0,) * (len(rs.ins[5].block) - len(h_cell))
    dx_lead = (0,) * (len(rs.out.block) - len(x_cell))

    def body(*refs):
        dx_ref = refs[ni]
        dh0_ref, db_ref, dc_ref, dda_ref = refs[ni + 1:ni + 5]
        dh_ref, dat_ref, ddat_ref = refs[ni + 5:ni + 8]
        ki = pl.program_id(stream_dim)

        @pl.when(ki == 0)
        def _init():
            dh_ref[...] = refs[6][...].reshape(h_cell).astype(acc_dtype)

        Cb = refs[0][...].reshape(c_cell).astype(acc_dtype)
        Bb = refs[1][...].reshape(b_cell).astype(acc_dtype)
        dat_ref[...] = jnp.transpose(
            refs[4][...].reshape(da_cell).astype(acc_dtype))    # (h, j)
        tril = _tril(q)
        tril_f = jnp.where(tril, jnp.ones((), acc_dtype),
                           jnp.zeros((), acc_dtype))
        ones_n = jnp.ones((q, n), acc_dtype)
        ones_q = jnp.ones((q, 1), acc_dtype)
        last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
        G = jnp.einsum(scores_plan, Cb, Bb,
                       preferred_element_type=acc_dtype)    # (i, j)

        def mid(ref, lead, hh):                             # (q, p) of head
            return ref[lead + (slice(None), pl.ds(hh, 1), slice(None))
                       ].reshape(q, p).astype(acc_dtype)

        # per head, as the forward: replay the chunk factoring, then chain
        # the cotangents back through it; dB, dC and dG sum over heads
        def head(hh, carry):
            dB, dC, dG = carry
            da = dat_ref[pl.ds(hh, 1), :]                   # (1, j)
            csh = _dot(tril_f, da, ((1,), (1,)))            # (i, 1)
            csh_row = _dot(da, tril_f, ((1,), (1,)))        # (1, i)
            L = jnp.exp(jnp.where(tril, csh - csh_row, NEG_INF))
            P = G * L
            in_decay = jnp.exp(csh)                         # (i, 1)
            hc = refs[5][h_lead + (hh,)].astype(acc_dtype)  # (p, n)
            dh = dh_ref[hh]                                 # (p, n)
            t_off = _dot(Cb, hc, ((1,), (1,)))              # (i, p)
            total = csh[q - 1:q]                            # (1, 1)
            total_row = _dot(da, ones_n, ((1,), (0,)))      # (1, n)
            decay = jnp.exp(total - csh)                    # (j, 1)
            xh = mid(refs[3], x_lead, hh)                   # (j, p)
            dyh = mid(refs[2], dy_lead, hh)                 # (i, p)
            xd = xh * decay

            dtotal = jnp.sum(jnp.sum(dh * hc, axis=1, keepdims=True),
                             axis=0, keepdims=True) * jnp.exp(total)
            dh_prev = jnp.exp(total_row) * dh
            dB = dB + _dot(xd, dh, ((1,), (0,)))            # (j, n)
            dxd = _dot(Bb, dh, ((1,), (1,)))                # (j, p)
            dx = dxd * decay
            ddec = jnp.sum(dxd * xh, axis=1, keepdims=True)     # (j, 1)
            dtotal = dtotal + jnp.sum(ddec * decay, axis=0, keepdims=True)
            dcsh = -(ddec * decay)
            dt_off = dyh * in_decay                         # (i, p)
            dcsh = dcsh + jnp.sum(dyh * t_off, axis=1,
                                  keepdims=True) * in_decay
            dC = dC + _dot(dt_off, hc, ((1,), (0,)))        # (i, n)
            dh_prev = dh_prev + _dot(dt_off, Cb, ((0,), (0,)))
            dP = _dot(dyh, xh, ((1,), (1,)))                # (i, j)
            dx = dx + _dot(P, dyh, ((0,), (0,)))            # (j, p)
            dG = dG + dP * L
            dseg = jnp.where(tril, dP * G * L, 0.0)
            dcsh = (dcsh + jnp.sum(dseg, axis=1, keepdims=True)
                    - _dot(dseg, ones_q, ((0,), (0,))))     # (j, 1)
            dcsh = dcsh + jnp.where(last, dtotal, 0.0)
            # the suffix sum over i >= j, as a row: dcsh against the mask
            ddat_ref[pl.ds(hh, 1), :] = _dot(dcsh, tril_f, ((0,), (0,)))
            dx_ref[dx_lead + (slice(None), pl.ds(hh, 1), slice(None))] = \
                dx.astype(out_dtype).reshape(q, 1, p)
            dh_ref[hh] = dh_prev
            return dB, dC, dG

        zeros_qn = jnp.zeros((q, n), acc_dtype)
        dB, dC, dG = jax.lax.fori_loop(
            0, hdim, head, (zeros_qn, zeros_qn, jnp.zeros((q, q), acc_dtype)))
        dC = dC + _dot(dG, Bb, ((1,), (0,)))
        dB = dB + _dot(dG, Cb, ((0,), (0,)))
        db_ref[...] = dB.reshape(rs.state_outs[1].block)
        dc_ref[...] = dC.reshape(rs.state_outs[2].block)
        dda_ref[...] = jnp.transpose(ddat_ref[...]).reshape(
            rs.state_outs[3].block)

        @pl.when(ki == nk - 1)
        def _flush():
            dh0_ref[...] = dh_ref[...].reshape(rs.state_outs[0].block)

    scratch = [pltpu.VMEM(h_cell, acc_dtype),
               pltpu.VMEM((hdim, q), acc_dtype),
               pltpu.VMEM((hdim, q), acc_dtype)]
    return body, scratch


def _windowed_decode_kind(rs: StreamingSchedule, *, scale, causal,
                          logical_stream, out_dtype, acc_dtype):
    """The windowed-decode monoid: online softmax over one query token's
    GQA group rows, streamed one KV page per step through the page-table
    index maps.  Operand order (Q, K, V, POS); the carried (m, l, acc)
    state is O(row x value) — with a window, the engine binds only the
    live pages, so a decode step is O(window) work and state no matter how
    long the sequence is.

    Masking is *dynamic*, from the runtime view-relative query position in
    the POS aux (``POS[0, 0]``): the page table is static per executor but
    the position is data, so one compiled kernel serves every token between
    page allocations.  Both the per-key mask and the whole-page block-skip
    derive from it — pages entirely after the query (or entirely behind
    the window) never run, which also keeps stale ring slabs inert."""
    ni = len(rs.ins)
    bq, bk = rs.row_block, rs.stream_block
    stream_dim = rs.stream_grid_dim
    nk = rs.grid[stream_dim].extent
    window = rs.window
    if rs.prefix_len:
        raise ValueError("windowed_decode does not take a prefix_len — "
                         "prefix tokens are all at or before the query")
    pos_spec = rs.ins[KIND_CONTRACTS["windowed_decode"].pos_input]
    scores_plan, scores_keep = rs.stages[0].einsum_plan()
    ctx_plan, ctx_keep = rs.stages[1].einsum_plan()
    acc_block = rs.acc_block

    def body(*refs):
        o_ref = refs[ni]
        m_ref, l_ref, acc_ref = refs[ni + 1:ni + 4]
        ki = pl.program_id(stream_dim)
        # view-relative query position: POS rides whole in SMEM, read at
        # the origin of the block its BlockSpec would have pinned
        vpos = refs[ni - 1][_block_origin(pos_spec)]

        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # dynamic block-skip: the page is after the query, or (windowed)
        # its newest key is already out of the window
        run = ki * bk <= vpos
        if window:
            run = jnp.logical_and(run, ki * bk + bk - 1 > vpos - window)

        @pl.when(run)
        def _step():
            q, k = (refs[i][...].reshape(
                tuple(opn.block[d] for d in keep))
                for i, (opn, keep) in enumerate(zip(rs.ins[:2], scores_keep)))
            s = jnp.einsum(scores_plan, q, k,
                           preferred_element_type=acc_dtype) * scale
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = kpos <= vpos
            if window:
                mask = jnp.logical_and(mask, kpos > vpos - window)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[:, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
            m_ref[:, 0] = m_new
            v = refs[2][...].reshape(
                tuple(rs.ins[2].block[d] for d in ctx_keep[1]))
            acc_ref[...] = (
                acc_ref[...] * corr[:, None]
                + jnp.einsum(ctx_plan, p.astype(v.dtype), v,
                             preferred_element_type=acc_dtype
                             ).reshape(acc_block))

        @pl.when(ki == nk - 1)
        def _flush():
            o_ref[...] = (acc_ref[...] /
                          jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                          ).astype(out_dtype).reshape(rs.out.block)

    scratch = [
        pltpu.VMEM((bq, 1), acc_dtype),              # running max m
        pltpu.VMEM((bq, 1), acc_dtype),              # denominator l
        pltpu.VMEM(acc_block, acc_dtype),            # rescaled acc
    ]
    return body, scratch


#: the carried-state monoid registry: ``expr.StateSpec.kind`` -> body
#: builder.  New recurrences (flash backward, windowed streams) register
#: here instead of growing their own emitters.  ``gated_backward`` IS the
#: forward ``gated`` body — the reversed cotangent recurrence is itself a
#: gated scan on flipped operands (the ops layer does the flip/shift).
RECURRENCE_KINDS: dict[str, Callable] = {
    "online_softmax": _softmax_kind,
    "ssd": _ssd_kind,
    "gated": _gated_kind,
    "flash_dq": _flash_dq_kind,
    "flash_dkv": _flash_dkv_kind,
    "ssd_backward": _ssd_backward_kind,
    "gated_backward": _gated_kind,
    "windowed_decode": _windowed_decode_kind,
}


@dataclasses.dataclass(frozen=True)
class KindContract:
    """The statically-declared guard + state discipline of a recurrence kind.

    Kind bodies used to keep their pad-guard strategy as closure-only state;
    the conformance analyzer (``analysis/conformance.py``) needs it as
    inspectable metadata to prove the emitted jaxpr honors it.

    ``guard`` names how the kind keeps padded streamed positions inert:

    * ``"identity-pad"`` — no in-kernel guard; the bundle executor pads with
      the monoid's identity element, so every step may fold unguarded
      (ssd, gated: zero-padded gates/inputs are the identity step).
    * ``"stream-mask"`` — folds into carried state must be dominated by the
      ``pos < logical_stream`` block-skip or the in-block pad mask
      (online softmax and the flash backwards: pad keys would otherwise
      poison the running max / denominator).
    * ``"dynamic-pos"`` — same, but the bound is *runtime data* read from
      the aux operand at ``pos_input`` (windowed decode: the view-relative
      query position).

    ``pos_input`` indexes ``schedule.ins`` (negative from the end) for the
    int32 position operand of a ``dynamic-pos`` kind.  ``causal_mask``
    marks kinds whose mask machinery also honors ``causal=True``.
    """
    guard: str
    pos_input: Optional[int] = None
    causal_mask: bool = False


#: kind -> declared guard/state contract, consumed by the conformance
#: analyzer.  A kind registered without a contract is skipped by the
#: guard-dominance rule (there is nothing declared to prove).
KIND_CONTRACTS: dict[str, KindContract] = {
    "online_softmax": KindContract(guard="stream-mask", causal_mask=True),
    "ssd": KindContract(guard="identity-pad"),
    "gated": KindContract(guard="identity-pad"),
    "flash_dq": KindContract(guard="stream-mask", causal_mask=True),
    "flash_dkv": KindContract(guard="stream-mask", causal_mask=True),
    "ssd_backward": KindContract(guard="identity-pad"),
    "gated_backward": KindContract(guard="identity-pad"),
    "windowed_decode": KindContract(guard="dynamic-pos", pos_input=-1),
}


def kind_contract(kind: str) -> Optional[KindContract]:
    return KIND_CONTRACTS.get(kind)


def register_recurrence_kind(kind: str, builder: Callable,
                             contract: Optional[KindContract] = None) -> None:
    RECURRENCE_KINDS[kind] = builder
    if contract is not None:
        KIND_CONTRACTS[kind] = contract


def emit_recurrent(rs: StreamingSchedule, *, scale: float = 1.0,
                   causal: bool = False, logical_stream: Optional[int] = None,
                   out_dtype=None, interpret: bool = False,
                   acc_dtype=None,
                   vmem_limit_bytes: Optional[int] = None) -> Callable:
    """Build the ``pl.pallas_call`` a ``RecurrentSchedule`` describes.

    The driver generalizes ``emit_pallas``'s sigma init/step/flush contract
    to a typed carried-state monoid: the state scratch initializes at step 0
    of the streamed grid axis, every step folds one streamed block through
    the registered kind's body (``RECURRENCE_KINDS``, keyed by the form's
    ``StateSpec.kind``), and the last step flushes — dividing out the
    softmax denominator, or exporting the final scan state as an extra
    kernel output (``state_outs``).

    Grid, BlockSpecs, dimension semantics, scratch shapes, masking metadata
    and every stage's in-block einsum all come from the schedule — nothing
    here is hand-written.  ``acc_dtype`` is the accumulator the solver
    budgeted for: it becomes every kind's carried-state scratch dtype, MXU
    ``preferred_element_type`` and exported-state dtype (default f32).
    """
    out_dtype = jnp.dtype(out_dtype or jnp.float32)
    acc_dtype = jnp.dtype(acc_dtype or jnp.float32)
    ni = len(rs.ins)
    builder = RECURRENCE_KINDS.get(rs.state.kind if rs.state else
                                   "online_softmax")
    if builder is None:
        raise ValueError(f"unregistered recurrence kind "
                         f"{rs.state.kind!r}; known: "
                         f"{sorted(RECURRENCE_KINDS)}")
    body, scratch = builder(rs, scale=scale, causal=causal,
                            logical_stream=logical_stream,
                            out_dtype=out_dtype, acc_dtype=acc_dtype)
    outs = (rs.out,) + rs.state_outs
    out_dtypes = (out_dtype,) + (acc_dtype,) * len(rs.state_outs)
    contract = kind_contract(rs.state.kind if rs.state else "online_softmax")
    pos = (contract.pos_input % ni
           if contract is not None and contract.pos_input is not None
           else None)
    call = pl.pallas_call(
        body,
        grid=rs.grid_extents,
        in_specs=[_in_spec(opn, scalar=(i == pos))
                  for i, opn in enumerate(rs.ins)],
        out_specs=[pl.BlockSpec(o.block, _index_map(o.grid_dims, o.offsets))
                   for o in outs],
        out_shape=[jax.ShapeDtypeStruct(o.shape, dt)
                   for o, dt in zip(outs, out_dtypes)],
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            dimension_semantics=rs.dimension_semantics,
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )

    def fn(*arrays):
        if len(arrays) != ni:
            raise ValueError(f"{rs.name}: expected {ni} operands")
        for arr, opn in zip(arrays, rs.ins):
            if tuple(arr.shape) != opn.shape:
                raise ValueError(
                    f"{rs.name}: operand {opn.array} has shape {arr.shape}, "
                    f"schedule derived {opn.shape} — pad first")
        out = call(*arrays)
        return out[0] if len(outs) == 1 else tuple(out)

    return fn


def emit_streaming(ss: StreamingSchedule, *, scale: float = 1.0,
                   causal: bool = False, logical_stream: Optional[int] = None,
                   out_dtype=None, interpret: bool = False) -> Callable:
    """.. deprecated:: the streaming (online-softmax) emitter is now the
    ``online_softmax`` kind of ``emit_recurrent``; kept for one release."""
    return emit_recurrent(ss, scale=scale, causal=causal,
                          logical_stream=logical_stream, out_dtype=out_dtype,
                          interpret=interpret)


def emit_recurrent_bundle(bundle: ScheduleBundle, *, scale: float = 1.0,
                          causal: bool = False, out_dtype=None,
                          interpret: bool = False) -> Callable:
    """Executable for a cached recurrent derivation over *logical* operands:
    pad the streamed axes to the derived block multiples (padded keys/tokens
    are inert — masked by the ``kpos < sk`` guard, or zero-padded into the
    monoid's identity step), run the emitted kernel, slice the logical
    result back out.  Exported state outputs pass through unsliced."""
    rs = bundle.schedule
    logical_stream = bundle.shapes[-1]
    kern = emit_recurrent(rs, scale=scale, causal=causal,
                          logical_stream=logical_stream,
                          out_dtype=out_dtype, interpret=interpret,
                          acc_dtype=bundle.acc_dtype,
                          vmem_limit_bytes=bundle.vmem_limit_bytes)
    out_slices = tuple(slice(0, d) for d in bundle.out_shape)
    exports = bool(rs.state_outs)

    def call(*arrays):
        padded = [_pad_to_shape(x, spec.shape)
                  for x, spec in zip(arrays, rs.ins)]
        out = kern(*padded)
        if exports:
            return (out[0][out_slices],) + tuple(out[1:])
        return out[out_slices]

    return call


#: one-release alias of :func:`emit_recurrent_bundle`
emit_streaming_bundle = emit_recurrent_bundle


# ---------------------------------------------------------------------------
# bundle executor: the ops-layer contract (collapse psi slabs, pad, run,
# slice) in one place, reused by the single-chip and shard_map paths
# ---------------------------------------------------------------------------

def _pad_to_shape(x: jax.Array, shape: tuple[int, ...],
                  value: float = 0.0) -> jax.Array:
    pads = [(0, t - d) for d, t in zip(x.shape, shape)]
    if any(p for _, p in pads):
        return jnp.pad(x, pads, constant_values=value)
    return x


def emit_bundle(bundle: ScheduleBundle, *, out_dtype=None,
                interpret: bool = False) -> Callable:
    """Executable for a cached derivation over *logical storage* operands.

    Collapses a psi view's fixed leading dims to the flat slab dim the
    schedule pinned, pads every operand to the schedule's (padded) storage
    shape with the semiring's inert element, runs the emitted kernel, and
    slices the logical result back out.  The missing-inert-element error
    is only raised when padding is actually required.

    A bundle with runtime-slab operands executes as ``call(slab, *arrays)``:
    ``slab`` an int32 scalar (it may be traced), each such operand the
    whole stacked leaf ``(L, ...)``, bound as it is — never padded.
    """
    sch = bundle.schedule
    kern = emit_pallas(sch, out_dtype=out_dtype, interpret=interpret,
                       acc_dtype=bundle.acc_dtype,
                       vmem_limit_bytes=bundle.vmem_limit_bytes)

    prep = []
    for spec, logical in zip(sch.ins, bundle.in_shapes):
        sym_rank = len(spec.shape) - (1 if spec.is_psi_view else 0)
        prep.append((len(logical) - sym_rank, spec))
    # the pad-value policy lives beside the bundle (schedule.py) so the
    # static verifier certifies the exact element this executor pads with
    pad_val = sched_mod.bundle_pad_value(bundle)
    out_slices = tuple(slice(0, d) for d in bundle.out_shape)

    slabbed = any(spec.runtime_slab for spec in sch.ins)

    def call(*arrays):
        padded = []
        if slabbed:
            slab, arrays = arrays[0], arrays[1:]
            padded.append(jnp.reshape(slab, (1,)).astype(jnp.int32))
        for x, (lead, spec) in zip(arrays, prep):
            if spec.runtime_slab:
                padded.append(x)
                continue
            if spec.is_psi_view:
                if lead > 1:                 # several fixed dims -> one slab
                    x = x.reshape((-1,) + x.shape[lead:])
                target = (x.shape[0],) + spec.shape[1:]
            else:
                if lead:                     # all-zero psi index: slab 0
                    x = x.reshape((-1,) + x.shape[lead:])[0]
                target = spec.shape
            padded.append(_pad_to_shape(x, target, pad_val))
        return kern(*padded)[out_slices]

    return call


# ---------------------------------------------------------------------------
# the mesh level: the same derived kernel per shard, inside shard_map
# ---------------------------------------------------------------------------

def emit_shard_map(plan, mesh, local_fn: Optional[Callable] = None, *,
                   out_dtype=None, interpret: bool = False,
                   use_kernel: bool = True) -> Callable:
    """Run a ``DistributedPlan``: the plan's per-shard derived kernel (or a
    caller-supplied differentiable local function, or the jnp oracle when
    ``use_kernel`` is False) inside ``shard_map`` with the plan's partition
    specs, followed by the plan's collective schedule.

    ``mesh`` is a live ``jax.sharding.Mesh`` whose axis names and sizes must
    match the plan's ``MeshShape``.  Returns ``fn(*global_operands) ->
    global_out``; operands bind exactly as in the single-chip path (storage
    shapes), only globally sized.
    """
    plan.check_mesh(mesh)
    if local_fn is None:
        if use_kernel:
            local_fn = emit_bundle(plan.bundle, out_dtype=jnp.float32,
                                   interpret=interpret)
        else:
            from repro.kernels import ref
            local_fn = functools.partial(ref.eval_nf, plan.local_nf)

    def body(*shards):
        y = local_fn(*shards)
        for step in plan.collectives:
            if step.kind == "psum":
                y = jax.lax.psum(y, step.mesh_axis)
            elif step.kind == "reduce_scatter":
                y = jax.lax.psum_scatter(y, step.mesh_axis,
                                         scatter_dimension=step.out_dim,
                                         tiled=True)
            elif step.kind == "all_gather":
                y = jax.lax.all_gather(y, step.mesh_axis, axis=step.out_dim,
                                       tiled=True)
            else:
                raise ValueError(f"unknown collective kind {step.kind!r}")
        return y if out_dtype is None else y.astype(out_dtype)

    return jax.shard_map(body, mesh=mesh, in_specs=plan.jax_in_specs(),
                         out_specs=plan.jax_out_spec(), check_vma=False)
