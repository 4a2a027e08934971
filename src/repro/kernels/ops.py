"""Public, jit-friendly entry points for the derived-schedule Pallas kernels.

Execution pipeline — the paper's derivation end to end, per call:

    expression ──normalize──► ONF ──lift/derive_schedule──► emit_pallas

The unit of dispatch is a **MoA expression** (``repro.core.expr``), not a
string op name: ``apply(expr, *arrays)`` runs any normalizable expression
through the derived-schedule pipeline, and the familiar entries
(``matmul``, ``expert_matmul``, ``moa_gemm``, ``hadamard``,
``semiring_matmul``) are one-line expression builders on top of it.  The
schedule cache (``repro.core.schedule``) is keyed on the expression's
*normal form*, and this module memoizes the emitted, jitted callables on the
same key, so hot serving and training paths never re-derive.

Dispatch is registry-driven (``repro.core.hardware``): the entry detected
once per process decides whether kernels compile (TPU), run through the
Pallas interpreter (CPU validation), or — for the high-level ``matmul`` /
``expert_matmul`` entries the models call — fall back to the XLA oracle with
identical f32-accumulation semantics.

``matmul(..., transpose_b=True)`` lowers ``x @ w.T`` to a transposed-operand
schedule: normalize turns the transposed leaf into column-gamma
coefficients, so the stored ``(n, k)`` array is blocked in place — no
relayout copy of (say) a vocab embedding table every step.

``matmul``/``expert_matmul``/``apply`` also accept a ``mesh=`` (a live
``jax.sharding.Mesh``): the call then derives a ``DistributedPlan``
(``repro.distributed.plan``) — partition specs, collective schedule and the
per-shard derived kernel all from the same lifted normal form — and runs it
through ``shard_map``.  ``shard`` names which axes lift onto which mesh
axes (roles ``{"m", "n", "k"}`` for matmul, plus ``"e"`` for experts; plan
axis symbols for ``apply``); non-divisible axes fall back to replication.

``layer_matmul(x, w_stacked, layer)`` — and ``matmul`` handed a
``LayerSlab`` — is the decode bodies' GEMM: the layer axis of a stacked
weight is lifted into the kernel's index space (a psi slab read at run
time from a scalar-prefetched index), so no launch copies a layer out.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import expr as E
from repro.core import schedule as _sched
from repro.core.blocking import BlockChoice
from repro.core.hardware import HardwareEntry, current_hardware, get_entry
from repro.kernels import ref
from repro.kernels.emit import emit_bundle, emit_shard_map


def _resolve(hardware, interpret) -> tuple[HardwareEntry, bool]:
    hw = hardware or current_hardware()
    return hw, (hw.interpret if interpret is None else interpret)


def _use_kernel(hw: HardwareEntry, interp: bool, interpret) -> bool:
    """The one dispatch policy for the streaming/recurrent entries
    (attention, scan_ssd, gated_scan): the derived kernel on compiled-Pallas
    entries, on "interpret" entries (the CPU validation path), or by
    explicit request; "xla" entries use the jnp oracle."""
    return (hw.backend == "pallas"
            or (hw.backend == "interpret" and interp)
            or bool(interpret))


# ---------------------------------------------------------------------------
# the generic executor: expression -> cached, jitted pad/kernel/slice callable
# ---------------------------------------------------------------------------

_CALLABLES: "OrderedDict[tuple, object]" = OrderedDict()
_CALLABLES_LOCK = threading.Lock()
_CALLABLES_SIZE = 512


def _block_key(blocks):
    return tuple(blocks) if isinstance(blocks, (list, tuple)) else blocks


def _cache_put(key, fn):
    with _CALLABLES_LOCK:
        fn = _CALLABLES.setdefault(key, fn)
        _CALLABLES.move_to_end(key)
        while len(_CALLABLES) > _CALLABLES_SIZE:
            _CALLABLES.popitem(last=False)
        return fn


def _cache_get(key):
    with _CALLABLES_LOCK:
        fn = _CALLABLES.get(key)
        if fn is not None:
            _CALLABLES.move_to_end(key)
        return fn


def _expr_callable(expr: "E.Expr", dtype_s: str, out_dtype_s: str,
                   hw_name: str, interpret: bool, blocks=None,
                   acc_dtype: str = "float32"):
    """The memoized executable for one normal form: pad operands to the
    schedule's storage shapes (with the semiring's inert element), run the
    emitted kernel, slice the logical result back out (``emit_bundle``)."""
    nf = expr if isinstance(expr, E.NormalForm) else E.normal_form(expr)
    key = (nf.key(), dtype_s, out_dtype_s, hw_name, interpret,
           _block_key(blocks), acc_dtype)
    fn = _cache_get(key)
    if fn is not None:
        return fn
    bundle = _sched.get_schedule(nf, dtype=dtype_s,
                                 hardware=get_entry(hw_name), blocks=blocks,
                                 acc_dtype=acc_dtype)
    call = jax.jit(emit_bundle(bundle, out_dtype=out_dtype_s,
                               interpret=interpret))
    return _cache_put(key, call)


def _sharded_callable(nf: "E.NormalForm", dtype_s: str, out_dtype_s: str,
                      hw_name: str, interpret: bool, use_kernel: bool,
                      mesh, shard: dict, replicate_out: bool,
                      local_fn=None, local_tag: Optional[str] = None,
                      scatter_axis=None, acc_dtype: str = "float32"):
    """Memoized shard_map executable for one (normal form, mesh, sharding)
    triple: derives (or re-reads from the plan cache) the DistributedPlan,
    then wraps its collectives around the per-shard kernel/oracle."""
    from repro.distributed import plan as dplan

    shard_key = tuple(sorted(shard.items()))
    key = ("shard", nf.key(), dtype_s, out_dtype_s, hw_name, interpret,
           use_kernel, mesh, shard_key, replicate_out, local_tag,
           scatter_axis, acc_dtype)
    fn = _cache_get(key)
    if fn is not None:
        return fn
    plan = dplan.derive_plan(nf, mesh, shard=shard,
                             hardware=get_entry(hw_name), dtype=dtype_s,
                             replicate_out=replicate_out,
                             scatter_axis=scatter_axis, acc_dtype=acc_dtype)
    call = jax.jit(emit_shard_map(plan, mesh, local_fn,
                                  out_dtype=out_dtype_s,
                                  interpret=interpret,
                                  use_kernel=use_kernel))
    return _cache_put(key, call)


def apply(expr: "E.Expr", *arrays: jax.Array, out_dtype=None,
          interpret: Optional[bool] = None,
          hardware: Optional[HardwareEntry] = None,
          blocks=None, mesh=None, shard: Optional[dict] = None,
          replicate_out: bool = False,
          acc_dtype: str = "float32",
          verify: Union[bool, str] = False) -> jax.Array:
    """Evaluate a composed MoA expression — the public derived-kernel entry.

    ``arrays`` bind the expression's leaves in composition order by their
    *storage* shapes: a row-major leaf takes its logical shape, a
    column-major leaf takes the reversed (physical buffer) shape — so
    ``transpose(arr((n, k)))`` and ``arr((k, n), layout="col")`` bind the
    identical ``(n, k)`` array, as they share a normal form.  On a Pallas
    backend the normal form is lifted, scheduled and emitted (cached per
    normal form); elsewhere the jnp oracle (``kernels.ref.eval_expr``)
    evaluates the same semantics.

    With ``mesh=`` (a live ``jax.sharding.Mesh``) the normal form is lifted
    one level further: ``shard`` maps its axis symbols to mesh axes, and the
    derived ``DistributedPlan`` runs the per-shard kernel (or oracle) inside
    ``shard_map`` with the plan's collectives.

    ``verify=True`` runs the static soundness checks (``repro.analysis``)
    on the derived schedule/plan before executing, raising
    ``VerificationError`` on any unsound derivation.  ``verify="kernel"``
    additionally traces the emitted Pallas kernel body and checks its
    effect summary against the schedule contract (single-chip path only;
    the sharded path keeps schedule-level checks).  Results are cached on
    the same normal-form keys as the schedules, so repeated calls — and
    every ``verify=False`` call — pay nothing.
    """
    nf = E.normal_form(expr)
    shapes = nf.leaf_storage_shapes()
    if len(arrays) != len(shapes):
        raise ValueError(f"expression has {len(shapes)} leaves, got "
                         f"{len(arrays)} arrays")
    for i, (a, s) in enumerate(zip(arrays, shapes)):
        if tuple(a.shape) != s:
            raise ValueError(f"leaf {i} ({nf.leaves[i].array!r}) expects "
                             f"storage shape {s}, got {tuple(a.shape)}")
    hw, interp = _resolve(hardware, interpret)
    out_dtype = jnp.dtype(out_dtype or arrays[0].dtype)
    # kernel path on Pallas backends or by explicit request; the registry's
    # "interpret"/"xla" entries otherwise use the jnp oracle (interpret-mode
    # Pallas is the validation path, not the default execution path)
    use_kernel = hw.backend == "pallas" or bool(interpret)
    dtype_s = str(jnp.dtype(arrays[0].dtype))
    if mesh is not None:
        if blocks is not None:
            raise ValueError(
                "apply(mesh=...) derives per-shard blocks from the plan; "
                "pinning blocks= is not supported on the sharded path")
        if verify:
            from repro import analysis
            analysis.verify_sharded(nf, mesh, shard or {}, hardware=hw,
                                    dtype=dtype_s,
                                    replicate_out=replicate_out,
                                    acc_dtype=acc_dtype)
        fn = _sharded_callable(nf, dtype_s, str(out_dtype), hw.name, interp,
                               use_kernel, mesh, shard or {}, replicate_out,
                               acc_dtype=acc_dtype)
        return fn(*arrays)
    if verify:
        from repro import analysis
        analysis.verify_expr(nf, dtype=dtype_s, hardware=hw, blocks=blocks,
                             acc_dtype=acc_dtype,
                             kernel=(verify == "kernel"))
    if use_kernel:
        fn = _expr_callable(nf, dtype_s, str(out_dtype), hw.name, interp,
                            blocks, acc_dtype=acc_dtype)
        return fn(*arrays)
    return ref.eval_expr(expr, *arrays).astype(out_dtype)


# ---------------------------------------------------------------------------
# kernel entry points (expression builders over the generic executor)
# ---------------------------------------------------------------------------

def moa_gemm(a: jax.Array, b: jax.Array, *, blocks: Optional[BlockChoice] = None,
             out_dtype=None, interpret: Optional[bool] = None,
             hardware: Optional[HardwareEntry] = None) -> jax.Array:
    """C = A @ B through the derived MoA blocked-contiguous schedule."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    hw, interp = _resolve(hardware, interpret)
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    fn = _expr_callable(E.matmul_expr(m, k, n), str(jnp.dtype(a.dtype)),
                        str(out_dtype), hw.name, interp, blocks)
    return fn(a, b)


def expert_gemm(x: jax.Array, w: jax.Array, *, blocks: Optional[BlockChoice] = None,
                out_dtype=None, interpret: Optional[bool] = None,
                hardware: Optional[HardwareEntry] = None) -> jax.Array:
    """(E, cap, d) x (E, d, f) -> (E, cap, f) capacity-padded expert GEMM —
    the same derived schedule with the expert axis as one more lift."""
    e, cap, d = x.shape
    e2, d2, f = w.shape
    if e != e2 or d != d2:
        raise ValueError(f"expert gemm mismatch {x.shape} x {w.shape}")
    hw, interp = _resolve(hardware, interpret)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    fn = _expr_callable(E.expert_gemm_expr(e, cap, d, f),
                        str(jnp.dtype(x.dtype)), str(out_dtype),
                        hw.name, interp, blocks)
    return fn(x, w)


def hadamard(a: jax.Array, b: jax.Array, *, block: tuple[int, int] = (256, 256),
             interpret: Optional[bool] = None,
             hardware: Optional[HardwareEntry] = None) -> jax.Array:
    if a.shape != b.shape:
        raise ValueError(f"hadamard shape mismatch {a.shape} vs {b.shape}")
    m, n = a.shape
    block = (min(block[0], max(m, 8)), min(block[1], max(n, 128)))
    hw, interp = _resolve(hardware, interpret)
    fn = _expr_callable(E.hadamard_expr(m, n), str(jnp.dtype(a.dtype)),
                        str(jnp.dtype(a.dtype)), hw.name, interp, block)
    return fn(a, b)


def semiring_matmul(a: jax.Array, b: jax.Array, *, plus: str, times: str,
                    interpret: Optional[bool] = None,
                    hardware: Optional[HardwareEntry] = None,
                    blocks=None) -> jax.Array:
    """Matmul over any registered semiring, e.g. ``plus="min", times="add"``
    (tropical shortest path) — the same derived schedule as ``moa_gemm``;
    only the emitted block body changes."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} . {b.shape}")
    hw, interp = _resolve(hardware, interpret)
    expr = E.inner(plus, times, E.arr("A", (m, k)), E.arr("B", (k, n)))
    if hw.backend == "pallas" or interpret:
        fn = _expr_callable(expr, str(jnp.dtype(a.dtype)), "float32",
                            hw.name, interp, blocks)
        return fn(a, b)
    return ref.eval_expr(expr, a, b)


# ---------------------------------------------------------------------------
# unified model-facing entries: derived schedules on Pallas backends, the
# identical-semantics XLA oracle elsewhere.  These are what the models,
# collectives and benchmarks call — the single execution path.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _pallas_matmul_f32(x2, w2, hw_name, interpret, transpose_b):
    m, k = x2.shape
    n = w2.shape[0] if transpose_b else w2.shape[1]
    fn = _expr_callable(E.matmul_expr(m, k, n, transpose_b=transpose_b),
                        str(jnp.dtype(x2.dtype)), "float32", hw_name,
                        interpret)
    return fn(x2, w2)


def _gemm_tb(a, b, out_dtype_s, hw_name, interpret):
    """a (m, k) @ b (n, k).T via the transposed-second-operand schedule."""
    fn = _expr_callable(E.matmul_expr(a.shape[0], a.shape[1], b.shape[0],
                                      transpose_b=True),
                        str(jnp.dtype(a.dtype)), out_dtype_s, hw_name,
                        bool(interpret))
    return fn(a, b)


def _gemm_ta(a, b, out_dtype_s, hw_name, interpret):
    """a (t, m).T @ b (t, n) — the transposed-FIRST-operand schedule (both
    VJP weight gradients have this shape), again with no relayout copy."""
    t, m = a.shape
    t2, n = b.shape
    expr = E.inner("add", "mul", E.transpose(E.arr("A", (t, m))),
                   E.arr("B", (t2, n)))
    fn = _expr_callable(expr, str(jnp.dtype(a.dtype)), out_dtype_s, hw_name,
                        bool(interpret))
    return fn(a, b)


def _pallas_matmul_fwd(x2, w2, hw_name, interpret, transpose_b):
    return _pallas_matmul_f32(x2, w2, hw_name, interpret, transpose_b), (x2, w2)


def _pallas_matmul_bwd(hw_name, interpret, transpose_b, resid, g):
    """Both gradients are two more derived GEMMs, every transposed operand
    read through its gamma coefficients — no transpose copy of either the
    weight or the (often vocab-sized) logits gradient."""
    x2, w2 = resid
    hw = get_entry(hw_name)
    if transpose_b:
        # y = x w^T: dx = g @ w (stored layout); dw = g^T @ x
        dx = moa_gemm(g, w2, out_dtype=x2.dtype, interpret=interpret,
                      hardware=hw)
        dw = _gemm_ta(g, x2, str(w2.dtype), hw_name, interpret)
    else:
        # dx = g @ w^T; dw = x^T @ g
        dx = _gemm_tb(g, w2, str(x2.dtype), hw_name, interpret)
        dw = _gemm_ta(x2, g, str(w2.dtype), hw_name, interpret)
    return dx, dw


_pallas_matmul_f32.defvjp(_pallas_matmul_fwd, _pallas_matmul_bwd)


def _xla_matmul_f32(x2: jax.Array, w2: jax.Array,
                    transpose_b: bool) -> jax.Array:
    """The XLA oracle body with the kernels' f32-accumulation contract."""
    if transpose_b:
        return jax.lax.dot_general(x2, w2, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return jnp.dot(x2, w2, preferred_element_type=jnp.float32)


def _matmul_sharded(x2, w2, transpose_b, hw, interp, use_kernel, mesh,
                    shard, replicate_out):
    """The mesh path of ``matmul``: derive the DistributedPlan for the 2-D
    GEMM and run the (differentiable) single-device body per shard."""
    from repro.distributed.plan import MATMUL_ROLES, _translate

    m, kdim = x2.shape
    n = w2.shape[0] if transpose_b else w2.shape[1]
    if shard is None:                      # rows over the first mesh axis,
        names = tuple(mesh.axis_names)     # columns over the second
        shard = {"m": names[0]}
        if len(names) > 1:
            shard["n"] = names[1]
    nf = E.normal_form(E.matmul_expr(m, kdim, n, transpose_b=transpose_b),
                       name="matmul")
    if use_kernel:
        local = lambda a, b: _pallas_matmul_f32(a, b, hw.name, bool(interp),
                                                transpose_b)
        tag = "matmul_vjp"
    else:
        local = lambda a, b: _xla_matmul_f32(a, b, transpose_b)
        tag = "matmul_xla"
    fn = _sharded_callable(nf, str(jnp.dtype(x2.dtype)), "float32", hw.name,
                           bool(interp), use_kernel, mesh,
                           _translate(shard, MATMUL_ROLES), replicate_out,
                           local_fn=local, local_tag=tag)
    return fn(x2, w2)


# ---------------------------------------------------------------------------
# layer-indexed GEMMs: a decode body hands the stacked weight leaf and a
# layer index; the kernel reads that layer in place through its runtime slab
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerSlab:
    """Layer ``layer`` (an int32 scalar, may be traced) of the stacked
    weight ``stacked`` (layers leading), not sliced out: ``matmul`` reads
    it in place through ``layer_matmul``.  ``k_minor`` records that the
    device stores each layer's contraction dim minor (see ``layer_slab``).
    ``shape`` and ``reshape`` act on the one layer's view, as they would
    on the sliced leaf."""
    stacked: jax.Array
    layer: jax.Array
    k_minor: bool = False

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.stacked.shape[1:])

    def reshape(self, *shape: int) -> "LayerSlab":
        out = self.stacked.reshape((self.stacked.shape[0],) + shape)
        if self.k_minor and out.shape[1] != self.stacked.shape[1]:
            raise ValueError("a k-minor layer slab may merge its output "
                             "dims only, not its contraction dim")
        return LayerSlab(out, self.layer, self.k_minor)

    def sliced(self) -> jax.Array:
        """The layer as its own array (a copy on the device)."""
        return jax.lax.dynamic_index_in_dim(self.stacked, self.layer,
                                            keepdims=False)


@functools.lru_cache(maxsize=1024)
def _default_order(device, dtype_s: str, shape: tuple[int, ...]
                   ) -> tuple[int, ...]:
    from jax.experimental.layout import Layout
    return tuple(Layout.from_pjrt_layout(device.client.get_default_layout(
        jnp.dtype(dtype_s), shape, device)).major_to_minor)


def stored_k_minor(shape: tuple[int, ...], dtype, lead: int = 1) -> bool:
    """Whether the target device's default layout of a stacked weight
    ``(*lead dims, k, *n dims)`` keeps ``k`` minor, the rest in order: the
    stored buffer is then each layer's transpose, and a kernel reads it in
    place only as ``(n, k)``.  A TPU does this where ``n``'s last dim pads
    its 128-lane tile and ``k`` does not (Mamba's in-projections, the
    attention q/k/v of 64-wide heads).  The device is JAX's default device
    (``jax.default_device``), else the first one."""
    if len(shape) - lead < 2:
        return False
    dev = jax.config.jax_default_device
    if not isinstance(dev, jax.Device):
        dev = jax.devices(dev)[0] if isinstance(dev, str) else jax.devices()[0]
    order = _default_order(dev, str(jnp.dtype(dtype)), tuple(shape))
    return order == (tuple(range(lead)) + tuple(range(lead + 1, len(shape)))
                     + (lead,))


def layer_slab(stacked: jax.Array, layer, lead: int = 1) -> LayerSlab:
    """Layer ``layer`` of the stacked weight leaf ``stacked``, whose first
    ``lead`` dims index layers (flattened row-major into one layer axis,
    a free reshape), to be read in place by ``matmul``."""
    k_minor = stored_k_minor(stacked.shape, stacked.dtype, lead)
    return LayerSlab(stacked.reshape((-1,) + stacked.shape[lead:]),
                     jnp.asarray(layer, jnp.int32), k_minor)


_gemm_reads = threading.local()


@contextlib.contextmanager
def count_gemm_reads():
    """Count, while a function traces, the model GEMMs that read a stacked
    weight in place (``layer_matmul``) and those handed a whole weight
    (``matmul``): yields ``{"in_place": n, "whole": m}``, filled as the
    trace runs.  A GEMM traced inside ``gemm_repeats(n)`` counts ``n``
    times (a scan body runs once a layer)."""
    prev = getattr(_gemm_reads, "counts", None), getattr(_gemm_reads,
                                                           "times", 1)
    counts = {"in_place": 0, "whole": 0}
    _gemm_reads.counts, _gemm_reads.times = counts, 1
    try:
        yield counts
    finally:
        _gemm_reads.counts, _gemm_reads.times = prev


@contextlib.contextmanager
def gemm_repeats(n: int):
    """GEMMs traced inside run ``n`` times each (the body of a scan over
    ``n`` layers) for ``count_gemm_reads``."""
    times = getattr(_gemm_reads, "times", 1)
    _gemm_reads.times = times * int(n)
    try:
        yield
    finally:
        _gemm_reads.times = times


def _count_gemm(kind: str) -> None:
    counts = getattr(_gemm_reads, "counts", None)
    if counts is not None:
        counts[kind] += _gemm_reads.times


def _layer_gemm_callable(m: int, k: int, n: int, k_minor: bool,
                         dtype_s: str, hw_name: str, interpret: bool):
    """The memoized kernel of ``layer_matmul``: the (m, k, n) GEMM whose
    weight leaf "B" is read in place from a stacked leaf, through its
    transpose where the device stores it k-minor."""
    key = ("layer_gemm", m, k, n, k_minor, dtype_s, hw_name, interpret)
    fn = _cache_get(key)
    if fn is not None:
        return fn
    bundle = _sched.get_schedule(E.matmul_expr(m, k, n, transpose_b=k_minor),
                                 dtype=dtype_s, hardware=get_entry(hw_name),
                                 in_place=("B",))
    return _cache_put(key, jax.jit(emit_bundle(
        bundle, out_dtype="float32", interpret=interpret)))


def layer_matmul(x: jax.Array, w: jax.Array, layer, *, out_dtype=None,
                 k_minor: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 hardware: Optional[HardwareEntry] = None) -> jax.Array:
    """``x[..., k] @ w[layer][k, ...]`` without slicing the layer out.

    ``w`` is a whole stacked leaf ``(L, k, ...)`` and ``layer`` an int32
    scalar that may be traced (a scan's layer index).  On a Pallas backend
    the derived GEMM takes ``layer`` as a scalar-prefetch operand, and the
    weight's BlockSpec index map returns ``(layer, kk, j)``: each layer is
    read in place, never copied or padded (the contraction block divides
    ``k``; a ragged output edge is the kernel's masked edge block, sliced
    off the result).  Only the activation pads, in M.  Where the device
    stores the leaf k-minor (``stored_k_minor``, which ``k_minor=None``
    asks) the kernel reads the same buffer as ``(L, n, k)`` through the
    transposed-operand schedule.  Elsewhere it is
    ``dot(x, dynamic_index(w, layer))`` under ``matmul``'s f32
    accumulation contract.  Not differentiable: decode bodies call it,
    training and prefill keep ``matmul`` on sliced weights."""
    kdim = x.shape[-1]
    if w.shape[1] != kdim:
        raise ValueError(f"layer_matmul contraction mismatch {x.shape} @ "
                         f"{w.shape}[layer]")
    _count_gemm("in_place")
    hw, interp = _resolve(hardware, interpret)
    out_tail = w.shape[2:]
    w3 = w.reshape(w.shape[0], kdim, -1)
    x2 = x.reshape(-1, kdim)
    layer = jnp.asarray(layer, jnp.int32)
    if hw.backend == "pallas" or bool(interpret):
        if k_minor is None:
            k_minor = stored_k_minor(w.shape, w.dtype)
        fn = _layer_gemm_callable(x2.shape[0], kdim, w3.shape[2], k_minor,
                                  str(jnp.dtype(x2.dtype)), hw.name,
                                  bool(interp))
        y = fn(layer, x2, jnp.swapaxes(w3, 1, 2) if k_minor else w3)
    else:
        y = _xla_matmul_f32(x2, jax.lax.dynamic_index_in_dim(
            w3, layer, keepdims=False), False)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    return y.astype(out_dtype).reshape(x.shape[:-1] + out_tail)


def _ambient_mesh():
    """The multi-device mesh of an enclosing ``with mesh:`` block, outside
    any ``shard_map`` body.  The SPMD partitioner cannot split a compiled
    Mosaic kernel, so under such a mesh the kernel GEMM runs per shard
    through its derived plan instead."""
    from repro.distributed.sharding import _current_mesh
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    mesh = _current_mesh()
    return mesh if mesh is not None and mesh.size > 1 else None


def matmul(x: jax.Array, w: jax.Array, *, transpose_b: bool = False,
           out_dtype=None, interpret: Optional[bool] = None,
           hardware: Optional[HardwareEntry] = None,
           mesh=None, shard: Optional[dict] = None,
           replicate_out: bool = False) -> jax.Array:
    """Unified MoA matmul: ``y[..., :] = x[..., k] @ w[k, ...]``.

    Leading dims of ``x`` and trailing dims of ``w`` collapse to the 2-D MoA
    GEMM (one gamma re-layout each way).  On a Pallas backend this executes
    the derived schedule (differentiable: the VJP is two more derived GEMMs);
    elsewhere it is the XLA oracle with the same f32-accumulation contract,
    so CPU tests and TPU serving share semantics.

    ``transpose_b`` contracts against the *stored* layout of a ``(..., k)``
    weight: ``y[..., :] = x[..., k] @ w[..., k].T``.  The derived schedule
    reads the table through column-gamma coefficients — no transpose copy —
    which is what lets the tied-embeddings logits head share this entry.

    ``mesh``/``shard``/``replicate_out`` lift the GEMM one level further to
    named device axes (roles ``{"m", "n", "k"}``; sharding "k" derives the
    tensor-parallel psum) and run the same body per shard through the
    derived ``DistributedPlan`` — see ``repro.distributed.plan``.  A
    compiled kernel called inside a multi-device ``with mesh:`` block
    takes that path on the enclosing mesh (rows over its first axis,
    columns over its second).

    A ``LayerSlab`` weight is read in place through ``layer_matmul``; only
    the mesh and transposed paths, which that entry lacks, slice the layer
    out first.
    """
    if isinstance(w, LayerSlab):
        hw, interp = _resolve(hardware, interpret)
        if (mesh is None and not transpose_b
                and not (hw.backend == "pallas" and not interp
                         and _ambient_mesh() is not None)):
            return layer_matmul(x, w.stacked, w.layer, out_dtype=out_dtype,
                                k_minor=w.k_minor, interpret=interpret,
                                hardware=hardware)
        w = w.sliced()
    _count_gemm("whole")
    kdim = x.shape[-1]
    if transpose_b:
        if w.shape[-1] != kdim:
            raise ValueError(
                f"matmul(transpose_b) contraction mismatch {x.shape} @ "
                f"{w.shape}.T")
        w2 = w.reshape(-1, kdim)
        out_tail = w.shape[:-1]
    else:
        if w.shape[0] != kdim:
            raise ValueError(f"matmul contraction mismatch {x.shape} @ {w.shape}")
        w2 = w.reshape(kdim, -1)
        out_tail = w.shape[1:]
    hw, interp = _resolve(hardware, interpret)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    x2 = x.reshape(-1, kdim)
    use_kernel = hw.backend == "pallas" or bool(interpret)
    if mesh is None and use_kernel and not interp:
        mesh = _ambient_mesh()
    if mesh is not None:
        y = _matmul_sharded(x2, w2, transpose_b, hw, interp, use_kernel,
                            mesh, shard, replicate_out)
    elif use_kernel:
        y = _pallas_matmul_f32(x2, w2, hw.name, bool(interp), transpose_b)
    else:
        y = _xla_matmul_f32(x2, w2, transpose_b)
    return y.astype(out_dtype).reshape(x.shape[:-1] + out_tail)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _pallas_expert_f32(x, w, hw_name, interpret):
    return expert_gemm(x, w, out_dtype=jnp.float32, interpret=interpret,
                       hardware=get_entry(hw_name))


def _pallas_expert_fwd(x, w, hw_name, interpret):
    return _pallas_expert_f32(x, w, hw_name, interpret), (x, w)


def _pallas_expert_bwd(hw_name, interpret, resid, g):
    x, w = resid
    hw = get_entry(hw_name)
    dx = expert_gemm(g, jnp.swapaxes(w, 1, 2), out_dtype=x.dtype,
                     interpret=interpret, hardware=hw)
    dw = expert_gemm(jnp.swapaxes(x, 1, 2), g, out_dtype=w.dtype,
                     interpret=interpret, hardware=hw)
    return dx, dw


_pallas_expert_f32.defvjp(_pallas_expert_fwd, _pallas_expert_bwd)


def expert_matmul(x: jax.Array, w: jax.Array, *, out_dtype=None,
                  interpret: Optional[bool] = None,
                  hardware: Optional[HardwareEntry] = None,
                  mesh=None, shard: Optional[dict] = None,
                  replicate_out: bool = False) -> jax.Array:
    """Unified batched expert contraction ``ecd,edf->ecf`` — the MoE dispatch
    hot path, through the derived expert schedule on Pallas backends.

    ``mesh``/``shard`` lift it across device axes (roles ``{"e", "m", "n",
    "k"}``; sharding "e" is expert parallelism) via a DistributedPlan."""
    hw, interp = _resolve(hardware, interpret)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    use_kernel = hw.backend == "pallas" or bool(interpret)
    if mesh is not None:
        from repro.distributed.plan import EXPERT_ROLES, _translate
        e, cap, d = x.shape
        f = w.shape[2]
        if shard is None:
            shard = {"e": tuple(mesh.axis_names)[0]}
        nf = E.normal_form(E.expert_gemm_expr(e, cap, d, f),
                           name="expert_gemm")
        if use_kernel:
            local = lambda a, b: _pallas_expert_f32(a, b, hw.name,
                                                    bool(interp))
            tag = "expert_vjp"
        else:
            local = lambda a, b: jnp.einsum(
                "ecd,edf->ecf", a, b, preferred_element_type=jnp.float32)
            tag = "expert_xla"
        fn = _sharded_callable(nf, str(jnp.dtype(x.dtype)), "float32",
                               hw.name, bool(interp), use_kernel, mesh,
                               _translate(shard, EXPERT_ROLES),
                               replicate_out, local_fn=local, local_tag=tag)
        y = fn(x, w)
    elif use_kernel:
        y = _pallas_expert_f32(x, w, hw.name, bool(interp))
    else:
        y = jnp.einsum("ecd,edf->ecf", x, w,
                       preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def head_matmul(x: jax.Array, w: jax.Array, *, transpose_b: bool = False,
                out_dtype=None, interpret: Optional[bool] = None,
                hardware: Optional[HardwareEntry] = None) -> jax.Array:
    """Per-head contraction ``bshk,khn->bshn`` (``bshk,nhk->bshn`` with
    ``transpose_b``) — the MLA-decode absorbed projections.

    The head axis batches the GEMM (one more dimension lift, like the
    expert axis), and the head-middle weight is read in its stored layout
    through derived strided coefficients — the per-step transpose copy of
    the ``(kv_rank, heads, dim)`` projection tables (and the einsum
    fallback for the output projection) are gone."""
    b, s, h, kdim = x.shape
    if transpose_b:
        n, h2, k2 = w.shape
    else:
        k2, h2, n = w.shape
    if h2 != h or k2 != kdim:
        raise ValueError(f"head_matmul mismatch {x.shape} . {w.shape}"
                         f"{'.T' if transpose_b else ''}")
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    expr = E.head_gemm_expr(h, b * s, kdim, n, transpose_b=transpose_b)
    y = apply(expr, x.reshape(b * s, h, kdim), w, out_dtype=jnp.float32,
              interpret=interpret, hardware=hardware)        # (h, b*s, n)
    return y.transpose(1, 0, 2).reshape(b, s, h, n).astype(out_dtype)


# ---------------------------------------------------------------------------
# attention: the derived streaming schedule behind an ops-level wrapper
# ---------------------------------------------------------------------------

def _oracle_attention(q, k, v, scale, causal, window=0, prefix_len=0):
    """The jnp online-softmax oracle on the grouped model layout (also the
    recompute body of the kernel path's backward pass)."""
    from repro.models.chunked_attention import chunked_attention
    return chunked_attention(q, k, v, scale=scale, causal=causal,
                             window=window, prefix_len=prefix_len)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_grouped(q, k, v, scale, causal, window, prefix_len, hw_name,
                   interpret, blocks):
    """Forward: the derived streaming Pallas kernel over the grouped layout
    ``q (B, Sq, KV, G, hd); k/v (B, Sk, KV, hd)`` -> ``(B, Sq, KV*G, hd)``.
    The schedule was derived on exactly these *stored* layouts (the logical
    grouped views are transposed leaves, pure index rewrites), so operands
    feed the kernel with no relayout copy; padding to the derived blocks and
    the slice back happen inside the cached executor
    (``kernels.flash_attention``).  ``window``/``prefix_len`` ride the
    recurrent form as streamed-axis masking metadata — the kernel derives
    its block-skip from them instead of falling back to the jnp path."""
    from repro.kernels import flash_attention as fa
    b, sq, kv, g, hd = q.shape
    sk, vd = k.shape[1], v.shape[-1]
    fn = fa._executor(b, kv, g, sq, sk, hd, vd, str(jnp.dtype(q.dtype)),
                      str(jnp.dtype(q.dtype)), hw_name, interpret, causal,
                      scale, blocks, window, prefix_len)
    out = fn(q, k, v)                               # (b, kv, g, sq, vd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, kv * g, vd)


def _flash_grouped_fwd(q, k, v, scale, causal, window, prefix_len, hw_name,
                       interpret, blocks):
    """Forward rule under differentiation: the ``flash_attention_stats``
    derivation — the same schedule as the primal (identical output, bit for
    bit) but with the carried online-softmax ``(m, l)`` statistics exported
    as extra state outputs.  Residuals are ``(q, k, v, out, m, l)``: the
    flash-backward recurrences reconstruct the probabilities from the saved
    statistics, so no jnp oracle recompute appears in the backward jaxpr."""
    from repro.kernels import flash_attention as fa
    b, sq, kv, g, hd = q.shape
    sk, vd = k.shape[1], v.shape[-1]
    fn = fa._stats_executor(b, kv, g, sq, sk, hd, vd, str(jnp.dtype(q.dtype)),
                            str(jnp.dtype(q.dtype)), hw_name, interpret,
                            causal, scale, blocks, window, prefix_len)
    out5, m, l = fn(q, k, v)                        # out (b, kv, g, sq, vd)
    out = out5.transpose(0, 3, 1, 2, 4).reshape(b, sq, kv * g, vd)
    return out, (q, k, v, out5, m, l)


def _flash_grouped_bwd(scale, causal, window, prefix_len, hw_name, interpret,
                       blocks, resid, g_out):
    """Derived flash backward: two recurrence kinds from the same lifted
    pipeline as the forward.  ``flash_dq`` streams key blocks with a carried
    dq accumulator; ``flash_dkv`` is the transposed weld — key rows, query
    stream — carrying dk with an exported dv state.  Both reuse the saved
    ``(m, l)`` row statistics; ``delta = rowsum(dO * O)`` is the one jnp
    reduction (a residual contraction, not a recompute).  Blocks are read
    from the forward's cached derivation so the padded row axes line up."""
    from repro.kernels import flash_attention as fa
    q, k, v, out5, m, l = resid
    b, sq, kv, g, hd = q.shape
    sk, vd = k.shape[1], v.shape[-1]
    dtype_s = str(jnp.dtype(q.dtype))
    do = g_out.reshape(b, sq, kv, g, vd)            # stored dO layout
    do5 = do.transpose(0, 2, 3, 1, 4)               # (b, kv, g, sq, vd)
    delta = jnp.sum(do5.astype(jnp.float32) * out5.astype(jnp.float32),
                    axis=-1)                        # (b, kv, g, sq)
    fwd_blocks = fa.attention_bundle(
        b, kv, g, sq, sk, hd, vd, dtype=dtype_s,
        hardware=get_entry(hw_name), blocks=blocks, window=window,
        prefix_len=prefix_len).blocks
    bq, bk = fwd_blocks.as_tuple()
    # pass StreamBlockChoice objects, not tuples: the forward's solved
    # blocks may exceed the logical extents (tiny sequences), and the
    # saved (m, l) ride the *forward's* padded row axis — the tuple path
    # would clamp and disagree with the residual padding
    from repro.core.blocking import StreamBlockChoice
    dkv_blocks = StreamBlockChoice(bk, bq, 0, 0.0, 1.0)
    dq_fn = fa._dq_executor(b, kv, g, sq, sk, hd, vd, dtype_s, hw_name,
                            interpret, causal, scale, fwd_blocks, window,
                            prefix_len)
    dq5 = dq_fn(q, k, k, do, v, m, l, delta)        # (b, kv, g, sq, hd)
    dkv_fn = fa._dkv_executor(b, kv, g, sq, sk, hd, vd, dtype_s, hw_name,
                              interpret, causal, scale, dkv_blocks, window,
                              prefix_len)
    dk5, dv5 = dkv_fn(k, q, q, do, v, m, l, delta)  # dk (b,kv,g,sk,hd)
    dq = dq5.transpose(0, 3, 1, 2, 4).astype(q.dtype)
    # per-group dk/dv; the GQA reduction over g is a residual sum (K/V's
    # zero group coefficient in the forward becomes a sum in the cotangent)
    dk = dk5.sum(axis=2).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv5[:, :, :, :sk].sum(axis=2).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


_flash_grouped.defvjp(_flash_grouped_fwd, _flash_grouped_bwd)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
              causal: bool = True, window: int = 0, prefix_len: int = 0,
              interpret: Optional[bool] = None,
              hardware: Optional[HardwareEntry] = None,
              blocks: Optional[tuple[int, int]] = None) -> jax.Array:
    """Unified grouped-query attention — the model-facing entry.

    ``q: (B, Sq, KV, G, hd)`` (GQA grouping, K/V heads never repeated);
    ``k/v: (B, Sk, KV, hd)``.  Returns ``(B, Sq, KV*G, hd)``.

    On a Pallas backend (or under ``interpret=True``) this runs the flash
    kernel from the *derived* streaming schedule, with the ops-level
    pad/slice contract: any sequence length works — operands are padded to
    the solver's ``(bq, bk)`` multiples, padded keys are masked inert by
    the kernel's ``kpos < sk`` guard, and the logical result is sliced
    back.  Differentiable with a fully *derived* VJP: the forward saves
    the (m, l) statistics (``attention_stats_form``) and the backward runs
    the ``flash_dq``/``flash_dkv`` recurrence kinds — no oracle recompute
    appears in a train step's jaxpr.  On "xla" entries the jnp oracle is
    the forward path (and differentiates through itself), so semantics
    are identical everywhere.

    ``window``/``prefix_len`` (causal only — the honor-or-raise contract of
    ``_chunk_mask``) derive windowed / prefix-LM schedules: the masking
    metadata rides the recurrent form, so the kernel block-skips from it
    instead of dispatching those modes to the jnp path.
    """
    hw, interp = _resolve(hardware, interpret)
    # kernel on compiled-Pallas entries, on "interpret" entries (the CPU
    # validation path — this is what attn_impl="pallas" means off-TPU), or
    # by explicit request; "xla" entries use the jnp oracle.
    use_kernel = _use_kernel(hw, interp, interpret)
    if use_kernel:
        return _flash_grouped(q, k, v, float(scale), bool(causal),
                              int(window), int(prefix_len), hw.name,
                              bool(interp), blocks)
    return _oracle_attention(q, k, v, scale, causal, window,
                             prefix_len).astype(q.dtype)


# ---------------------------------------------------------------------------
# carried-state recurrences: the SSD chunked scan and the RG-LRU gated scan
# through the same derived-schedule pipeline (expr.RecurrentForm ->
# derive_recurrent_schedule -> emit_recurrent), with the ops-level contract:
# pad/reshape the sequence into the derived chunks (padded tokens are the
# monoid's identity step), differentiable via derived backward kernels (the
# ssd_backward / gated_backward recurrence kinds — the jnp oracles survive
# only as bit-identity references), "xla" entries dispatch to the oracle
# directly.
# ---------------------------------------------------------------------------

def default_ssd_chunk(s: int, h: int, p: int, n: int, dtype="float32",
                      hardware: Optional[HardwareEntry] = None) -> int:
    """The derived SSD chunk length: ``solve_recurrence_blocks`` with the
    carried (h, p, n) state, the double-buffered per-token operands and the
    quadratic segsum intermediates (scores + the per-head decay mask L) in
    the VMEM working-set model — replacing the old hand-written
    ``models.ssm.default_ssd_chunk`` doubling heuristic."""
    from repro.core.blocking import solve_recurrence_blocks
    hw = hardware or current_hardware()
    choice = solve_recurrence_blocks(
        s,
        token_elems=2 * n + h * (p + 1) + h * p,     # B, C, x, dA in + y out
        state_elems=2 * h * p * n,                   # carried h + H0 operand
        quad_elems=1 + h,                            # scores G + decay L
        lin_elems=4 * h,                             # cumsum/decay vectors
        dtype=dtype, hardware=getattr(hw, "shape", hw))
    return choice.bs


def default_gated_chunk(s: int, w: int, dtype="float32",
                        hardware: Optional[HardwareEntry] = None) -> int:
    """The derived RG-LRU chunk length: per-channel state, three per-token
    streams (gate log, input, output), linear scan intermediates."""
    from repro.core.blocking import solve_recurrence_blocks
    hw = hardware or current_hardware()
    choice = solve_recurrence_blocks(
        s, token_elems=3 * w, state_elems=2 * w, quad_elems=0,
        lin_elems=2 * w, dtype=dtype, hardware=getattr(hw, "shape", hw))
    return choice.bs


@functools.lru_cache(maxsize=128)
def _ssd_executor(b, nc, q, h, p, n, dtype_s, hw_name, interpret):
    """Jitted executable for one chunked SSD shape: the cached derivation
    of ``expr.ssd_form`` through ``emit_recurrent``.  Binds the chunked
    storage views (pure reshapes of the stored model buffers) in schedule
    operand order (C, B, X, dA, H0); returns ``(y, final_state)``."""
    from repro.kernels.emit import emit_recurrent_bundle
    form = E.ssd_form(b, nc, q, h, p, n)
    bundle = _sched.get_schedule(form, dtype=dtype_s,
                                 hardware=get_entry(hw_name), blocks=(q,))
    return jax.jit(emit_recurrent_bundle(bundle, out_dtype="float32",
                                         interpret=interpret))


def _ssd_oracle(xdt, dA, B, C, h0, chunk, unroll=False):
    """The chunked-jnp oracle with the ops-level pad/slice contract (padded
    tokens are inert: zero ``xdt`` adds nothing, zero ``dA`` decays by 1)."""
    s = xdt.shape[1]
    pad = (-s) % chunk
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y, final = ref.ssd_scan_ref(xdt, dA, B, C, h0, chunk=chunk,
                                unroll=unroll)
    return y[:, :s], final


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd_kernel(xdt, dA, B, C, h0, chunk, hw_name, interpret):
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    sp = s + pad
    nc = sp // chunk
    xp = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else xdt
    dp = jnp.pad(dA, ((0, 0), (0, pad), (0, 0))) if pad else dA
    Bp = jnp.pad(B, ((0, 0), (0, pad), (0, 0))) if pad else B
    Cp = jnp.pad(C, ((0, 0), (0, pad), (0, 0))) if pad else C
    fn = _ssd_executor(b, nc, chunk, h, p, n, str(jnp.dtype(xdt.dtype)),
                       hw_name, interpret)
    y, final = fn(Cp.reshape(b, nc, chunk, n), Bp.reshape(b, nc, chunk, n),
                  xp.reshape(b, nc, chunk, h, p),
                  dp.reshape(b, nc, chunk, h), h0)
    return y.reshape(b, sp, h, p)[:, :s], final


@functools.lru_cache(maxsize=128)
def _ssd_chk_executor(b, nc, q, h, p, n, dtype_s, hw_name, interpret):
    """Forward executor under differentiation: the same ``ssd`` monoid with
    the per-chunk *entering* states additionally exported (``h_in (b, nc,
    h, p, n)``) — the O(S/chunk) checkpoints the backward scan replays
    from.  Returns ``(y, final_state, h_in)``."""
    from repro.kernels.emit import emit_recurrent_bundle
    form = E.ssd_chk_form(b, nc, q, h, p, n)
    bundle = _sched.get_schedule(form, dtype=dtype_s,
                                 hardware=get_entry(hw_name), blocks=(q,))
    return jax.jit(emit_recurrent_bundle(bundle, out_dtype="float32",
                                         interpret=interpret))


@functools.lru_cache(maxsize=128)
def _ssd_bwd_executor(b, nc, q, h, p, n, dtype_s, hw_name, interpret):
    """The ``ssd_backward`` recurrence: streams chunks in *reverse* (the
    caller flips the chunk axis) carrying the state cotangent dh, replays
    each chunk's forward factoring from the saved entering state, and emits
    the full cotangent chain per chunk.  Operand order
    ``(C, B, dY, X, dA, Hin, dHf)``; returns ``(dX, dh0, dB, dC, ddA)``."""
    from repro.kernels.emit import emit_recurrent_bundle
    form = E.ssd_bwd_form(b, nc, q, h, p, n)
    bundle = _sched.get_schedule(form, dtype=dtype_s,
                                 hardware=get_entry(hw_name), blocks=(q,))
    return jax.jit(emit_recurrent_bundle(bundle, out_dtype="float32",
                                         interpret=interpret))


def _ssd_kernel_fwd(xdt, dA, B, C, h0, chunk, hw_name, interpret):
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    sp = s + pad
    nc = sp // chunk
    xp = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else xdt
    dp = jnp.pad(dA, ((0, 0), (0, pad), (0, 0))) if pad else dA
    Bp = jnp.pad(B, ((0, 0), (0, pad), (0, 0))) if pad else B
    Cp = jnp.pad(C, ((0, 0), (0, pad), (0, 0))) if pad else C
    fn = _ssd_chk_executor(b, nc, chunk, h, p, n, str(jnp.dtype(xdt.dtype)),
                           hw_name, interpret)
    y, final, hin = fn(Cp.reshape(b, nc, chunk, n),
                       Bp.reshape(b, nc, chunk, n),
                       xp.reshape(b, nc, chunk, h, p),
                       dp.reshape(b, nc, chunk, h), h0)
    return (y.reshape(b, sp, h, p)[:, :s], final), (xdt, dA, B, C, hin)


def _ssd_kernel_bwd(chunk, hw_name, interpret, resid, g):
    """Derived scan backward: the ``ssd_backward`` recurrence streamed over
    *time-reversed* chunks, seeded with the final-state cotangent.  Each
    step replays the chunk's forward factoring from the saved entering
    state ``h_in`` (same O(chunk) live intermediates as the old oracle
    recompute, but as a derived kernel) and chains the cotangents; the
    carried dh after the last (earliest) chunk is dh0."""
    xdt, dA, B, C, hin = resid
    gy, gfinal = g
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    sp = s + pad
    nc = sp // chunk
    xp = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else xdt
    dp = jnp.pad(dA, ((0, 0), (0, pad), (0, 0))) if pad else dA
    Bp = jnp.pad(B, ((0, 0), (0, pad), (0, 0))) if pad else B
    Cp = jnp.pad(C, ((0, 0), (0, pad), (0, 0))) if pad else C
    gyp = jnp.pad(gy, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else gy

    def rev(a):
        return jnp.flip(a, axis=1)

    fn = _ssd_bwd_executor(b, nc, chunk, h, p, n, str(jnp.dtype(xdt.dtype)),
                           hw_name, interpret)
    dX, dh0, dB, dC, ddA = fn(rev(Cp.reshape(b, nc, chunk, n)),
                              rev(Bp.reshape(b, nc, chunk, n)),
                              rev(gyp.reshape(b, nc, chunk, h, p)),
                              rev(xp.reshape(b, nc, chunk, h, p)),
                              rev(dp.reshape(b, nc, chunk, h)),
                              rev(hin), gfinal)
    dxdt = rev(dX).reshape(b, sp, h, p)[:, :s].astype(xdt.dtype)
    dBv = rev(dB).reshape(b, sp, n)[:, :s].astype(B.dtype)
    dCv = rev(dC).reshape(b, sp, n)[:, :s].astype(C.dtype)
    ddAv = rev(ddA).reshape(b, sp, h)[:, :s].astype(dA.dtype)
    return dxdt, ddAv, dBv, dCv, dh0


_ssd_kernel.defvjp(_ssd_kernel_fwd, _ssd_kernel_bwd)


def scan_ssd(xdt: jax.Array, dA: jax.Array, B: jax.Array, C: jax.Array, *,
             init_state: Optional[jax.Array] = None,
             chunk: Optional[int] = None, unroll: bool = False,
             interpret: Optional[bool] = None,
             hardware: Optional[HardwareEntry] = None
             ) -> tuple[jax.Array, jax.Array]:
    """Unified Mamba-2 SSD chunked scan — the model-facing entry.

    ``xdt (B, S, H, P)`` the dt-folded input, ``dA (B, S, H)`` the log
    decay, ``B/C (B, S, N)`` the state projections.  Returns ``(y (B, S,
    H, P) f32, final state (B, H, P, N) f32)``.

    On a Pallas backend (or under ``interpret=True``) this runs the kernel
    from the *derived* recurrent schedule (``expr.ssd_form`` — the chunk
    from ``solve_recurrence_blocks`` unless pinned), with the ops-level
    pad/slice contract: any sequence length works, padded tokens are the
    monoid's identity step.  Differentiable with a fully *derived* VJP:
    the forward checkpoints the per-chunk entering states
    (``ssd_chk_form``) and the backward streams the chunks in reverse
    through the ``ssd_backward`` recurrence kind — no oracle recompute.
    On "xla" entries the jnp oracle is the forward path (and
    differentiates through itself), so semantics are identical everywhere.
    """
    hw, interp = _resolve(hardware, interpret)
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    if chunk is None:
        chunk = default_ssd_chunk(s, h, p, n, str(jnp.dtype(xdt.dtype)), hw)
    chunk = max(1, min(int(chunk), s))
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)
    use_kernel = _use_kernel(hw, interp, interpret)
    if use_kernel:
        return _ssd_kernel(xdt, dA, B, C, init_state, chunk, hw.name,
                           bool(interp))
    return _ssd_oracle(xdt, dA, B, C, init_state, chunk, unroll)


@functools.lru_cache(maxsize=128)
def _gated_executor(b, nc, q, w, dtype_s, hw_name, interpret):
    """Jitted executable for one chunked gated-scan shape
    (``expr.rglru_form`` through ``emit_recurrent``): operand order
    (log_a, b, H0); returns ``(h_seq, final_state)``."""
    from repro.kernels.emit import emit_recurrent_bundle
    form = E.rglru_form(b, nc, q, w)
    bundle = _sched.get_schedule(form, dtype=dtype_s,
                                 hardware=get_entry(hw_name), blocks=(q,))
    return jax.jit(emit_recurrent_bundle(bundle, out_dtype="float32",
                                         interpret=interpret))


def _gated_oracle(log_a, b_in, h0):
    return ref.gated_scan_ref(log_a, b_in, h0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gated_kernel(log_a, b_in, h0, chunk, hw_name, interpret):
    b, s, w = log_a.shape
    pad = (-s) % chunk
    sp = s + pad
    nc = sp // chunk
    la = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0))) if pad else log_a
    bb = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0))) if pad else b_in
    fn = _gated_executor(b, nc, chunk, w, str(jnp.dtype(log_a.dtype)),
                         hw_name, interpret)
    hs, final = fn(la.reshape(b, nc, chunk, w), bb.reshape(b, nc, chunk, w),
                   h0)
    return hs.reshape(b, sp, w)[:, :s], final


@functools.lru_cache(maxsize=128)
def _gated_bwd_executor(b, nc, q, w, hw_name, interpret):
    """The degenerate backward kind: the gated-scan cotangent recurrence
    ``dbar_t = dy_t + a_{t+1} dbar_{t+1}`` *is* a gated scan on
    time-reversed operands with the gate shifted one step — so the
    ``gated_backward`` kind reuses the forward kernel body verbatim on a
    form of its own (its own schedule-cache entry)."""
    from repro.kernels.emit import emit_recurrent_bundle
    form = E.rglru_bwd_form(b, nc, q, w)
    bundle = _sched.get_schedule(form, dtype="float32",
                                 hardware=get_entry(hw_name), blocks=(q,))
    return jax.jit(emit_recurrent_bundle(bundle, out_dtype="float32",
                                         interpret=interpret))


def _gated_kernel_fwd(log_a, b_in, h0, chunk, hw_name, interpret):
    out = _gated_kernel(log_a, b_in, h0, chunk, hw_name, interpret)
    return out, (log_a, b_in, h0, out[0])


def _gated_kernel_bwd(chunk, hw_name, interpret, resid, g):
    """Derived gated backward: run the ``gated_backward`` recurrence on the
    flipped, gate-shifted operands to get dbar, then the per-token
    cotangents are elementwise in the saved forward outputs (no oracle
    recompute — ``h_{t-1}`` comes from the saved sequence, not a replay)."""
    log_a, b_in, h0, hs = resid
    gy, gfin = g
    b, s, w = log_a.shape
    la32 = log_a.astype(jnp.float32)
    dy = gy.astype(jnp.float32).at[:, -1].add(gfin.astype(jnp.float32))
    la_shift = jnp.concatenate(
        [la32[:, 1:], jnp.zeros((b, 1, w), jnp.float32)], axis=1)
    laf = jnp.flip(la_shift, axis=1)
    dyf = jnp.flip(dy, axis=1)
    pad = (-s) % chunk
    sp = s + pad
    nc = sp // chunk
    # trailing pads sit *after* t=0 in reversed time: log_a=0 gates by 1,
    # dy=0 adds nothing, and the padded outputs are sliced away
    if pad:
        laf = jnp.pad(laf, ((0, 0), (0, pad), (0, 0)))
        dyf = jnp.pad(dyf, ((0, 0), (0, pad), (0, 0)))
    fn = _gated_bwd_executor(b, nc, chunk, w, hw_name, interpret)
    dbf, _ = fn(laf.reshape(b, nc, chunk, w), dyf.reshape(b, nc, chunk, w),
                jnp.zeros((b, w), jnp.float32))
    dbar = jnp.flip(dbf.reshape(b, sp, w)[:, :s], axis=1)
    a = jnp.exp(la32)
    h_prev = jnp.concatenate(
        [h0.astype(jnp.float32)[:, None], hs[:, :-1]], axis=1)
    dlog_a = (dbar * a * h_prev).astype(log_a.dtype)
    db = dbar.astype(b_in.dtype)
    dh0 = a[:, 0] * dbar[:, 0]
    return dlog_a, db, dh0


_gated_kernel.defvjp(_gated_kernel_fwd, _gated_kernel_bwd)


def gated_scan(log_a: jax.Array, b_in: jax.Array, *,
               init_state: Optional[jax.Array] = None,
               chunk: Optional[int] = None,
               interpret: Optional[bool] = None,
               hardware: Optional[HardwareEntry] = None
               ) -> tuple[jax.Array, jax.Array]:
    """Unified RG-LRU gated linear scan ``h_t = exp(log_a_t) h_{t-1} +
    b_t`` — the model-facing entry.  Returns ``(h (B, S, w) f32, final
    (B, w) f32)``.

    Same contract as ``scan_ssd``: the derived chunked kernel on Pallas /
    interpret entries (chunk from ``solve_recurrence_blocks``), the
    log-depth associative-scan oracle on "xla" entries only.  The VJP is
    derived too — the reversed cotangent scan is *itself* a gated scan on
    flipped, gate-shifted operands (the ``gated_backward`` kind).
    """
    hw, interp = _resolve(hardware, interpret)
    b, s, w = log_a.shape
    if init_state is None:
        init_state = jnp.zeros((b, w), jnp.float32)
    use_kernel = _use_kernel(hw, interp, interpret)
    if not use_kernel:
        return _gated_oracle(log_a, b_in, init_state)
    if chunk is None:
        chunk = default_gated_chunk(s, w, str(jnp.dtype(log_a.dtype)), hw)
    chunk = max(1, min(int(chunk), s))
    return _gated_kernel(log_a, b_in, init_state, chunk, hw.name,
                         bool(interp))


# ---------------------------------------------------------------------------
# paged decode: one query token against a paged KV cache.  The page table is
# STATIC schedule metadata (it rides RecurrentForm.key(), so the executor
# cache re-keys only when pages are allocated, never per token); the query's
# view-relative position is RUNTIME data in the POS aux operand, so one
# compiled kernel serves every token between allocations.  "xla" entries use
# the gather-pages jnp oracle — also the bit-identity reference for tests.
# ---------------------------------------------------------------------------

def default_decode_page(view_tokens: int, hkv: int, g: int, hd: int,
                        vd: int = 0, dtype="float32",
                        hardware: Optional[HardwareEntry] = None) -> int:
    """The derived KV page size: ``solve_recurrence_blocks`` over the
    streamed key axis with the O(window) carried (m, l, acc) state, the
    per-page K/V slabs as the token operands and the (g, page) score block
    as the quadratic intermediate.  The solved stream block IS the page —
    pages exist so BlockSpecs can address them, so their size is a property
    of the memory hierarchy, not a tuning knob."""
    from repro.core.blocking import solve_recurrence_blocks
    vd = vd or hd
    hw = hardware or current_hardware()
    choice = solve_recurrence_blocks(
        view_tokens,
        token_elems=hkv * (hd + vd),            # one K + one V row per key
        state_elems=g * (vd + 2),               # carried acc + (m, l)
        quad_elems=g,                           # the (g, page) score block
        lin_elems=g * hd,                       # the resident query rows
        dtype=dtype, hardware=getattr(hw, "shape", hw))
    return choice.bs


@functools.lru_cache(maxsize=512)
def _decode_executor(hkv, g, hd, vd, page, view_pages, pool_pages, table,
                     window, scale, dtype_s, hw_name, interpret):
    """Jitted executable for one paged-decode shape + page table: the
    cached derivation of ``expr.windowed_decode_form`` through
    ``emit_recurrent``.  Binds (q, k_pool, v_pool, pos); returns the
    (hkv, g, vd) f32 context.  A canonical page allocator makes tables recur
    across sequences, so this cache stays hot in steady-state serving."""
    from repro.kernels.emit import emit_recurrent_bundle
    form = E.windowed_decode_form(hkv, g, hd, vd, page=page,
                                  view_pages=view_pages,
                                  pool_pages=pool_pages, page_table=table,
                                  window=window)
    bundle = _sched.get_schedule(form, dtype=dtype_s,
                                 hardware=get_entry(hw_name),
                                 blocks=(g, page))
    return jax.jit(emit_recurrent_bundle(bundle, scale=scale, causal=True,
                                         out_dtype="float32",
                                         interpret=interpret))


def _paged_oracle(q, k_pool, v_pool, pos, table, page, scale, window):
    """Gather the view pages into a contiguous cache, then run the masked
    softmax — the reference the kernel must match bit-for-bit on integer
    inputs (both paths do the same float ops in the same order per key)."""
    idx = jnp.concatenate(
        [jnp.arange(t * page, (t + 1) * page) for t in table])
    k = k_pool[idx]                              # (sk, hkv, hd)
    v = v_pool[idx]
    s = jnp.einsum("hgc,jhc->hgj", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    j = jnp.arange(k.shape[0])[None, None, :]
    vpos = pos[0, 0]
    mask = j <= vpos
    if window:
        mask = jnp.logical_and(mask, j > vpos - window)
    s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("hgj,jhd->hgd", p, v.astype(jnp.float32))


def paged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                 pos: jax.Array, *, page_table: tuple, page: int,
                 scale: float, window: int = 0,
                 interpret: Optional[bool] = None,
                 hardware: Optional[HardwareEntry] = None) -> jax.Array:
    """One decode step of grouped-query attention against a paged KV cache.

    ``q`` is (hkv, g, hd) — one token's query heads grouped under their KV
    head; ``k_pool``/``v_pool`` are the (pool_tokens, hkv, hd) slab pools;
    ``pos`` is the (1, 2) int32 POS aux whose ``[0, 0]`` entry is the
    query's VIEW-RELATIVE position (absolute position minus the view's
    start token).  ``page_table`` maps view page -> pool slab; masking is
    entirely in view coordinates, so unallocated trailing view pages may
    point at any slab — the causal mask keeps them inert.
    """
    hw, interp = _resolve(hardware, interpret)
    table = tuple(int(t) for t in page_table)
    if not table:
        raise ValueError("paged_decode requires a non-empty page table")
    hkv, g, hd = q.shape
    vd = v_pool.shape[-1]
    if k_pool.shape[0] % page or k_pool.shape[0] != v_pool.shape[0]:
        raise ValueError(
            f"pool token extents {k_pool.shape[0]}/{v_pool.shape[0]} must "
            f"be equal and a multiple of page={page}")
    pool_pages = k_pool.shape[0] // page
    use_kernel = _use_kernel(hw, interp, interpret)
    if not use_kernel:
        return _paged_oracle(q, k_pool, v_pool, pos, table, page,
                             float(scale), int(window))
    fn = _decode_executor(hkv, g, hd, vd, int(page), len(table),
                          pool_pages, table, int(window), float(scale),
                          str(jnp.dtype(q.dtype)), hw.name, bool(interp))
    return fn(q, k_pool, v_pool, pos)


@functools.lru_cache(maxsize=512)
def _batched_decode_executor(slots, hkv, g, hd, vd, page, view_pages,
                             pool_pages, tables, window, scale, dtype_s,
                             hw_name, interpret):
    """Jitted executable for one batched-decode shape + STACKED page table:
    the cached derivation of ``expr.batched_decode_form`` through
    ``emit_recurrent``.  Binds (q, k_pool, v_pool, pos); returns the
    (slots, hkv, g, vd) f32 context.  The LRU key is the stacked-table
    tuple — the engine pads dead slots with a dead table row, so the key
    changes only when live pages move, never with the active slot count."""
    from repro.kernels.emit import emit_recurrent_bundle
    form = E.batched_decode_form(slots, hkv, g, hd, vd, page=page,
                                 view_pages=view_pages,
                                 pool_pages=pool_pages, page_tables=tables,
                                 window=window)
    bundle = _sched.get_schedule(form, dtype=dtype_s,
                                 hardware=get_entry(hw_name),
                                 blocks=(g, page))
    return jax.jit(emit_recurrent_bundle(bundle, scale=scale, causal=True,
                                         out_dtype="float32",
                                         interpret=interpret))


def _batched_oracle(q, k_pool, v_pool, pos, tables, page, scale, window):
    """Per-slot ``_paged_oracle`` stacked over the slot axis — the batched
    reference.  Dead slots (pos -1) produce garbage rows the caller masks;
    the oracle clamps their gather indices like the device would."""
    outs = [_paged_oracle(q[s], k_pool, v_pool, pos[s:s + 1], tables[s],
                          page, scale, window)
            for s in range(q.shape[0])]
    return jnp.stack(outs)


def paged_decode_batched(q: jax.Array, k_pool: jax.Array,
                         v_pool: jax.Array, pos: jax.Array, *,
                         page_tables: tuple, page: int, scale: float,
                         window: int = 0, interpret: Optional[bool] = None,
                         hardware: Optional[HardwareEntry] = None
                         ) -> jax.Array:
    """One decode step for EVERY active slot in one kernel launch.

    ``q`` is (slots, hkv, g, hd) — one query token per slot; the pools are
    the same shared (pool_tokens, hkv, hd) slab storage ``paged_decode``
    binds; ``pos`` is the (slots, 2) int32 POS aux whose ``[s, 0]`` entry
    is slot ``s``'s view-relative query position.  ``page_tables`` is the
    stacked ``[slot][k]`` view->slab map — static metadata on the executor
    cache, so the launch count per engine iteration is 1 regardless of the
    active slot count.  A dead/padded slot rides a row of dead entries
    with ``pos[s, 0] == -1``: every block-skip guard ``k*page <= -1`` is
    false, so its (m, l, acc) state never folds and the flush emits the
    0/max(l, eps) zero row.
    """
    hw, interp = _resolve(hardware, interpret)
    tables = tuple(tuple(int(t) for t in row) for row in page_tables)
    if not tables or not tables[0]:
        raise ValueError(
            "paged_decode_batched requires a non-empty stacked page table")
    slots, hkv, g, hd = q.shape
    vd = v_pool.shape[-1]
    if k_pool.shape[0] % page or k_pool.shape[0] != v_pool.shape[0]:
        raise ValueError(
            f"pool token extents {k_pool.shape[0]}/{v_pool.shape[0]} must "
            f"be equal and a multiple of page={page}")
    pool_pages = k_pool.shape[0] // page
    use_kernel = _use_kernel(hw, interp, interpret)
    if not use_kernel:
        return _batched_oracle(q, k_pool, v_pool, pos, tables, page,
                               float(scale), int(window))
    fn = _batched_decode_executor(slots, hkv, g, hd, vd, int(page),
                                  len(tables[0]), pool_pages, tables,
                                  int(window), float(scale),
                                  str(jnp.dtype(q.dtype)), hw.name,
                                  bool(interp))
    return fn(q, k_pool, v_pool, pos)


# ---------------------------------------------------------------------------
# the unified operator (paper appendix: "one algorithm/circuit (ipophp)")
# ---------------------------------------------------------------------------

def outer(a: jax.Array, b: jax.Array, *, interpret: Optional[bool] = None
          ) -> jax.Array:
    """Outer product of matrices through the SAME gemm circuit: the MoA
    degenerate inner product — rav(A) (mn,1) . rav(B)^T (1,pq), reshaped.
    (Contraction extent 1: the sigma loop collapses, nothing else changes.)"""
    m, n = a.shape
    p, q = b.shape
    flat = moa_gemm(a.reshape(m * n, 1), b.reshape(1, p * q),
                    interpret=interpret)
    return flat.reshape(m, n, p, q)


def kron(a: jax.Array, b: jax.Array, *, interpret: Optional[bool] = None
         ) -> jax.Array:
    """Kronecker product = outer product + gamma re-layout (transpose/reshape):
    the paper's claim that KP shares the MM circuit, realized literally."""
    m, n = a.shape
    p, q = b.shape
    return outer(a, b, interpret=interpret).transpose(0, 2, 1, 3).reshape(m * p, n * q)


def ipophp(a: jax.Array, b: jax.Array, mode: str, *,
           interpret: Optional[bool] = None) -> jax.Array:
    """Unified inner/outer/hadamard/kron dispatcher (single blocked circuit:
    'ip' is the full schedule, 'op'/'kp' are its contraction-degenerate form,
    'hp' its pairing-degenerate form)."""
    if mode == "ip":
        return moa_gemm(a, b, interpret=interpret)
    if mode == "op":
        return outer(a, b, interpret=interpret)
    if mode == "kp":
        return kron(a, b, interpret=interpret)
    if mode == "hp":
        return hadamard(a, b, interpret=interpret)
    raise ValueError(f"unknown ipophp mode {mode!r}")


# convenience: oracle aliases so callers can switch paths uniformly
gemm_ref = ref.gemm_ref
ipophp_ref = ref.ipophp_ref
