"""The schedule-derivation subsystem: lifted ONF -> Schedule -> Pallas.

Covers the satellite checklist: gamma round-trips, gamma_blocked vs
lift_loop access-rewrite consistency, and the keystone — the emitted kernel
for a derived schedule matching both the ``onf_gemm`` ONF oracle and
``jnp.dot`` in interpret mode, including non-divisible (padded/masked)
shapes — plus the schedule cache counters and the hardware registry.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import expr
from repro.core import hardware as hw
from repro.core import moa, onf
from repro.core import schedule as sched
from repro.core.blocking import BlockChoice
from repro.kernels import ops
from repro.kernels.emit import emit_pallas


def _err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))


# ---------------------------------------------------------------------------
# property round-trips (plain pytest, no hypothesis dependency)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1,), (7,), (3, 5), (2, 3, 4), (4, 1, 2, 3)])
def test_gamma_row_roundtrip_every_offset(shape):
    for off in range(moa.pi(shape)):
        idx = moa.gamma_row_inverse(off, shape)
        assert moa.gamma_row(idx, shape) == off
    for idx in moa.iota(shape).reshape(-1, len(shape)):
        idx = tuple(int(i) for i in idx)
        assert moa.gamma_row_inverse(moa.gamma_row(idx, shape), shape) == idx


@pytest.mark.parametrize("m,n,bm,bn", [(4, 6, 2, 3), (8, 8, 4, 2), (6, 4, 3, 4)])
def test_gamma_blocked_is_lifted_row_major(m, n, bm, bn):
    """gamma_blocked == gamma_row over the dimension-lifted index
    (i_o, j_o, i_i, j_i) with the lifted shape — blocking IS lifting."""
    for i, j in itertools.product(range(m), range(n)):
        lifted_idx = (i // bm, j // bn, i % bm, j % bn)
        lifted_shape = (m // bm, n // bn, bm, bn)
        assert moa.gamma_blocked((i, j), (m, n), (bm, bn)) == \
            moa.gamma_row(lifted_idx, lifted_shape)


def test_lift_loop_rewrite_preserves_gamma_offsets():
    """The affine access rewrite of lift_loop resolves to the SAME flat
    offsets as gamma_row on the unsplit index — layout is untouched."""
    m, n, p = 8, 6, 4
    o = onf.gemm_onf(m, n, p)
    lifted = onf.lift_loop(o, "i", 2, "proc")
    a_acc = lifted.ins[0]          # A, coeffs over i_o/i_i/k
    for i, k in itertools.product(range(m), range(n)):
        env = {"i_o": i // (m // 2), "i_i": i % (m // 2), "k": k, "j": 0}
        assert a_acc.offset(env) == moa.gamma_row((i, k), (m, n))


# ---------------------------------------------------------------------------
# derivation structure: the schedule reproduces the hand-written layout
# ---------------------------------------------------------------------------

def test_derived_gemm_schedule_matches_handwritten_layout():
    m, k, n = 256, 192, 128
    bm, bk, bn = 64, 48, 32
    lifted = onf.gemm_fully_lifted(m, k, n, procs=m // bm, bk=bk, bn=bn)
    s = sched.derive_schedule(lifted)
    assert s.grid_extents == (m // bm, n // bn, k // bk)
    assert s.dimension_semantics == ("parallel", "parallel", "arbitrary")
    a, b = s.ins
    assert (a.block, a.grid_dims) == ((bm, bk), (0, 2))
    assert (b.block, b.grid_dims) == ((bk, bn), (2, 1))
    assert (s.out.block, s.out.grid_dims) == ((bm, bn), (0, 1))
    assert s.contracted == ("k",) and s.needs_scratch


def test_derived_expert_schedule_lifts_expert_axis():
    s = sched.derive_schedule(
        onf.expert_gemm_fully_lifted(4, 64, 96, 32, bm=32, bk=48, bn=32))
    assert s.grid_extents == (4, 2, 1, 2)
    assert s.dimension_semantics == ("parallel",) * 3 + ("arbitrary",)
    assert s.ins[0].block == (1, 32, 48)      # expert axis rides as block 1
    assert s.out.grid_dims == (0, 1, 2)


def test_derive_requires_a_lifted_nest():
    with pytest.raises(ValueError, match="lift"):
        sched.derive_schedule(onf.gemm_onf(8, 8, 8))


def test_derive_handles_nested_double_lift():
    """Lifting a lifted axis again (i -> i_o -> i_i_o) is a deeper hierarchy,
    not an error: the derivation treats i and i_i as nested logical axes and
    the emitted kernel still reproduces the GEMM."""
    o = onf.gemm_onf(16, 16, 16)
    o = onf.lift_loop(o, "i", 2, "proc")
    o = onf.lift_loop(o, "i_i", 2, "vector")
    s = sched.derive_schedule(o)
    assert s.grid_extents == (2, 2)
    assert s.dimension_semantics == ("parallel", "parallel")
    fn = emit_pallas(s, out_dtype=jnp.float32, interpret=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(9))
    a = jax.random.normal(k1, (16, 16), jnp.float32)
    b = jax.random.normal(k2, (16, 16), jnp.float32)
    # operands arrive in the lifted view — a pure gamma re-layout (reshape)
    got = fn(a.reshape(s.ins[0].shape), b.reshape(s.ins[1].shape))
    assert _err(got.reshape(16, 16), jnp.dot(a, b)) < 1e-4


# ---------------------------------------------------------------------------
# keystone: emitted kernel == ONF oracle == jnp.dot (interpret mode)
# ---------------------------------------------------------------------------

def test_emit_derived_gemm_matches_onf_oracle_and_dot():
    m, k, n = 32, 48, 16
    lifted = onf.gemm_fully_lifted(m, k, n, procs=4, bk=16, bn=8)
    fn = emit_pallas(sched.derive_schedule(lifted), out_dtype=jnp.float32,
                     interpret=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (m, k), jnp.float32)
    b = jax.random.normal(k2, (k, n), jnp.float32)
    got = fn(a, b)
    want_onf = lifted.execute(np.zeros(m * n, np.float32),
                              np.asarray(a).ravel(), np.asarray(b).ravel())
    assert _err(got, want_onf.reshape(m, n)) < 1e-4
    assert _err(got, jnp.dot(a, b)) < 1e-4
    # and the flat ONF form (paper eq. 3) agrees too
    want_flat = moa.onf_gemm(np.asarray(a).ravel(), np.asarray(b).ravel(),
                             m, k, n)
    assert _err(got, want_flat.reshape(m, n)) < 1e-4


@pytest.mark.parametrize("m,k,n", [(129, 257, 127), (100, 70, 130), (1, 1, 1),
                                   (8, 1024, 8)])
def test_derived_path_non_divisible_shapes(m, k, n):
    """Padding/masking path: ops.moa_gemm pads to block multiples, runs the
    derived schedule, slices back — must match jnp.dot exactly in shape."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    a = jax.random.normal(k1, (m, k), jnp.float32)
    b = jax.random.normal(k2, (k, n), jnp.float32)
    got = ops.moa_gemm(a, b, interpret=True)
    want = jnp.dot(a, b, preferred_element_type=jnp.float32)
    assert got.shape == (m, n)
    assert _err(got, want) < 5e-5 * max(k, 1)


@pytest.mark.parametrize("op,shapes", [
    ("gemm", (37, 23, 41)),
    ("expert", (3, 18, 12, 10)),
    ("hadamard", (37, 141)),
])
def test_derived_bit_identical_to_onf_oracle(op, shapes):
    """Interpret-mode kernels are bit-identical to the ONF oracle
    (``Onf.execute``) on integer-valued f32 inputs, where every summation
    order produces the same exact floats — including padded remainder
    blocks.  This replaced the legacy hand-written-kernel cross-check when
    those kernels were removed."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))

    def ints(key, shape):
        return jax.random.randint(key, shape, -4, 5).astype(jnp.float32)

    if op == "gemm":
        m, k, n = shapes
        a, b = ints(k1, (m, k)), ints(k2, (k, n))
        got = ops.moa_gemm(a, b, interpret=True)
        o = onf.gemm_onf(m, k, n)
        want = o.execute(o.init_out(m * n), np.asarray(a).ravel(),
                         np.asarray(b).ravel()).reshape(m, n)
    elif op == "expert":
        e, cap, d, f = shapes
        x, w = ints(k1, (e, cap, d)), ints(k2, (e, d, f))
        got = ops.expert_gemm(x, w, interpret=True)
        o = onf.expert_gemm_onf(e, cap, d, f)
        want = o.execute(o.init_out(e * cap * f), np.asarray(x).ravel(),
                         np.asarray(w).ravel()).reshape(e, cap, f)
    else:
        m, n = shapes
        a = ints(k1, (m, n))
        got = ops.hadamard(a, a, interpret=True)
        o = onf.hadamard_onf(m, n)
        want = o.execute(o.init_out(m * n), np.asarray(a).ravel(),
                         np.asarray(a).ravel()).reshape(m, n)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_unified_matmul_entry_collapses_batch_and_head_dims():
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (2, 5, 16), jnp.float32)
    w = jax.random.normal(k2, (16, 3, 4), jnp.float32)
    got = ops.matmul(x, w, interpret=True)          # forced kernel path
    want = jnp.einsum("bsd,dhk->bshk", x, w)
    assert got.shape == (2, 5, 3, 4)
    assert _err(got, want) < 1e-4
    # XLA-oracle dispatch (no interpret flag on a CPU entry) agrees too
    with hw.use_hardware("v100"):
        assert _err(ops.matmul(x, w), want) < 1e-4


def test_unified_matmul_is_differentiable_through_kernel():
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(k1, (6, 8), jnp.float32)
    w = jax.random.normal(k2, (8, 4), jnp.float32)

    def loss(xx, ww):
        return (ops.matmul(xx, ww, interpret=True) ** 2).sum()

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(2 * (x @ w) @ w.T),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(2 * x.T @ (x @ w)),
                               rtol=1e-4, atol=1e-4)


def test_expert_matmul_entry_matches_einsum():
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(k1, (3, 10, 12), jnp.float32)
    w = jax.random.normal(k2, (3, 12, 6), jnp.float32)
    want = jnp.einsum("ecd,edf->ecf", x, w)
    assert _err(ops.expert_matmul(x, w, interpret=True), want) < 1e-4
    assert _err(ops.expert_matmul(x, w), want) < 1e-4


# ---------------------------------------------------------------------------
# the schedule cache: repeated calls never re-run solve_blocks
# ---------------------------------------------------------------------------

def test_schedule_cache_keyed_on_normal_form():
    sched.reset_schedule_cache()
    entry = hw.get_entry("cpu")
    gemm = expr.matmul_expr(300, 200, 100)
    b0 = sched.get_schedule(gemm, dtype="float32", hardware=entry)
    after_first = sched.schedule_cache_stats()
    assert after_first["misses"] == 1 and after_first["solves"] == 1
    b1 = sched.get_schedule(gemm, dtype="float32", hardware=entry)
    after_second = sched.schedule_cache_stats()
    assert b1 is b0
    assert after_second["hits"] == 1
    assert after_second["solves"] == 1          # no repeated brute-force work
    # a structurally identical expression is the SAME cache line — the
    # normal form, not object identity or a string name, is the key
    again = expr.inner("add", "mul", expr.arr("A", (300, 200)),
                       expr.arr("B", (200, 100)))
    assert sched.get_schedule(again, dtype="float32", hardware=entry) is b0
    # a different hardware entry is a different cache line
    sched.get_schedule(gemm, dtype="float32", hardware=hw.get_entry("v100"))
    assert sched.schedule_cache_stats()["misses"] == 2


def test_deprecated_string_op_lands_on_expression_cache_line():
    """The one-release string signature still works (with a warning) and
    shares cache lines with the equivalent expression."""
    sched.reset_schedule_cache()
    entry = hw.get_entry("cpu")
    b0 = sched.get_schedule(expr.matmul_expr(64, 32, 48), dtype="float32",
                            hardware=entry)
    with pytest.deprecated_call():
        b1 = sched.get_schedule("gemm", (64, 32, 48), "float32", entry)
    assert b1 is b0
    assert sched.schedule_cache_stats()["hits"] == 1
    with pytest.raises(ValueError, match="unknown schedule op"):
        with pytest.deprecated_call():
            sched.get_schedule("conv", (1, 2, 3), "float32", entry)


def test_transposed_and_col_layout_share_a_normal_form():
    """transpose(row-major (n,k)) and col-major (k,n) psi-reduce to the same
    flat coefficients, hence the same schedule-cache line."""
    sched.reset_schedule_cache()
    entry = hw.get_entry("cpu")
    via_transpose = expr.inner("add", "mul", expr.arr("A", (32, 16)),
                               expr.transpose(expr.arr("B", (24, 16))))
    via_col = expr.inner("add", "mul", expr.arr("A", (32, 16)),
                         expr.arr("B", (16, 24), layout="col"))
    b0 = sched.get_schedule(via_transpose, dtype="float32", hardware=entry)
    b1 = sched.get_schedule(via_col, dtype="float32", hardware=entry)
    assert b1 is b0
    assert sched.schedule_cache_stats() == {"hits": 1, "misses": 1,
                                            "solves": 1}


def test_ops_path_reuses_cached_schedule():
    sched.reset_schedule_cache()
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    a = jax.random.normal(k1, (96, 64), jnp.float32)
    b = jax.random.normal(k2, (64, 80), jnp.float32)
    ops.moa_gemm(a, b, interpret=True)
    solves = sched.schedule_cache_stats()["solves"]
    for _ in range(3):
        ops.moa_gemm(a, b, interpret=True)
    assert sched.schedule_cache_stats()["solves"] == solves


# ---------------------------------------------------------------------------
# hardware registry
# ---------------------------------------------------------------------------

def test_registry_detects_and_overrides():
    entry = hw.detect_hardware()
    assert entry.name in hw.registered_hardware()
    if jax.default_backend() == "cpu":
        assert entry.name == "cpu" and entry.interpret
    with hw.use_hardware("tpu_v5e") as forced:
        assert forced.backend == "pallas" and not forced.interpret
        assert hw.current_hardware().name == "tpu_v5e"
    assert hw.current_hardware().name == entry.name
    with pytest.raises(KeyError):
        hw.get_entry("dgx-imaginary")


def test_registry_detects_tpu_by_device_kind():
    """A TPU maps to its entry by ``device_kind``; a kind with no entry
    raises and names itself instead of borrowing another chip's table."""
    assert hw.entry_for_device("tpu", "TPU v5 lite") == "tpu_v5e"
    assert hw.entry_for_device("cpu", "cpu") == "cpu"
    with pytest.raises(RuntimeError, match="TPU v4"):
        hw.entry_for_device("tpu", "TPU v4")


def test_vmem_validation_rejects_oversized_blocks():
    huge = BlockChoice(bm=4096, bk=4096, bn=4096, vmem_bytes=0,
                       arithmetic_intensity=0, utilization=1)
    with pytest.raises(ValueError, match="VMEM"):
        sched.get_schedule(expr.matmul_expr(8192, 8192, 8192),
                           dtype="float32", hardware=hw.get_entry("cpu"),
                           blocks=huge)
