"""Distributed dimension-lifting: derived shard_map plans.

In-process tests cover pure plan derivation (no devices needed): partition
specs recovered from lifted Access coefficients, the derived collective
choice per sharding kind, non-divisible replication fallback, the plan
cache, and the modeled per-device byte counts.  The multi-device matrix —
sharded result == single-device oracle across mesh shapes {1, 2, 4, 8} x
{row, col, both, sigma}-sharded operands, with jaxpr pins that no unplanned
collective appears — runs in-process when 8 devices exist (the CI
multi-device job) and in a subprocess with 8 forced host devices otherwise.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.core import expr as E
from repro.core import hardware as hw
from repro.core import mesh as mesh_mod
from repro.core import onf as onf_mod
from repro.core import schedule as sched
from repro.core.mesh import MeshShape
from repro.distributed import plan as dplan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = hw.get_entry("cpu")
MS8 = MeshShape((("x", 8),))


# ---------------------------------------------------------------------------
# the mesh level of the lifting hierarchy
# ---------------------------------------------------------------------------

def test_mesh_shape_validation_and_lookup():
    ms = MeshShape((("data", 4), ("model", 2)))
    assert ms.axis_names == ("data", "model")
    assert ms.shape == (4, 2) and ms.n_devices == 8
    assert ms.axis_size("model") == 2
    with pytest.raises(KeyError):
        ms.axis_size("pod")
    with pytest.raises(ValueError, match="duplicate"):
        MeshShape((("x", 2), ("x", 4)))
    with pytest.raises(ValueError, match="non-positive"):
        MeshShape((("x", 0),))
    # the registry's hardware shapes already declare their mesh axes
    from repro.core.lifting import TPU_V5E
    assert MeshShape.from_hardware(TPU_V5E).axes == (("data", 16),
                                                     ("model", 16))


def test_mesh_lift_tags_loops_and_single_chip_schedule_rejects_them():
    """A mesh-lifted loop is one more dimension lift (same affine rewrite),
    and has no single-chip schedule — derive_schedule must reject it with a
    pointer to the plan subsystem, not silently grid it."""
    o = E.normalize(E.matmul_expr(8, 8, 8))
    lifted = mesh_mod.mesh_lift(o, "i", MeshShape((("x", 2),)), "x")
    (outer,) = [l for l in lifted.loops if l.resource == "mesh:x"]
    assert outer.index == "i_o" and outer.extent == 2
    assert lifted.ins[0].coeffs["i_o"] == 4 * 8     # i -> i_o*4 + i_i
    with pytest.raises(ValueError, match="mesh"):
        sched.derive_schedule(onf_mod.lift_loop(lifted, "j", 1, "proc"))


# ---------------------------------------------------------------------------
# plan derivation: specs and collectives, asserted from the plan itself
# ---------------------------------------------------------------------------

def test_plan_specs_and_collectives_per_sharding_kind():
    cases = [
        ("row", {"m": "x"}, {}, "none",
         ((("x", None)), (None, None)), ("x", None)),
        ("col", {"n": "x"}, {}, "none",
         (((None, None)), (None, "x")), (None, "x")),
        ("sigma", {"k": "x"}, {}, "psum",
         (((None, "x")), ("x", None)), (None, None)),
        ("gather", {"m": "x"}, {"replicate_out": True}, "all_gather",
         ((("x", None)), (None, None)), (None, None)),
        ("scatter", {"k": "x"}, {"scatter_axis": "m"}, "reduce_scatter",
         (((None, "x")), ("x", None)), ("x", None)),
    ]
    for name, shard, kw, coll, in_entries, out_entries in cases:
        plan = dplan.matmul_plan(64, 48, 32, MS8, shard=shard, hardware=CPU,
                                 **kw)
        assert plan.collective == coll, name
        assert plan.in_entries == in_entries, name
        assert plan.out_entries == out_entries, name
        assert plan.dropped == (), name


def test_plan_both_sharded_needs_no_collective():
    ms = MeshShape((("dx", 4), ("dy", 2)))
    plan = dplan.matmul_plan(64, 48, 32, ms, shard={"m": "dx", "n": "dy"},
                             hardware=CPU)
    assert plan.collective == "none"
    assert plan.in_entries == (("dx", None), (None, "dy"))
    assert plan.out_entries == ("dx", "dy")
    # mixed row+sigma across two axes: psum over the sigma axis only
    plan2 = dplan.matmul_plan(64, 48, 32, ms, shard={"m": "dx", "k": "dy"},
                              hardware=CPU)
    assert plan2.collective == "psum"
    assert plan2.collectives[0].mesh_axis == "dy"
    assert plan2.out_entries == ("dx", None)


def test_plan_transposed_operand_spec_lands_on_stored_dim():
    """The acceptance property at the mesh level: specs are recovered from
    the lifted coefficients, so sharding the output columns of x @ w.T
    shards dim 0 of the STORED (n, k) table — no special casing."""
    plan = dplan.matmul_plan(64, 32, 48, MS8, shard={"n": "x"},
                             transpose_b=True, hardware=CPU)
    assert plan.in_entries[1] == ("x", None)        # stored (n, k)
    assert plan.out_entries == (None, "x")
    assert plan.collective == "none"


def test_plan_per_shard_schedule_uses_local_extents():
    plan = dplan.matmul_plan(64, 48, 32, MS8, shard={"m": "x"}, hardware=CPU)
    assert plan.local_extent("i") == 8              # 64 / 8 devices
    assert plan.local_extent("k") == 48 and plan.local_extent("j") == 32
    # the per-shard bundle is a real derived schedule over local shapes
    assert plan.bundle.out_shape == (8, 32)
    assert plan.bundle.in_shapes == ((8, 48), (48, 32))


def test_plan_non_divisible_falls_back_to_replication():
    plan = dplan.matmul_plan(30, 48, 32, MeshShape((("x", 4),)),
                             shard={"m": "x"}, hardware=CPU)
    assert plan.applied == () and plan.dropped == (("i", "x"),)
    assert plan.in_entries == ((None, None), (None, None))
    assert plan.collective == "none"
    assert plan.local_extent("i") == 30             # nothing was split


def test_apply_rejects_blocks_on_sharded_path():
    """apply(mesh=...) derives per-shard blocks from the plan; a pinned
    blocks= used to be silently dropped — now it raises."""
    from repro.kernels import ops
    mesh1 = jax.make_mesh((1,), ("x",), devices=jax.devices()[:1])
    a = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="blocks"):
        ops.apply(E.matmul_expr(8, 8, 8), a, a, mesh=mesh1,
                  shard={"i": "x"}, blocks=(64, 64, 64))


def test_plan_rejects_noncommutative_sigma_shard():
    """psum ADDS per-device partials; mesh-lifting the sigma axis of a
    tropical (max/min) semiring must raise, not silently sum partial maxes."""
    maxplus = E.inner("max", "add", E.arr("A", (32, 32)),
                      E.arr("B", (32, 32)))
    with pytest.raises(ValueError, match="reduce"):
        dplan.derive_plan(maxplus, MeshShape((("x", 2),)), shard={"k": "x"},
                          hardware=CPU)
    # output-axis sharding of the same semiring needs no cross-device
    # reduction and stays derivable
    plan = dplan.derive_plan(maxplus, MeshShape((("x", 2),)),
                             shard={"i": "x"}, hardware=CPU)
    assert plan.collective == "none"


def test_plan_rejects_bad_requests():
    with pytest.raises(KeyError, match="unknown axis"):
        dplan.derive_plan(E.matmul_expr(8, 8, 8), MS8, shard={"z": "x"},
                          hardware=CPU)
    with pytest.raises(KeyError):
        dplan.matmul_plan(8, 8, 8, MS8, shard={"m": "nope"}, hardware=CPU)
    with pytest.raises(ValueError, match="two axes"):
        dplan.matmul_plan(64, 64, 64, MS8, shard={"m": "x", "n": "x"},
                          hardware=CPU)
    with pytest.raises(KeyError, match="role"):
        dplan.matmul_plan(8, 8, 8, MS8, shard={"rows": "x"}, hardware=CPU)
    # scatter_axis without a mesh-lifted sigma axis must fail loudly, not
    # silently return a collective-free plan
    with pytest.raises(ValueError, match="reduction axis"):
        dplan.matmul_plan(64, 48, 32, MS8, shard={"m": "x"},
                          scatter_axis="m", hardware=CPU)
    with pytest.raises(ValueError, match="output axis"):
        dplan.matmul_plan(64, 48, 32, MS8, shard={"m": "x"},
                          scatter_axis="k", hardware=CPU)


def test_tp_shard_helper_rejects_unknown_axis_names():
    """Silent empty shards would mean every device redundantly computes the
    full GEMM while the caller believes TP is active."""
    assert dplan.tp_matmul_shard(MeshShape((("data", 4), ("model", 2))),
                                 "sigma") == {"m": "data", "k": "model"}
    with pytest.raises(ValueError, match="data"):
        dplan.tp_matmul_shard(MS8, "col")       # axes named ("x",)
    with pytest.raises(ValueError, match="row|col|sigma"):
        dplan.tp_matmul_shard(MeshShape((("model", 2),)), "diag")


def test_expert_plan_shards_the_expert_axis():
    plan = dplan.expert_plan(8, 16, 12, 10, MS8, shard={"e": "x"},
                             hardware=CPU)
    assert plan.collective == "none"
    assert plan.in_entries == (("x", None, None), ("x", None, None))
    assert plan.out_entries == ("x", None, None)
    assert plan.local_extent("i") == 1              # one expert per device


def test_plan_cache_hits_and_stats():
    dplan.reset_plan_cache()
    p0 = dplan.matmul_plan(300, 200, 100, MS8, shard={"m": "x"}, hardware=CPU)
    assert dplan.plan_cache_stats() == {"hits": 0, "misses": 1}
    p1 = dplan.matmul_plan(300, 200, 100, MS8, shard={"m": "x"}, hardware=CPU)
    assert p1 is p0
    assert dplan.plan_cache_stats() == {"hits": 1, "misses": 1}
    # a different sharding of the same normal form is a different plan line
    dplan.matmul_plan(300, 200, 100, MS8, shard={"k": "x"}, hardware=CPU)
    assert dplan.plan_cache_stats()["misses"] == 2


def test_plan_byte_model():
    """Modeled per-device HBM and interconnect traffic: sharding shrinks the
    resident bytes; only collective-bearing plans move ICI bytes."""
    esize = 4
    none_plan = dplan.matmul_plan(64, 48, 32, MS8, shard={"m": "x"},
                                  hardware=CPU)
    assert none_plan.ici_bytes_per_device() == 0
    assert none_plan.hbm_bytes_per_device() == \
        (8 * 48 + 48 * 32 + 8 * 32) * esize
    psum_plan = dplan.matmul_plan(64, 48, 32, MS8, shard={"k": "x"},
                                  hardware=CPU)
    out_bytes = 64 * 32 * 4
    assert psum_plan.ici_bytes_per_device() == int(2 * 7 / 8 * out_bytes)
    ag_plan = dplan.matmul_plan(64, 48, 32, MS8, shard={"m": "x"},
                                replicate_out=True, hardware=CPU)
    assert ag_plan.ici_bytes_per_device() == int(7 / 8 * out_bytes)
    # the gathered result is FULL-size resident on every device
    assert ag_plan.local_out_shape() == (64, 32)
    assert ag_plan.hbm_bytes_per_device() == \
        (8 * 48 + 48 * 32 + 64 * 32) * esize


def test_plan_psi_view_nonzero_offset_lowered_to_index_map():
    """A psi view with a non-zero slab offset plans like any other leaf:
    the fixed slab dim is replicated, the sharded axis lands on the right
    stored dim, and the per-shard bundle re-derives the constant Access
    term at local extents as a BlockSpec index-map offset
    (``OperandSpec.offsets``) — no materializing copy."""
    e = E.inner("add", "mul", E.psi((1,), E.arr("X", (2, 16, 16))),
                E.arr("B", (16, 8)))
    plan = dplan.derive_plan(e, MS8, shard={"i": "x"}, hardware=CPU)
    assert plan.in_entries[0] == (None, "x", None)
    assert plan.in_entries[1] == (None, None)
    assert plan.out_entries == ("x", None)
    assert plan.collective == "none"
    assert plan.local_extent("i") == 2
    x_spec = plan.bundle.schedule.ins[0]
    assert x_spec.is_psi_view
    assert x_spec.offsets[0] == 1 and x_spec.block[0] == 1
    # sigma sharding through the viewed contraction still derives the psum
    psum = dplan.derive_plan(e, MS8, shard={"k": "x"}, hardware=CPU)
    assert psum.collective == "psum"
    assert psum.in_entries[0] == (None, None, "x")


def test_plan_psi_view_at_index_zero_places_specs_structurally():
    """Regression: _spec_entries used to key psi-view detection on
    Access.const *truthiness*, so a view at index 0 (const == 0) mis-placed
    its PartitionSpec entries on the leading slab dim.  Fixed leading dims
    are now detected structurally (storage rank vs entry count): the slab
    dim is replicated and the sharded axis lands on the right stored dim."""
    e = E.inner("add", "mul", E.psi((0,), E.arr("X", (2, 8, 8))),
                E.arr("B", (8, 8)))
    plan = dplan.derive_plan(e, MS8, shard={"i": "x"}, hardware=CPU)
    # X binds its FULL (2, 8, 8) storage: slab dim replicated, rows sharded
    assert plan.in_entries[0] == (None, "x", None)
    assert plan.in_entries[1] == (None, None)
    assert plan.out_entries == ("x", None)
    assert plan.collective == "none"
    # and the plan executes: sharded == single-device oracle
    devs = jax.devices()
    if len(devs) >= 8:
        from jax.sharding import Mesh
        from repro.kernels.emit import emit_shard_map
        x = jnp.arange(2 * 8 * 8, dtype=jnp.float32).reshape(2, 8, 8)
        b = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        with Mesh(np.array(devs[:8]), ("x",)) as m:
            got = emit_shard_map(plan, m, use_kernel=False)(x, b)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(x[0] @ b), atol=1e-4)


# ---------------------------------------------------------------------------
# multi-device matrix: sharded result == single-device oracle, and the
# jaxpr contains exactly the planned collectives
# ---------------------------------------------------------------------------

def _assert_planned_collectives_only(fn, args, collective):
    """The jaxpr pin: exactly the plan's collectives appear — no unplanned
    resharding transfer anywhere in the traced program."""
    assert not analysis.lint(fn, *args, rules=("only-planned-collectives",),
                             collective=collective), collective


def _run_matrix():
    """The acceptance matrix; callable in-process (8 devices) or from the
    subprocess runner below."""
    from repro.kernels import ops
    from repro.kernels.emit import emit_shard_map

    assert jax.device_count() >= 8, jax.device_count()
    m, k, n = 32, 48, 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    A = jax.random.randint(k1, (m, k), -4, 5).astype(jnp.float32)
    B = jax.random.randint(k2, (k, n), -4, 5).astype(jnp.float32)
    # integer-valued f32 inputs: every summation order yields the same exact
    # floats, so sharded == single-device is assert_array_equal, not allclose
    want = np.asarray(ops.matmul(A, B, out_dtype=jnp.float32))
    shards = {"row": {"m": "x"}, "col": {"n": "x"}, "sigma": {"k": "x"}}
    both_factors = {1: (1, 1), 2: (2, 1), 4: (2, 2), 8: (4, 2)}

    for p in (1, 2, 4, 8):
        for kind in ("row", "col", "both", "sigma"):
            if kind == "both":
                a, b = both_factors[p]
                mesh = jax.make_mesh((a, b), ("dx", "dy"),
                                     devices=jax.devices()[:p])
                shard = {"m": "dx", "n": "dy"}
            else:
                mesh = jax.make_mesh((p,), ("x",), devices=jax.devices()[:p])
                shard = shards[kind]
            plan = dplan.matmul_plan(m, k, n, mesh, shard=shard)
            expect = "psum" if kind == "sigma" else "none"
            assert plan.collective == expect, (p, kind, plan.collective)

            fn = lambda x, w: ops.matmul(x, w, mesh=mesh, shard=shard,
                                         out_dtype=jnp.float32)
            got = fn(A, B)
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=f"{p}x{kind}")
            _assert_planned_collectives_only(fn, (A, B), plan.collective)

    mesh8 = jax.make_mesh((8,), ("x",))
    # all-gather: row-sharded input, replicated output
    plan = dplan.matmul_plan(m, k, n, mesh8, shard={"m": "x"},
                             replicate_out=True)
    assert plan.collective == "all_gather"
    fn = lambda x, w: ops.matmul(x, w, mesh=mesh8, shard={"m": "x"},
                                 replicate_out=True, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(fn(A, B)), want)
    _assert_planned_collectives_only(fn, (A, B), "all_gather")

    # reduce-scatter: sigma-sharded with the output scattered over rows
    plan = dplan.matmul_plan(m, k, n, mesh8, shard={"k": "x"},
                             scatter_axis="m")
    assert plan.collective == "reduce_scatter"
    fn = emit_shard_map(plan, mesh8, out_dtype=jnp.float32, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(fn(A, B)), want)
    _assert_planned_collectives_only(fn, (A, B), "reduce_scatter")

    # non-divisible fallback: replicated, still exact
    mesh4 = jax.make_mesh((4,), ("x",), devices=jax.devices()[:4])
    got = ops.matmul(A[:30], B, mesh=mesh4, shard={"m": "x"},
                     out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), want[:30])

    # the derived interpret-mode Pallas kernel inside shard_map agrees too
    got = ops.apply(E.matmul_expr(m, k, n), A, B, interpret=True,
                    mesh=mesh8, shard={"i": "x"}, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), want)

    # expert parallelism through the same planning path
    X = jax.random.randint(k1, (8, 6, 12), -3, 4).astype(jnp.float32)
    W = jax.random.randint(k2, (8, 12, 10), -3, 4).astype(jnp.float32)
    wantE = np.asarray(ops.expert_matmul(X, W, out_dtype=jnp.float32))
    gotE = ops.expert_matmul(X, W, mesh=mesh8, shard={"e": "x"},
                             out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(gotE), wantE)

    # planned-mesh model routing: apply_mlp + the tied vocab head produce
    # exactly the single-device numbers (integer-valued params)
    from repro.models import layers
    from repro.models.common import ArchConfig
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=16,
                     n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=64,
                     tie_embeddings=True)
    meshdm = jax.make_mesh((4, 2), ("data", "model"))
    kp = jax.random.PRNGKey(7)
    p = {"wi": jax.random.randint(kp, (16, 64), -2, 3).astype(jnp.float32),
         "wo": jax.random.randint(kp, (32, 16), -2, 3).astype(jnp.float32)}
    x = jax.random.randint(kp, (8, 4, 16), -2, 3).astype(jnp.float32)
    base = np.asarray(layers.apply_mlp(p, x, cfg))
    with dplan.planned_mesh(meshdm):
        planned = np.asarray(layers.apply_mlp(p, x, cfg))
    # silu makes the hidden non-integer, so the derived TP psum's summation
    # order costs a few ulps — allclose here, exact for the linear head below
    np.testing.assert_allclose(planned, base, rtol=1e-4, atol=1e-3)
    params = {"embed": {"table":
                        jax.random.randint(kp, (64, 16), -2, 3)
                        .astype(jnp.float32)}}
    base_l = np.asarray(layers.logits_from_hidden(params, x, cfg))
    with dplan.planned_mesh(meshdm):
        planned_l = np.asarray(layers.logits_from_hidden(params, x, cfg))
    np.testing.assert_array_equal(planned_l, base_l)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (CI multi-device job sets "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_sharded_matmul_matrix_in_process():
    _run_matrix()


@pytest.mark.slow
def test_sharded_matmul_matrix_subprocess():
    """The same matrix under 8 forced host devices, so the single-device
    tier-1 run still covers it end to end."""
    if jax.device_count() >= 8:
        pytest.skip("covered by the in-process matrix test")
    prog = ("import sys; sys.path.insert(0, r'%s'); "
            "from test_distributed_plan import _run_matrix; _run_matrix(); "
            "print('SUBPROCESS_OK')" % os.path.join(ROOT, "tests"))
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


def _run_psi_offset_matrix():
    """Psi views with non-zero slab offsets through ``emit_shard_map``:
    every sharding kind, both the per-shard oracle and the derived
    interpret-mode kernel, exact against the sliced single-device matmul."""
    from repro.kernels import ops
    from repro.kernels.emit import emit_shard_map

    assert jax.device_count() >= 8, jax.device_count()
    s, m, k, n = 3, 16, 16, 8
    X = jax.random.randint(jax.random.PRNGKey(0), (s, m, k), -3, 4) \
        .astype(jnp.float32)
    B = jax.random.randint(jax.random.PRNGKey(1), (k, n), -3, 4) \
        .astype(jnp.float32)
    e = E.inner("add", "mul", E.psi((2,), E.arr("X", (s, m, k))),
                E.arr("B", (k, n)))
    want = np.asarray(X[2] @ B)
    mesh8 = jax.make_mesh((8,), ("x",))
    for shard, coll in [({"i": "x"}, "none"), ({"j": "x"}, "none"),
                        ({"k": "x"}, "psum")]:
        plan = dplan.derive_plan(e, mesh8, shard=shard)
        assert plan.collective == coll, (shard, plan.collective)
        oracle = emit_shard_map(plan, mesh8, use_kernel=False,
                                out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(oracle(X, B)), want,
                                      err_msg=f"oracle {shard}")
        _assert_planned_collectives_only(oracle, (X, B), coll)
        got = ops.apply(e, X, B, interpret=True, mesh=mesh8, shard=shard,
                        out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"kernel {shard}")
    plan = dplan.derive_plan(e, mesh8, shard={"i": "x"}, replicate_out=True)
    assert plan.collective == "all_gather"
    fn = emit_shard_map(plan, mesh8, use_kernel=False, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(fn(X, B)), want)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (CI multi-device job sets "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_psi_offset_matrix_in_process():
    _run_psi_offset_matrix()


@pytest.mark.slow
def test_psi_offset_matrix_subprocess():
    """The psi-offset matrix under 8 forced host devices, so the
    single-device tier-1 run covers it end to end."""
    if jax.device_count() >= 8:
        pytest.skip("covered by the in-process psi-offset matrix test")
    prog = ("import sys; sys.path.insert(0, r'%s'); "
            "from test_distributed_plan import _run_psi_offset_matrix; "
            "_run_psi_offset_matrix(); "
            "print('SUBPROCESS_OK')" % os.path.join(ROOT, "tests"))
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_planned_mesh_train_step_matches_unplanned():
    """make_train_step(planned_mesh=...) — the model's matmuls running
    through derived shard_map plans — reproduces the unplanned loss."""
    prog = """
import os
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.data import PipelineConfig, SyntheticLM
from repro.train import train_step as ts

cfg = get_config("stablelm-1.6b", reduced=True).with_(remat=False)
key = jax.random.PRNGKey(0)
data = SyntheticLM(PipelineConfig(cfg.vocab_size, 16, 8), cfg)
batch = jax.tree.map(jnp.asarray, data.global_batch(0))
state, _ = ts.init_state(cfg, key)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
_, m0 = jax.jit(ts.make_train_step(cfg))(state, batch)
_, m1 = jax.jit(ts.make_train_step(cfg, planned_mesh=mesh))(state, batch)
a, b = float(m0["loss"]), float(m1["loss"])
assert abs(a - b) < 5e-3, (a, b)
print("SUBPROCESS_OK", a, b)
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
