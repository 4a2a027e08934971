"""The static verifier: soundness proofs on known-good schedules, mutation
tests seeding one defect per class, the jaxpr lint rules, and the
verification cache contract."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.core import expr as E
from repro.core import hardware as hw
from repro.core import schedule as sched
from repro.core import semiring
from repro.distributed import plan as dplan
from repro.kernels import ops

HW = hw.get_entry("cpu")


def _rules(findings):
    return sorted({f.rule for f in findings if f.level == "error"})


def _gemm_bundle():
    # 300/200/160 are off every block multiple: padding on m, n AND k
    return sched.get_schedule(E.matmul_expr(300, 200, 160),
                              dtype="float32", hardware=HW)


# ---------------------------------------------------------------------------
# known-good derivations verify clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("form", [
    E.matmul_expr(300, 200, 160),
    E.matmul_expr(300, 200, 160, transpose_b=True),
    E.expert_gemm_expr(4, 60, 96, 72),
    E.hadamard_expr(200, 300),
    E.head_gemm_expr(4, 48, 32, 40),
    E.inner("max", "add", E.arr("A", (100, 60)), E.arr("B", (60, 80))),
    E.inner("min", "add", E.arr("A", (100, 60)), E.arr("B", (60, 80))),
    E.attention_form(1, 2, 2, 300, 300, 64),
    E.attention_stats_form(1, 1, 1, 300, 300, 64),
    E.attention_dq_form(1, 1, 1, 300, 300, 64),
    E.attention_dkv_form(1, 1, 1, 300, 300, 64),
    E.ssd_form(1, 4, 64, 2, 16, 16),
    E.ssd_bwd_form(1, 4, 64, 2, 16, 16),
    E.rglru_form(1, 4, 64, 32),
], ids=lambda f: getattr(f, "name", type(f).__name__))
def test_known_good_forms_verify_clean(form):
    bundle = sched.get_schedule(form, dtype="float32", hardware=HW)
    findings = analysis.verify_bundle(bundle, hardware=HW)
    assert not analysis.verify.errors(findings), [str(f) for f in findings]


def test_verify_expr_strict_passes_and_caches():
    analysis.reset_verification_cache()
    expr = E.matmul_expr(300, 200, 160)
    assert not analysis.verify_expr(expr, dtype="float32", hardware=HW)
    s1 = analysis.verification_cache_stats()
    assert not analysis.verify_expr(expr, dtype="float32", hardware=HW)
    s2 = analysis.verification_cache_stats()
    assert s2["hits"] == s1["hits"] + 1
    assert s2["misses"] == s1["misses"]


# ---------------------------------------------------------------------------
# mutation tests: one seeded defect, exactly that defect class flagged
# ---------------------------------------------------------------------------

def test_mutation_shifted_index_map_is_coverage_defect():
    b = _gemm_bundle()
    a0 = b.schedule.ins[0]                      # A's m dim is grid-driven
    mut = dataclasses.replace(a0, offsets=(1,) + a0.offsets[1:])
    s = dataclasses.replace(b.schedule, ins=(mut,) + b.schedule.ins[1:])
    findings = analysis.verify_bundle(dataclasses.replace(b, schedule=s),
                                      hardware=HW)
    assert _rules(findings) == ["coverage"]


def test_mutation_revisiting_grid_axis_is_race_defect():
    b = _gemm_bundle()
    # drop the declared reduction: the k grid axis still revisits the
    # output block every step — the Pallas write-write race
    s = dataclasses.replace(b.schedule, reduce_grid_dim=None)
    findings = analysis.verify_bundle(dataclasses.replace(b, schedule=s),
                                      hardware=HW)
    assert _rules(findings) == ["race"]


def test_mutation_parallel_reduce_axis_is_race_defect():
    b = _gemm_bundle()
    kd = b.schedule.reduce_grid_dim
    grid = tuple(dataclasses.replace(g, semantics="parallel")
                 if i == kd else g
                 for i, g in enumerate(b.schedule.grid))
    s = dataclasses.replace(b.schedule, grid=grid)
    findings = analysis.verify_bundle(dataclasses.replace(b, schedule=s),
                                      hardware=HW)
    assert _rules(findings) == ["race"]


def test_mutation_undersized_scratch_is_scratch_defect():
    b = _gemm_bundle()
    blk = dataclasses.replace(b.blocks, vmem_bytes=64)
    findings = analysis.verify_bundle(dataclasses.replace(b, blocks=blk),
                                      hardware=HW)
    assert _rules(findings) == ["scratch"]


def test_mutation_wrong_min_plus_pad_value_is_pad_value_defect(monkeypatch):
    bundle = sched.get_schedule(
        E.inner("min", "add", E.arr("A", (100, 60)), E.arr("B", (60, 80))),
        dtype="float32", hardware=HW)
    assert bundle.padded != bundle.shapes       # k=60 really is padded
    assert not analysis.verify.errors(
        analysis.verify_bundle(bundle, hardware=HW))
    # min-plus pads must be +inf; 0.0 contributes 0+0=0 to a min-reduce
    monkeypatch.setitem(semiring._PAD_VALUES, ("add", "min"), 0.0)
    findings = analysis.verify_bundle(bundle, hardware=HW)
    assert _rules(findings) == ["pad-value"]


def test_mutation_unregistered_pad_is_pad_guard_defect(monkeypatch):
    bundle = sched.get_schedule(
        E.inner("max", "add", E.arr("A", (100, 60)), E.arr("B", (60, 80))),
        dtype="float32", hardware=HW)
    monkeypatch.delitem(semiring._PAD_VALUES, ("add", "max"))
    findings = analysis.verify_bundle(bundle, hardware=HW)
    assert _rules(findings) == ["pad-guard"]


def test_mutation_dropped_stream_pad_guard_is_pad_guard_defect():
    b = sched.get_schedule(E.attention_form(1, 1, 1, 300, 300, 64),
                           dtype="float32", hardware=HW)
    assert b.padded[-1] != b.shapes[-1]         # sk=300 padded to the block
    # the emitter masks padded keys with a ``kpos < shapes[-1]`` guard;
    # recording the padded extent there drops the guard entirely
    mut = dataclasses.replace(b, shapes=b.shapes[:-1] + (b.padded[-1],))
    findings = analysis.verify_bundle(mut, hardware=HW)
    assert _rules(findings) == ["pad-guard"]


def test_mutation_oversized_working_set_is_resource_defect():
    b = _gemm_bundle()
    out = b.schedule.out
    fat = dataclasses.replace(
        out, block=(out.block[0] * 1024, out.block[1] * 1024),
        shape=(out.shape[0] * 1024, out.shape[1] * 1024))
    s = dataclasses.replace(b.schedule, out=fat)
    findings = analysis.verify_bundle(dataclasses.replace(b, schedule=s),
                                      hardware=HW)
    assert "resource" in _rules(findings)


# ---------------------------------------------------------------------------
# distributed plans: fallback warnings, widened shard accumulators,
# collective ordering
# ---------------------------------------------------------------------------

def test_plan_replication_fallback_warns_and_is_reported():
    from repro.core.mesh import MeshShape
    dplan.reset_plan_cache()
    with pytest.warns(dplan.ReplicationFallbackWarning, match="'i'"):
        plan = dplan.derive_plan(E.matmul_expr(31, 96, 32),
                                 MeshShape((("x", 2),)),
                                 shard={"i": "x"}, hardware=HW)
    assert plan.dropped == (("i", "x"),)
    findings = analysis.verify_plan(plan, hardware=HW)
    warns = [f for f in findings if f.rule == "replication-fallback"]
    assert len(warns) == 1 and warns[0].level == "warning"
    assert "'i'" in warns[0].message and "'x'" in warns[0].message
    assert not analysis.verify.errors(findings)


def test_plan_collective_order_mutation_is_flagged():
    dplan.reset_plan_cache()
    from repro.core.mesh import MeshShape
    plan = dplan.derive_plan(E.matmul_expr(64, 96, 32), MeshShape((("x", 2),)),
                             shard={"k": "x"}, hardware=HW)
    assert plan.collective == "psum"
    assert not analysis.verify.errors(analysis.verify_plan(plan, hardware=HW))
    # sequence a gather BEFORE the reduction: the gather replicates
    # partial sums — the ordering hazard the analyzer must flag
    bad = (dplan.CollectiveStep("all_gather", "x", 0),) + plan.collectives
    mut = dataclasses.replace(plan, collectives=bad)
    findings = analysis.verify_plan(mut, hardware=HW)
    assert "collective-order" in _rules(findings)


def test_plan_bundle_carries_widened_accumulator():
    dplan.reset_plan_cache()
    from repro.core.mesh import MeshShape
    plan = dplan.derive_plan(E.matmul_expr(64, 96, 32), MeshShape((("x", 2),)),
                             shard={"k": "x"}, hardware=HW,
                             dtype="bfloat16", acc_dtype="bfloat16")
    assert plan.bundle.acc_dtype == "bfloat16"
    findings = analysis.verify_plan(plan, hardware=HW, dtype="bfloat16")
    assert not analysis.verify.errors(findings)


def test_apply_mesh_accepts_acc_dtype():
    """Satellite: the PR-6 f32-only rejection on the sharded path is gone —
    bf16 accumulation threads through derive_plan's per-shard bundle and
    matches the single-chip result exactly."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (64, 96), jnp.bfloat16)
    w = jax.random.normal(k2, (96, 32), jnp.bfloat16)
    expr = E.matmul_expr(64, 96, 32)
    got = ops.apply(expr, x, w, mesh=mesh, shard={"k": "x"},
                    acc_dtype="bfloat16", interpret=True,
                    out_dtype=jnp.float32, verify=True)
    want = ops.apply(expr, x, w, acc_dtype="bfloat16", interpret=True,
                     out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_apply_verify_true_matches_and_caches():
    analysis.reset_verification_cache()
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (30, 20), jnp.float32)
    w = jax.random.normal(k2, (20, 40), jnp.float32)
    expr = E.matmul_expr(30, 20, 40)
    got = ops.apply(expr, x, w, interpret=True, verify=True)
    want = ops.apply(expr, x, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    before = analysis.verification_cache_stats()
    ops.apply(expr, x, w, interpret=True, verify=True)
    after = analysis.verification_cache_stats()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]


# ---------------------------------------------------------------------------
# the jaxpr lint rules
# ---------------------------------------------------------------------------

def test_lint_no_transpose_copy_clean_on_derived_kernel():
    fn = ops._expr_callable(E.matmul_expr(64, 32, 48, transpose_b=True),
                            "float32", "float32", "cpu", True)
    x = jnp.zeros((64, 32), jnp.float32)
    w = jnp.zeros((48, 32), jnp.float32)
    assert not analysis.lint(fn, x, w, rules=("no-transpose-copy",
                                              "no-silent-fallback"))


def test_lint_no_transpose_copy_flags_relayout():
    def relayout(x, w):
        return jnp.transpose(x) @ w

    x = jnp.zeros((8, 4), jnp.float32)
    w = jnp.zeros((8, 5), jnp.float32)
    findings = analysis.lint(relayout, x, w, rules=("no-transpose-copy",))
    assert _rules(findings) == ["no-transpose-copy"]


def test_lint_no_silent_fallback_flags_oracle_dispatch():
    def oracle(x, w):
        return x @ w

    x = jnp.zeros((8, 4), jnp.float32)
    findings = analysis.lint(oracle, x, jnp.zeros((4, 5), jnp.float32),
                             rules=("no-silent-fallback",))
    assert _rules(findings) == ["no-silent-fallback"]


def test_lint_only_planned_collectives():
    def plain(x):
        return x * 2.0

    x = jnp.zeros((4,), jnp.float32)
    assert not analysis.lint(plain, x, rules=("only-planned-collectives",),
                             collective="none")
    # a planned psum that never appears is as wrong as an unplanned one
    findings = analysis.lint(plain, x, rules=("only-planned-collectives",),
                             collective="psum")
    assert _rules(findings) == ["only-planned-collectives"]
    assert not analysis.lint(plain, x, rules=("only-planned-collectives",),
                             allowed=())


def test_lint_jaxpr_entry_and_strict_mode():
    def relayout(x):
        return jnp.transpose(x)

    jaxpr = jax.make_jaxpr(relayout)(jnp.zeros((3, 4), jnp.float32))
    findings = analysis.lint_jaxpr(jaxpr, rules=("no-transpose-copy",))
    assert findings
    with pytest.raises(analysis.LintError):
        analysis.lint_jaxpr(jaxpr, rules=("no-transpose-copy",), strict=True)
    with pytest.raises(KeyError, match="no-such-rule"):
        analysis.lint_jaxpr(jaxpr, rules=("no-such-rule",))


def test_lint_rule_registry_lists_all_four():
    names = [r.name for r in analysis.jaxpr_lint.lint_rules()]
    assert names == sorted(names)
    assert set(names) >= {"no-transpose-copy", "no-oracle-recompute",
                          "only-planned-collectives", "no-silent-fallback"}


def test_planned_prims_cover_ring_and_moe_collectives():
    """Satellite: the ROADMAP's ring-attention and MoE all-to-all plans are
    expressible as planned-collective summaries."""
    assert analysis.PLANNED_PRIMS["ppermute"] == frozenset({"ppermute"})
    assert analysis.PLANNED_PRIMS["all_to_all"] == frozenset({"all_to_all"})

    def plain(x):
        return x + 1.0

    x = jnp.zeros((4,), jnp.float32)
    # a planned ppermute/all_to_all that never appears is now a *known*
    # summary (one finding), not an unknown-summary parse error
    for summary in ("ppermute", "all_to_all"):
        findings = analysis.lint(plain, x,
                                 rules=("only-planned-collectives",),
                                 collective=summary)
        assert _rules(findings) == ["only-planned-collectives"]
        assert "never appears" in findings[0].message


def test_planned_collective_combined_summary_parsing():
    """``"a+b"`` summaries union their allowed prims; an unknown component
    anywhere in the chain is named in the finding."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))

    def ring(x):
        return jax.lax.ppermute(x, "x", [(0, 0)])

    fn = jax.shard_map(ring, mesh=mesh,
                       in_specs=jax.sharding.PartitionSpec("x"),
                       out_specs=jax.sharding.PartitionSpec("x"))
    x = jnp.zeros((4,), jnp.float32)
    # traced ppermute against its own plan: clean; against a combined
    # summary that does not include it: unplanned
    assert not analysis.lint(fn, x, rules=("only-planned-collectives",),
                             collective="ppermute")
    findings = analysis.lint(fn, x, rules=("only-planned-collectives",),
                             collective="reduce_scatter+all_gather")
    assert _rules(findings) == ["only-planned-collectives"]
    assert "ppermute" in findings[0].message

    def plain(x):
        return x * 2.0

    findings = analysis.lint(plain, x,
                             rules=("only-planned-collectives",),
                             collective="reduce_scatter+ring_exchange")
    assert _rules(findings) == ["only-planned-collectives"]
    assert "ring_exchange" in findings[0].message


# ---------------------------------------------------------------------------
# the registry sweep is importable and passes in-process
# ---------------------------------------------------------------------------

def test_verify_all_sweep_passes_and_pins_json_report(tmp_path):
    from repro.analysis import verify_all
    out = tmp_path / "verify_all.json"
    assert verify_all.main(["--json", str(out)]) == 0
    import json
    report = json.loads(out.read_text())
    assert report["sweep"] == "verify_all"
    assert report["failed"] == 0 and report["findings"] == []
    # pin the summary counts: silent registry shrinkage (a form, hardware
    # entry, or dtype pair dropping out of the sweep) fails loudly here
    assert len(report["hardware"]) == 5
    assert report["checked"] == 305
    assert report["refused"] == 140


def test_strict_verification_raises_with_findings():
    b = _gemm_bundle()
    s = dataclasses.replace(b.schedule, reduce_grid_dim=None)
    with pytest.raises(analysis.VerificationError, match="race"):
        analysis.verify_bundle(dataclasses.replace(b, schedule=s),
                               hardware=HW, strict=True)
