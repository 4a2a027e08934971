"""Serving subsystem: page-pool bookkeeping, paged-vs-contiguous kernel
bit-identity, page-bounds verification, the single-sweep prefill
regression, and continuous batching with recompute preemption."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_lint, verify
from repro.configs import get_config
from repro.core import expr as E
from repro.core import schedule as sched_mod
from repro.core.hardware import get_entry
from repro.kernels import ops
from repro.models import registry, transformer
from repro.serving import OutOfPages, PagePool, ServeEngine, pages_needed
from repro.train.serve_step import greedy_generate

CPU = get_entry("cpu")


@pytest.fixture(scope="module")
def gemma():
    cfg = get_config("gemma-2b", reduced=True)
    params, _ = registry.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def mamba():
    cfg = get_config("mamba2-780m", reduced=True)
    params, _ = registry.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


# -- page pool ---------------------------------------------------------------

def test_pages_needed():
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2
    assert pages_needed(64, 16) == 4


def test_pool_alloc_free_roundtrip(gemma):
    cfg, _ = gemma
    pool = PagePool(cfg, pool_pages=4, page=8)
    assert pool.free_pages == 4
    a = pool.alloc(2)
    assert a == [0, 1]                    # front-to-back on a fresh pool
    b = pool.alloc(1)
    assert b == [2] and pool.used_pages == 3
    pool.free([1])
    assert pool.alloc(1) == [1]           # lowest free slab reissues first
    with pytest.raises(OutOfPages):
        pool.alloc(2)                     # only slab 3 is free
    with pytest.raises(ValueError, match="outside pool"):
        pool.free([9])
    with pytest.raises(ValueError, match="double free"):
        pool.free([3])                    # 3 is already on the free stack


# -- paged decode kernel -----------------------------------------------------

def test_paged_decode_bit_identical_to_contiguous():
    """The same derived kernel through an identity table on a contiguous
    pool vs a scrambled table on a scattered pool: identical blocked
    compute order, so the outputs are bitwise equal on integer inputs."""
    hkv, g, hd, page, view_pages = 2, 4, 16, 8, 2
    sk = view_pages * page
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-3, 4, (hkv, g, hd)), jnp.float32)
    k = jnp.asarray(rng.integers(-3, 4, (sk, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.integers(-3, 4, (sk, hkv, hd)), jnp.float32)
    pos = jnp.asarray([[12, 0]], jnp.int32)

    # scatter the same pages into a larger pool, slabs (3, 1)
    pool_pages, perm = 4, (3, 1)
    k2 = jnp.zeros((pool_pages * page, hkv, hd), jnp.float32)
    v2 = jnp.zeros_like(k2)
    for vpg, slab in enumerate(perm):
        k2 = k2.at[slab * page:(slab + 1) * page].set(
            k[vpg * page:(vpg + 1) * page])
        v2 = v2.at[slab * page:(slab + 1) * page].set(
            v[vpg * page:(vpg + 1) * page])

    kw = dict(page=page, scale=hd ** -0.5, interpret=True, hardware=CPU)
    contig = ops.paged_decode(q, k, v, pos, page_table=(0, 1), **kw)
    paged = ops.paged_decode(q, k2, v2, pos, page_table=perm, **kw)
    assert np.array_equal(np.asarray(contig), np.asarray(paged))
    oracle = ops._paged_oracle(q, k, v, pos, (0, 1), page, hd ** -0.5, 0)
    np.testing.assert_allclose(np.asarray(contig), np.asarray(oracle),
                               atol=1e-5, rtol=1e-5)


def test_paged_decode_windowed_matches_oracle():
    hkv, g, hd, page = 1, 2, 8, 4
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(hkv, g, hd)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(16, hkv, hd)), jnp.float32)
    pos = jnp.asarray([[13, 0]], jnp.int32)
    kw = dict(page_table=(0, 1, 2, 3), page=page, scale=1.0, window=6)
    got = ops.paged_decode(q, kv, kv, pos, interpret=True, hardware=CPU,
                           **kw)
    want = ops._paged_oracle(q, kv, kv, pos, kw["page_table"], page, 1.0, 6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# -- static verification -----------------------------------------------------

def _paged_form(table=(0, 3, 1, 5), pool_pages=6):
    return E.windowed_decode_form(2, 4, 32, page=16, view_pages=4,
                                  pool_pages=pool_pages, page_table=table,
                                  window=32)


def test_verify_paged_form_clean():
    findings = verify.verify_expr(_paged_form(), dtype="float32",
                                  hardware=CPU, blocks=(4, 16),
                                  strict=False)
    assert not verify.errors(findings)


def test_verify_paged_form_kernel_body_conforms():
    """Serve-smoke pin for the PR-9 conformance rules: the paged decode
    kernel the engine binds passes the body checks (``kernel=True`` runs
    ``effect``/``acc-dtype``/``guard-dominance``/``state-discipline``
    alongside the schedule-layer rules)."""
    findings = verify.verify_expr(_paged_form(), dtype="float32",
                                  hardware=CPU, blocks=(4, 16),
                                  strict=False, kernel=True)
    assert not verify.errors(findings)
    banned = {"effect", "acc-dtype", "guard-dominance", "state-discipline"}
    assert not [f for f in findings if f.rule in banned]


def test_paged_form_refuses_out_of_pool_table():
    with pytest.raises(ValueError, match="outside the pool"):
        _paged_form(table=(0, 3, 1, 6))


def test_verify_schedule_flags_bad_page_table():
    """Tampering a derived schedule's page table past the slab pool is
    caught by the static verifier as a page-bounds error."""
    bundle = sched_mod.get_schedule(_paged_form(), dtype="float32",
                                    hardware=CPU, blocks=(4, 16))
    sched = bundle.schedule
    ins = tuple(
        dataclasses.replace(spec, page_table=(0, 3, 1, 99))
        if spec.page_table is not None else spec
        for spec in sched.ins)
    assert ins != sched.ins
    bad = dataclasses.replace(sched, ins=ins)
    errs = verify.errors(verify.verify_schedule(bad))
    assert errs and all(f.rule == "page-bounds" for f in errs)

    short = tuple(
        dataclasses.replace(spec, page_table=(0, 3))
        if spec.page_table is not None else spec
        for spec in sched.ins)
    errs = verify.errors(verify.verify_schedule(
        dataclasses.replace(sched, ins=short)))
    assert any(f.rule == "page-bounds" for f in errs)


# -- prefill regression ------------------------------------------------------

def test_greedy_generate_prefill_single_sweep(gemma, monkeypatch):
    """Prompt ingestion routes through ``registry.prefill`` — ONE derived
    kernel sweep — and ``decode_step`` traces only for the generation
    scan, never a token-by-token prompt feed."""
    cfg, params = gemma
    calls = {"prefill": 0, "decode": 0}
    real_prefill, real_decode = registry.prefill, registry.decode_step

    def count_prefill(*a, **kw):
        calls["prefill"] += 1
        return real_prefill(*a, **kw)

    def count_decode(*a, **kw):
        calls["decode"] += 1
        return real_decode(*a, **kw)

    monkeypatch.setattr(registry, "prefill", count_prefill)
    monkeypatch.setattr(registry, "decode_step", count_decode)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0,
                                cfg.vocab_size)
    out = greedy_generate(params, cfg, prompt, n_new=4, cache_len=16)
    assert out.shape == (1, 10)
    assert calls["prefill"] == 1
    assert calls["decode"] == 1           # the gen scan's single trace

    # the fallback feed-scan path produces the same tokens
    calls.update(prefill=0, decode=0)
    monkeypatch.setattr(transformer, "has_prefill_decode_relayout",
                        lambda _cfg: False)
    ref = greedy_generate(params, cfg, prompt, n_new=4, cache_len=16)
    assert calls["prefill"] == 0 and calls["decode"] == 2
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# -- engine ------------------------------------------------------------------

def test_engine_decode_binds_derived_kernel(gemma):
    """The engine's paged decode step binds the derived windowed_decode
    kernel through the page-table psi view — pinned by jaxpr lint: a
    pallas_call inside the layer scan, no oracle recompute, no silent
    fallback."""
    cfg, params = gemma
    engine = ServeEngine(cfg, params, max_slots=1, max_len=16, page=4,
                         interpret=True)
    assert engine.paged
    fn = engine._paged_decode_fn((0, 1))
    findings = jaxpr_lint.lint(
        fn, jnp.zeros((1,), jnp.int32), jnp.asarray([5], jnp.int32),
        engine.pool.pools,
        rules=("no-oracle-recompute", "no-silent-fallback"),
        min_calls=1)
    assert not findings, findings


def test_engine_eviction_under_pressure_matches_isolated(gemma):
    """Three concurrent requests against a pool too small for them all:
    the engine preempts (recompute eviction), and every request still
    decodes exactly what it would have alone."""
    cfg, params = gemma
    key = jax.random.PRNGKey(7)
    prompts = [jax.random.randint(k, (n,), 0, cfg.vocab_size).tolist()
               for k, n in zip(jax.random.split(key, 3), (5, 6, 4))]
    max_new = 5
    engine = ServeEngine(cfg, params, max_slots=3, max_len=16, page=4,
                         pool_pages=5, interpret=True)
    rids = [engine.submit(p, max_new) for p in prompts]
    results = engine.run()
    assert sum(r["request"].evictions for r in results.values()) > 0
    for rid, prompt in zip(rids, prompts):
        ref = greedy_generate(params, cfg,
                              jnp.asarray([prompt], jnp.int32),
                              n_new=max_new, cache_len=16)
        assert results[rid]["tokens"] == np.asarray(
            ref[0, len(prompt):]).tolist()


# -- batched multi-slot decode -----------------------------------------------

def _stacked_form(tables=((0, 3, 1, 5), (2, 4, 6, 7)), slots=2,
                  pool_pages=8):
    return E.batched_decode_form(slots, 2, 4, 32, page=16, view_pages=4,
                                 pool_pages=pool_pages,
                                 page_tables=tables, window=32)


def test_batched_decode_bit_identical_to_sequential():
    """One batched launch over N slots vs N sequential per-slot launches
    of the same derived kernel against the same pools: each (s, h) grid
    cell folds exactly the per-slot float ops, so live rows are bitwise
    equal on integer inputs; a dead row (pos -1) flushes exact zeros."""
    slots, hkv, g, hd, page, view = 3, 2, 4, 16, 8, 2
    pool_pages = 8
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.integers(-3, 4, (slots, hkv, g, hd)), jnp.float32)
    kp = jnp.asarray(rng.integers(-3, 4, (pool_pages * page, hkv, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.integers(-3, 4, kp.shape), jnp.float32)
    tables = ((5, 2), (0, 7), (3, 3))     # slot 2 is dead: stale entries
    pos = jnp.asarray([[11, 0], [4, 0], [-1, 0]], jnp.int32)

    kw = dict(page=page, scale=hd ** -0.5, window=6, interpret=True,
              hardware=CPU)
    got = ops.paged_decode_batched(q, kp, vp, pos, page_tables=tables,
                                   **kw)
    for s in range(slots):
        if int(pos[s, 0]) < 0:
            assert not np.asarray(got[s]).any()
            continue
        one = ops.paged_decode(q[s], kp, vp, pos[s:s + 1],
                               page_table=tables[s], **kw)
        assert np.array_equal(np.asarray(got[s]), np.asarray(one)), s


def test_stacked_form_refusals():
    with pytest.raises(ValueError, match="rows for"):
        _stacked_form(tables=((0, 1, 2, 3),), slots=2)
    with pytest.raises(ValueError, match="view_pages"):
        _stacked_form(tables=((0, 1, 2), (3, 4, 5, 6)))
    with pytest.raises(ValueError, match="outside the pool"):
        _stacked_form(tables=((0, 1, 2, 9), (3, 4, 5, 6)))


def test_verify_stacked_form_clean_and_tamperable():
    """The batched form passes the full static + kernel-body check; a
    tampered stacked row (out-of-pool slab, slot-labeled) and a dropped
    row (slot-grid mismatch) are both page-bounds errors."""
    form = _stacked_form()
    findings = verify.verify_expr(form, dtype="float32", hardware=CPU,
                                  blocks=(4, 16), strict=False,
                                  kernel=True)
    assert not verify.errors(findings)

    bundle = sched_mod.get_schedule(form, dtype="float32", hardware=CPU,
                                    blocks=(4, 16))
    sched = bundle.schedule
    bad = tuple(
        dataclasses.replace(spec, page_table=((0, 3, 1, 99), (2, 4, 6, 7)))
        if spec.page_table is not None else spec
        for spec in sched.ins)
    errs = verify.errors(verify.verify_schedule(
        dataclasses.replace(sched, ins=bad)))
    assert errs and all(f.rule == "page-bounds" for f in errs)
    assert any("slot 0" in f.message for f in errs)

    dropped = tuple(
        dataclasses.replace(spec, page_table=((0, 3, 1, 5),))
        if spec.page_table is not None else spec
        for spec in sched.ins)
    errs = verify.errors(verify.verify_schedule(
        dataclasses.replace(sched, ins=dropped)))
    assert any(f.rule == "page-bounds" for f in errs)


def test_engine_batched_iteration_binds_one_pallas_call(gemma):
    """The tentpole pin: one batched engine iteration traces to exactly
    ONE pallas_call — the slot axis rides the grid of a single derived
    kernel (shared across the layer scan), not a per-slot launch loop."""
    cfg, params = gemma
    engine = ServeEngine(cfg, params, max_slots=3, max_len=16, page=4,
                         interpret=True)
    assert engine.batched
    tables = tuple((0,) * engine._view_pages
                   for _ in range(engine.max_slots))
    fn = engine._batched_decode_fn(tables)
    jaxpr = jax.make_jaxpr(fn)(
        jnp.zeros((3,), jnp.int32),
        jnp.asarray([5, 2, -1], jnp.int32), engine.pool.pools)
    assert jaxpr_lint.jaxpr_primitives(jaxpr)["pallas_call"] == 1


def test_engine_batched_eviction_under_pressure_matches_isolated(gemma):
    """Four concurrent requests through the BATCHED path against a pool
    too small for them all: recompute preemption still fires, every
    request decodes exactly its isolated greedy tokens, and the launch
    count stays below one per token (the dispatch-amortization claim)."""
    cfg, params = gemma
    key = jax.random.PRNGKey(11)
    prompts = [jax.random.randint(k, (n,), 0, cfg.vocab_size).tolist()
               for k, n in zip(jax.random.split(key, 4), (5, 6, 4, 7))]
    max_new = 5
    engine = ServeEngine(cfg, params, max_slots=4, max_len=16, page=4,
                         pool_pages=7, interpret=True)
    assert engine.batched
    rids = [engine.submit(p, max_new) for p in prompts]
    results = engine.run()
    assert sum(r["request"].evictions for r in results.values()) > 0
    n_tokens = sum(len(r["tokens"]) for r in results.values())
    assert engine.kernel_calls < n_tokens
    for rid, prompt in zip(rids, prompts):
        ref = greedy_generate(params, cfg,
                              jnp.asarray([prompt], jnp.int32),
                              n_new=max_new, cache_len=16)
        assert results[rid]["tokens"] == np.asarray(
            ref[0, len(prompt):]).tolist()


def test_engine_contiguous_fallback_ssm(mamba):
    """Families without a paged KV view serve through one stacked
    contiguous cache under the same scheduler."""
    cfg, params = mamba
    engine = ServeEngine(cfg, params, max_slots=2, max_len=16)
    assert not engine.paged and engine.pool is None
    prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 6), 0,
                                cfg.vocab_size)
    rid = engine.submit(prompt[0].tolist(), 4)
    results = engine.run()
    ref = greedy_generate(params, cfg, prompt, n_new=4, cache_len=16)
    assert results[rid]["tokens"] == np.asarray(ref[0, 6:]).tolist()


def _param_constants(lowered, params) -> list:
    """Constants in the lowered program whose type is a parameter's."""
    import re
    text = lowered.as_text()
    consts = set(re.findall(r"stablehlo\.constant dense<[^>]*> : "
                            r"(tensor<[^>]*>)", text))
    types = {"tensor<" + "x".join(map(str, p.shape)) + "x"
             + {"float32": "f32", "bfloat16": "bf16"}[str(p.dtype)] + ">"
             for p in jax.tree.leaves(params) if p.size >= 64}
    return sorted(consts & types)


def test_engine_executables_take_params_as_arguments(gemma, mamba):
    """Every engine executable binds ``params`` as a jit argument: a
    closed-over array would be baked into the program as a constant, once
    per prompt length and page table (gigabytes at full width)."""
    for cfg, params in (gemma, mamba):
        engine = ServeEngine(cfg, params, max_slots=2, max_len=16, page=4,
                             interpret=True)
        engine.submit(list(range(1, 6)), 2)
        engine.run()
        toks = jnp.zeros((1, 5), jnp.int32)
        lowered = [engine._prefill_fns[5].lower(params, toks)]
        if engine.batched:
            fn = engine._batched_decode_fn(((0, 1), (0, 0)))
            rest = (jnp.zeros((2,), jnp.int32), jnp.asarray([4, -1]),
                    engine.pool.pools)
        else:
            fn = engine._contig_decode_fn()
            cache = transformer.init_cache(cfg, 2, 16)   # the stacked cache
            rest = (jnp.zeros((2,), jnp.int32), jnp.asarray([4, 0]), cache)
        assert fn.args == (params,)
        lowered.append(fn.func.lower(*fn.args, *rest))
        for low in lowered:
            args, _ = low.args_info
            assert jax.tree.structure(args[0]) == jax.tree.structure(params)
            assert not _param_constants(low, params), cfg.name


@pytest.mark.parametrize("arch", ["mamba2-780m", "stablelm-1.6b",
                                  "minicpm3-4b", "granite-4.0-h-micro"])
def test_engine_contiguous_one_launch_per_iteration(arch, monkeypatch):
    """Contiguous families (ssm, dense MHA, MLA, the Mamba-2/attention
    hybrid with recurrent state and K/V side by side) decode every live
    slot in ONE launch per iteration over the stacked cache, bucketed by
    occupancy: rows retire and are reused mid-run, a dead row sits inside
    a bucket, and a lone slot on row 0 launches one row.  Every request
    decodes exactly what it would have alone."""
    cfg = get_config(arch, reduced=True)
    params, _ = registry.init(cfg, jax.random.PRNGKey(0))
    rows = []
    real = ServeEngine._contig_decode_fn

    def spy(self):
        fn = real(self)

        def call(toks, poss, cache):
            assert {t.shape[1] for t in jax.tree.leaves(cache)} == {
                toks.shape[0]}
            rows.append(toks.shape[0])
            return fn(toks, poss, cache)
        return call
    monkeypatch.setattr(ServeEngine, "_contig_decode_fn", spy)
    engine = ServeEngine(cfg, params, max_slots=3, max_len=16)
    assert not engine.paged and not engine.batched
    key = jax.random.PRNGKey(11)
    # rows 0-2 fill; row 1 retires and is reused twice, then lies dead
    # under row 2; rid 0 (row 0) ends alone
    lengths, budgets = (5, 3, 7, 4, 6), (9, 2, 5, 2, 2)
    prompts = [jax.random.randint(k, (n,), 0, cfg.vocab_size).tolist()
               for k, n in zip(jax.random.split(key, 5), lengths)]
    rids = [engine.submit(p, n) for p, n in zip(prompts, budgets)]
    while not engine.idle:
        calls, launches = engine.kernel_calls, len(rows)
        engine.step()
        assert engine.kernel_calls - calls == len(rows) - launches == 1
    assert rows == [3, 3, 3, 3, 1, 1, 1, 1]
    results = engine.results()
    for rid, prompt, n in zip(rids, prompts, budgets):
        ref = greedy_generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                              n_new=n, cache_len=16)
        assert results[rid]["tokens"] == np.asarray(
            ref[0, len(prompt):]).tolist(), (arch, rid)


# -- engine spans, step names and request stamps -----------------------------

def _serve_steps(cfg, params, prompts, max_new, trace_dir=None):
    """Serve ``prompts`` step by step, under the profiler when
    ``trace_dir`` is given.  Returns the engine and, per step, the
    increase of ``kernel_calls`` and the rids whose first token came."""
    engine = ServeEngine(cfg, params, max_slots=3, max_len=16, page=4,
                         interpret=True)
    for p in prompts:
        engine.submit(p, max_new)
    steps, seen = [], set()
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        while not engine.idle:
            calls = engine.kernel_calls
            new = {rid for rid, _ in engine.step() if rid not in seen}
            seen |= new
            steps.append((engine.kernel_calls - calls, new))
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return engine, steps


def _read_trace(trace_dir):
    """The ``engine.*`` host spans ``(name, start, end, stats)`` and the
    ``hlo_module`` names of the traced ops."""
    import glob
    import os
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            for e in ln.events:
                stats = dict(e.stats)
                if e.name.startswith("engine."):
                    spans.append((e.name, e.start_ns, e.end_ns, stats))
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
    return spans, modules


@pytest.mark.parametrize("family", ["mamba", "gemma"])
def test_engine_spans_tree_and_served_tokens(family, request, tmp_path):
    """Each ``engine.step`` holds one ``engine.admit`` (with one
    ``engine.prefill`` per request admitted, ``n`` = tokens prefilled),
    exactly one ``engine.launch`` whose ``n`` is the step's
    increase of ``kernel_calls``, and at most one ``engine.sync`` followed
    by ``engine.emit``; the device work carries the steps' names; and the
    tokens served are the same with the profiler off."""
    cfg, params = request.getfixturevalue(family)
    key = jax.random.PRNGKey(3)
    prompts = [jax.random.randint(k, (n,), 0, cfg.vocab_size).tolist()
               for k, n in zip(jax.random.split(key, 4), (5, 6, 4, 7))]
    engine, steps = _serve_steps(cfg, params, prompts, 3, str(tmp_path))
    assert engine.batched == (family == "gemma")
    spans, modules = _read_trace(str(tmp_path))
    decode = "jit_engine_decode_batched" if engine.batched else "jit_engine_decode"
    assert {"jit_engine_prefill", decode} <= modules

    step_spans = sorted((s for s in spans if s[0] == "engine.step"),
                        key=lambda s: s[1])
    assert len(step_spans) == len(steps)
    n_children = 0
    for (_, a, b, _), (calls, admitted) in zip(step_spans, steps):
        inside = sorted((s for s in spans if s[0] != "engine.step"
                         and a <= s[1] and s[2] <= b), key=lambda s: s[1])
        n_children += len(inside)
        by = {}
        for s in inside:
            by.setdefault(s[0], []).append(s)
        assert len(by["engine.admit"]) == 1 and len(by["engine.launch"]) == 1
        assert len(by.get("engine.sync", [])) <= 1
        assert len(by.get("engine.emit", [])) == len(by.get("engine.sync", []))
        (admit,), (launch,) = by["engine.admit"], by["engine.launch"]
        assert launch[3]["n"] == calls
        prefills = by.get("engine.prefill", [])
        assert all(admit[1] <= p[1] and p[2] <= admit[2] for p in prefills)
        assert sorted(p[3]["n"] for p in prefills) == sorted(
            len(prompts[rid]) for rid in admitted)
        order = [s[0] for s in inside if s[0] != "engine.prefill"]
        assert order == ["engine.admit", "engine.launch", "engine.sync",
                         "engine.emit"][:len(order)]
    assert n_children + len(step_spans) == len(spans)   # none outside a step

    untraced, _ = _serve_steps(cfg, params, prompts, 3)
    assert {r: v["tokens"] for r, v in untraced.results().items()} == {
        r: v["tokens"] for r, v in engine.results().items()}


def test_engine_jitted_steps_are_named(gemma, mamba):
    """The jitted steps carry stable names, which the device trace shows
    as their module names (``jit_<name>``)."""
    cfg, params = mamba
    engine = ServeEngine(cfg, params, max_slots=2, max_len=16)
    engine.submit(list(range(1, 6)), 2)
    engine.run()
    assert engine._prefill_fns[5].__name__ == "engine_prefill"
    assert engine._contig_decode_fn().func.__name__ == "engine_decode"
    cfg, params = gemma
    engine = ServeEngine(cfg, params, max_slots=2, max_len=16, page=4,
                         interpret=True)
    assert engine._paged_decode_fn((0, 1)).func.__name__ == (
        "engine_decode_paged")
    fn = engine._batched_decode_fn(((0, 1), (0, 0)))
    assert fn.func.__name__ == "engine_decode_batched"
    lowered = fn.func.lower(*fn.args, jnp.zeros((2,), jnp.int32),
                            jnp.asarray([4, -1]), engine.pool.pools)
    assert "@jit_engine_decode_batched" in lowered.as_text()


def test_engine_stamps_tokens_on_the_callers_clock(mamba):
    """With a clock, a request admitted and prefilled in one step is
    stamped as it goes: ``first_tok_t`` once the token is on the host,
    after ``admit_t``; with none every stamp is the step's ``now``."""
    cfg, params = mamba
    ticks = iter(range(1, 1000))
    engine = ServeEngine(cfg, params, max_slots=2, max_len=16)
    rid = engine.submit(list(range(1, 6)), 2)
    engine.step(0.0, clock=lambda: float(next(ticks)))  # admit .. retire
    req = engine.results()[rid]["request"]
    assert 0.0 < req.admit_t < req.first_tok_t < req.done_t
    engine = ServeEngine(cfg, params, max_slots=2, max_len=16)
    rid = engine.submit(list(range(1, 6)), 2)
    engine.run(clock=lambda: float(next(ticks)))
    req = engine.results()[rid]["request"]
    assert req.admit_t < req.first_tok_t < req.done_t
    engine = ServeEngine(cfg, params, max_slots=2, max_len=16)
    rid = engine.submit(list(range(1, 6)), 2)
    engine.step(0.0)
    req = engine.results()[rid]["request"]
    assert req.admit_t == req.first_tok_t == req.done_t == 0.0
