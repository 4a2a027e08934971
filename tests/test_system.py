"""End-to-end behaviour tests for the whole system."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_resume_from_checkpoint_is_bitwise_consistent(tmp_path):
    """Train 6 steps; train 3 + restart + 3 from checkpoint: same params.
    This is the node-failure recovery guarantee."""
    from repro.configs import get_config
    from repro.checkpoint import Checkpointer
    from repro.data import PipelineConfig, SyntheticLM
    from repro.train import train_step as ts

    cfg = get_config("gemma-2b", reduced=True).with_(remat=False)
    key = jax.random.PRNGKey(0)
    data = SyntheticLM(PipelineConfig(cfg.vocab_size, 16, 4), cfg)
    step = jax.jit(ts.make_train_step(cfg))

    def run(state, lo, hi):
        for s in range(lo, hi):
            state, _ = step(state, jax.tree.map(jnp.asarray, data.global_batch(s)))
        return state

    straight, _ = ts.init_state(cfg, key)
    straight = run(straight, 0, 6)

    st, _ = ts.init_state(cfg, key)
    st = run(st, 0, 3)
    ck = Checkpointer(str(tmp_path))
    ck.save(3, st, metadata={"data_step": 3})
    restored, man = ck.restore(st)
    resumed = run(restored, man["metadata"]["data_step"], 6)

    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_serve_driver_end_to_end():
    from repro.launch.serve import main
    prev = jax.config.jax_compilation_cache_dir
    try:
        results = main(["--arch", "gemma-2b", "--reduced", "--requests", "2",
                        "--prompt-len", "4", "--new-tokens", "4",
                        "--max-slots", "2", "--page", "4"])
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert len(results) == 2
    assert all(len(r["tokens"]) == 4 for r in results.values())


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    """Unset, the entry points keep JAX's persistent cache at the fixed
    ``<repo>/.jax_cache``; set, ``JAX_COMPILATION_CACHE_DIR`` wins and
    no other directory is configured in code."""
    from repro.launch import cache
    default = os.path.join(ROOT, ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cache.compile_cache_dir() == default
        assert cache.enable_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
        jax.config.update("jax_compilation_cache_dir", prev)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_greedy_generation_is_deterministic():
    from repro.configs import get_config
    from repro.models import registry
    from repro.train.serve_step import greedy_generate
    cfg = get_config("stablelm-1.6b", reduced=True)
    params, _ = registry.init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.ones((2, 4), jnp.int32)
    a = greedy_generate(params, cfg, prompt, 6, 16)
    b = greedy_generate(params, cfg, prompt, 6, 16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """The real dry-run path: 512 forced devices, production mesh, lower +
    compile + roofline for one cheap cell on both meshes."""
    prog = textwrap.dedent("""
        from repro.launch import dryrun
        rec = dryrun.run_cell("whisper-base", "train_4k", "single", None)
        assert rec["status"] == "OK", rec
        assert rec["roofline"]["global_flops"] > 0
        assert rec["n_chips"] == 256
        rec2 = dryrun.run_cell("whisper-base", "train_4k", "multi", None)
        assert rec2["status"] == "OK", rec2
        assert rec2["n_chips"] == 512
        print("SUBPROCESS_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_input_specs_cover_all_cells():
    """Every (arch x shape) cell has well-formed abstract inputs."""
    from repro.configs import SHAPES, all_cells, cell_applicable, get_config
    from repro.models import registry
    n_ok = n_skip = 0
    for arch, shape in all_cells():
        ok, why = cell_applicable(arch, shape)
        if not ok:
            n_skip += 1
            assert "full-attention" in why
            continue
        cfg = get_config(arch)
        specs = registry.input_specs(cfg, SHAPES[shape])
        leaves = jax.tree.leaves(specs)
        assert leaves and all(hasattr(l, "shape") for l in leaves)
        n_ok += 1
    assert n_ok + n_skip == 44
    assert n_skip == 8


@pytest.mark.slow
def test_elastic_restart_different_mesh():
    """Train on a (4,2) mesh, checkpoint, restore onto a (2,4) mesh and keep
    training: the elastic re-shard path must preserve semantics exactly
    (same data order via the pure-function pipeline)."""
    prog = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import Checkpointer
        from repro.configs import get_config
        from repro.data import PipelineConfig, SyntheticLM
        from repro.distributed import sharding as sr
        from repro.launch.mesh import make_host_mesh
        from repro.train import train_step as ts

        cfg = get_config("stablelm-1.6b", reduced=True).with_(remat=False)
        key = jax.random.PRNGKey(0)
        data = SyntheticLM(PipelineConfig(cfg.vocab_size, 16, 8), cfg)
        step_fn = ts.make_train_step(cfg)

        def put(state, mesh):
            axes = ts.state_logical_axes(state, p_axes)
            sh = sr.param_shardings(state, axes, mesh)
            return jax.tree.map(jax.device_put, state, sh), sh

        # straight-through on one mesh
        mesh_a = make_host_mesh(dp=4, tp=2)
        with mesh_a:
            state, p_axes = ts.init_state(cfg, key)
            state, _ = put(state, mesh_a)
            step = jax.jit(step_fn)
            for s in range(4):
                state, m = step(state, jax.tree.map(jnp.asarray, data.global_batch(s)))
            straight = jax.tree.map(np.asarray, state.params)

        # train 2 on mesh A, checkpoint, restore on mesh B, train 2 more
        with mesh_a:
            state, _ = ts.init_state(cfg, key)
            state, _ = put(state, mesh_a)
            step = jax.jit(step_fn)
            for s in range(2):
                state, _ = step(state, jax.tree.map(jnp.asarray, data.global_batch(s)))
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(2, state, metadata={"data_step": 2})

        mesh_b = make_host_mesh(dp=2, tp=4)            # DIFFERENT mesh
        with mesh_b:
            like, _ = ts.init_state(cfg, jax.random.PRNGKey(1))
            axes = ts.state_logical_axes(like, p_axes)
            sh = sr.param_shardings(like, axes, mesh_b)
            state_b, man = ck.restore(like, shardings=sh)
            step_b = jax.jit(step_fn)
            for s in range(man["metadata"]["data_step"], 4):
                state_b, _ = step_b(state_b, jax.tree.map(jnp.asarray, data.global_batch(s)))
            resumed = jax.tree.map(np.asarray, state_b.params)

        for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
            np.testing.assert_allclose(a.astype(np.float32), b.astype(np.float32),
                                       atol=2e-4)
        print("SUBPROCESS_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
