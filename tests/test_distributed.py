"""Distributed layer: sharding rules (in-process) + multi-device collective
matmul equivalence (subprocess with 8 forced host devices, so the main test
process keeps seeing exactly 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import sharding as sr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(shape, names):
    devs = np.array(jax.devices()[:1]).reshape((1,) * len(shape))
    # fake multi-axis mesh over 1 device is not possible; use abstract sizes
    # by constructing a mesh only when sizes are all 1 — rule tests below use
    # a synthetic Mesh via jax.make_mesh on 1 device for (1,1) only.
    raise NotImplementedError


class FakeMesh:
    """Duck-typed mesh (axis_names + devices.shape) for rule testing without
    actual devices."""
    def __init__(self, sizes: dict):
        self.axis_names = tuple(sizes)
        import numpy as _np
        self.devices = _np.empty(tuple(sizes.values()), dtype=object)
        self.empty = False


MESH = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_param_rules_fsdp_plus_tp():
    spec = sr.param_spec(("d_model", "d_ff"), (12288, 33792), MESH)
    assert spec == P(("pod", "data"), "model")


def test_param_rules_divisibility_fallback():
    # 40 heads don't divide 16-way model axis -> replicate that dim
    spec = sr.param_spec(("d_model", "heads", None), (2560, 40, 96), MESH)
    assert spec == P(("pod", "data"),)


def test_param_rules_mesh_axis_used_once():
    spec = sr.param_spec(("experts", "d_model", "moe_ff"), (64, 2048, 1408), MESH)
    assert spec == P("model", ("pod", "data"))   # moe_ff loses to experts


def test_act_rules_batch_and_kv():
    spec = sr.act_spec(("batch", "kv_seq", "kv_heads", None),
                       (128, 32768, 8, 128), MESH)
    assert spec == P(("pod", "data"), "model")
    # batch=1 (long_500k): falls back to replication, seq takes model
    spec = sr.act_spec(("batch", "kv_seq", "kv_heads", None),
                       (1, 524288, 8, 128), MESH)
    assert spec == P(None, "model")


def test_act_rules_seq_parallel():
    spec = sr.act_spec(("batch", "seq_sp", None), (256, 4096, 12288), MESH)
    assert spec == P(("pod", "data"), "model")


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.distributed import collectives as cl

    mesh = jax.make_mesh((8,), ("x",))
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    X = jax.random.normal(k1, (64, 32), jnp.float32)     # rows sharded
    W = jax.random.normal(k2, (32, 16), jnp.float32)

    ag = shard_map(lambda x, w: cl.ag_matmul(x, w, "x"), mesh=mesh,
                   in_specs=(P("x", None), P(None, None)),
                   out_specs=P(None, None), check_vma=False)
    ref = shard_map(lambda x, w: cl.reference_ag_matmul(x, w, "x"), mesh=mesh,
                    in_specs=(P("x", None), P(None, None)),
                    out_specs=P(None, None), check_vma=False)
    got, want = ag(X, W), ref(X, W)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-4), "ag_matmul"
    assert np.allclose(np.asarray(want), np.asarray(X @ W), atol=1e-4)

    X2 = jax.random.normal(k1, (48, 64), jnp.float32)    # k sharded
    W2 = jax.random.normal(k2, (64, 24), jnp.float32)
    ps = shard_map(lambda x, w: cl.psum_matmul(x, w, "x"), mesh=mesh,
                   in_specs=(P(None, "x"), P("x", None)),
                   out_specs=P(None, None), check_vma=False)
    got2 = ps(X2, W2)
    assert np.allclose(np.asarray(got2), np.asarray(X2 @ W2), atol=1e-3), "psum_matmul"
    print("SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_collective_matmuls_multi_device():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_pjit_sharded_train_step_multi_device():
    """8-device pjit train step with lifting-derived shardings runs and the
    loss matches the 1-device result (sharding must not change semantics)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.data import PipelineConfig, SyntheticLM
        from repro.distributed import sharding as sr
        from repro.launch.mesh import make_host_mesh
        from repro.train import train_step as ts

        cfg = get_config("stablelm-1.6b", reduced=True).with_(remat=False)
        key = jax.random.PRNGKey(0)
        data = SyntheticLM(PipelineConfig(cfg.vocab_size, 16, 8), cfg)
        batch = jax.tree.map(jnp.asarray, data.global_batch(0))

        losses = {}
        for dp, tp in [(1, 1), (4, 2)]:
            mesh = make_host_mesh(dp=dp, tp=tp)
            with mesh:
                state, axes = ts.init_state(cfg, key)
                st_axes = ts.state_logical_axes(state, axes)
                sh = sr.param_shardings(state, st_axes, mesh)
                state = jax.tree.map(jax.device_put, state, sh)
                step = jax.jit(ts.make_train_step(cfg))
                _, m = step(state, batch)
                losses[(dp, tp)] = float(m["loss"])
        a, b = losses[(1, 1)], losses[(4, 2)]
        assert abs(a - b) < 5e-3, losses
        print("SUBPROCESS_OK", losses)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
