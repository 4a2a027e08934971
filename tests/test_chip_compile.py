"""Compile the main-path kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler installed with JAX compiles each kernel for
a ``v5e:2x2`` topology that is described, not attached, and refuses what
the chip would refuse (tiling, VMEM, unlowerable primitives).  Each test
asserts the compiled program holds the Mosaic kernel (``tpu_custom_call``)
— interpret-mode tests on the CPU cannot see any of this.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.hardware import use_hardware
from repro.kernels import ops
from repro.models import registry, transformer


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # not under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described device cannot read a persistent-cache entry back; the
    # cache memoizes whether it is on, so reset it around the switch
    from jax.experimental.compilation_cache import compilation_cache
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with use_hardware("tpu_v5e"):
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,n,dtype,transpose_b", [
    (512, 2048, 16384, jnp.bfloat16, False),     # gemma-2b MLP up-proj
    (2048, 2048, 2048, jnp.float32, False),
    (8, 2048, 256000, jnp.bfloat16, True),       # gemma-2b tied head
])
def test_matmul_compiles(one_chip, m, k, n, dtype, transpose_b):
    w_shape = (n, k) if transpose_b else (k, n)
    text = _compiled_text(
        lambda x, w: ops.matmul(x, w, transpose_b=transpose_b,
                                out_dtype=jnp.float32),
        _sds(one_chip, (m, k), dtype), _sds(one_chip, w_shape, dtype))
    assert "tpu_custom_call" in text


def test_batched_decode_compiles(one_chip):
    """gemma-2b: 4 slots, one KV head, a group of 8, head_dim 256, the
    solved page.  The POS operand rides whole in SMEM — as a (1, 2) VMEM
    block of a (4, 2) array it broke the (8, 128) tiling rule."""
    page = ops.default_decode_page(1024, 1, 8, 256, dtype="bfloat16")
    tables = tuple((s,) for s in range(4))
    text = _compiled_text(
        lambda q, k, v, pos: ops.paged_decode_batched(
            q, k, v, pos, page_tables=tables, page=page, scale=256 ** -0.5),
        _sds(one_chip, (4, 1, 8, 256)), _sds(one_chip, (8 * page, 1, 256)),
        _sds(one_chip, (8 * page, 1, 256)),
        _sds(one_chip, (4, 2), jnp.int32))
    assert "tpu_custom_call" in text


def test_scan_ssd_compiles(one_chip):
    """mamba2-780m: 48 heads of 64, state 128, a 1024-token prompt."""
    f32 = jnp.float32
    text = _compiled_text(
        lambda x, a, b, c: ops.scan_ssd(x, a, b, c),
        _sds(one_chip, (1, 1024, 48, 64), f32),
        _sds(one_chip, (1, 1024, 48), f32),
        _sds(one_chip, (1, 1024, 128), f32),
        _sds(one_chip, (1, 1024, 128), f32))
    assert "tpu_custom_call" in text


def test_scan_ssd_backward_compiles(one_chip):
    """The SSD backward kernel (a train step's gradient through the scan)
    at mamba2-780m's widths, a 512-token sequence."""
    f32 = jnp.float32

    def grads(x, a, b, c):
        return jax.grad(lambda *t: ops.scan_ssd(*t)[0].sum(),
                        argnums=(0, 1, 2, 3))(x, a, b, c)
    text = _compiled_text(
        grads, _sds(one_chip, (1, 512, 48, 64), f32),
        _sds(one_chip, (1, 512, 48), f32), _sds(one_chip, (1, 512, 128), f32),
        _sds(one_chip, (1, 512, 128), f32))
    assert "tpu_custom_call" in text


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: 'vector types must have positive constant sizes but got 0, "
    "4096' — the strided slices of jax.lax.associative_scan in the gated "
    "kind's body do not lower"))
def test_gated_scan_compiles(one_chip):
    f32 = jnp.float32
    text = _compiled_text(lambda a, b: ops.gated_scan(a, b),
                          _sds(one_chip, (1, 1024, 4096), f32),
                          _sds(one_chip, (1, 1024, 4096), f32))
    assert "tpu_custom_call" in text


@pytest.mark.xfail(strict=True, reason=(
    "Pallas TPU: 'the last two dimensions of your block shape [must be] "
    "divisible by 8 and 128 respectively, or be equal to the respective "
    "dimensions of the overall array' — the Q block (1, bq, 1, 1, hd) over "
    "the stored (B, Sq, Hkv, G, hd) layout lifts G onto the grid; a block "
    "must keep G whole"))
@pytest.mark.parametrize("kv,g,hd", [(1, 8, 256), (8, 4, 128)])
def test_flash_attention_compiles(one_chip, kv, g, hd):
    text = _compiled_text(
        lambda q, k, v: ops.attention(q, k, v, scale=hd ** -0.5),
        _sds(one_chip, (1, 1024, kv, g, hd)), _sds(one_chip, (1, 1024, kv, hd)),
        _sds(one_chip, (1, 1024, kv, hd)))
    assert "tpu_custom_call" in text


def test_matmul_under_mesh_compiles(topo):
    """Inside a multi-device ``with mesh:`` block (the sharded train
    step's context) the kernel GEMM runs per shard through its derived
    plan: the SPMD partitioner cannot split a Mosaic kernel."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = _sds(NamedSharding(mesh, P("data", None)), (512, 2048))
    w = _sds(NamedSharding(mesh, P(None, "model")), (2048, 5632))
    with mesh:
        text = _compiled_text(
            lambda a, b: ops.matmul(a, b, out_dtype=jnp.float32), x, w)
    assert "tpu_custom_call" in text


#: what a decode launch must not stage in HBM: a slice, copy or pad of a
#: layer GEMM weight (one layer's, one period's for the hybrid, or the
#: whole leaf relaid out, in either orientation of each layer).  A
#: result in memory space 1 (VMEM, ``S(1)`` in its layout) is XLA
#: prefetching a small leaf whole into the chip's scratchpad, not staging.
_STAGING = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\](\S*) (\S+?)\(")


def _weight_shapes(params: dict, lead: int) -> set:
    shapes = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params["layers"]):
        if path[-1].key not in transformer._GEMM_WEIGHTS:
            continue
        per_layer = leaf.shape[lead:]
        k, n = per_layer[0], math.prod(per_layer[1:])
        layers = (math.prod(leaf.shape[:lead]),)
        for view in (per_layer, (1,) + per_layer, leaf.shape[1:],
                     (1,) + leaf.shape[1:], leaf.shape, layers + per_layer,
                     (k, n), (n, k), layers + (k, n), layers + (n, k)):
            shapes.add(tuple(view))
    return shapes


def _staged_weights(text: str, weights: set) -> list:
    found = []
    for line in text.splitlines():
        hit = _STAGING.match(line)
        if not hit or not hit.group(2):
            continue
        name, shape, layout, op = hit.groups()
        shape = tuple(int(d) for d in shape.split(","))
        staging = any(s in name or s == op
                      for s in ("copy", "pad", "slice", "dynamic-slice"))
        if staging and shape in weights and "S(1)" not in layout:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("arch,n_layers,batch,lead", [
    ("granite-4.0-h-micro", 20, 16, 2),     # two periods of ten
    ("mamba2-780m", 2, 64, 1),
])
def test_decode_step_reads_weights_in_place(topo, one_chip, arch, n_layers,
                                            batch, lead):
    """The decode step at published widths (cut in depth only): every
    layer GEMM reads its stacked leaf in place, so the compiled module
    holds no slice, copy or pad of a weight's per-layer or per-period
    shape, and the hybrid's B = 16 step stages no weights in its
    temporaries (1.58 GB when each period's weights were sliced out)."""
    cfg = get_config(arch).with_(n_layers=n_layers)
    params = jax.eval_shape(lambda k: registry.init(cfg, k)[0],
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: transformer.init_cache(cfg, batch, 256))
    on_chip = lambda t: _sds(one_chip, t.shape, t.dtype)
    params, cache = jax.tree.map(on_chip, (params, cache))
    toks = _sds(one_chip, (batch,), jnp.int32)
    # the weights' stored layouts are the described chip's defaults
    with jax.default_device(topo.devices[0]), ops.count_gemm_reads() as reads:
        lowered = jax.jit(lambda p, t, c: transformer.decode_step(
            p, cfg, t, t, c)).lower(params, toks, cache)
    assert reads["whole"] == 1 and reads["in_place"] > 0
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _staged_weights(text, _weight_shapes(params, lead)) == []
    if batch == 16:
        assert compiled.memory_analysis().temp_size_in_bytes < 1.58e9
