"""Layer-indexed decode GEMMs: ``ops.layer_matmul`` reads one layer of a
stacked weight in place, through a runtime psi slab (a scalar-prefetched
layer index), and the ssm and Mamba-2/attention decode bodies hand their
GEMMs the stacked leaves instead of sliced layers.

Kernel cases run the emitted Pallas kernel in interpret mode; integer-
valued operands make every f32 accumulation exact, so the in-place read
must equal ``ops.matmul`` on the sliced layer bit for bit, whatever the
K block.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import expr as E
from repro.core import schedule as sched_mod
from repro.core.hardware import get_entry
from repro.kernels import ops
from repro.kernels.emit import _shape_ok
from repro.models import registry, transformer
from repro.serving import ServeEngine

CPU = get_entry("cpu")


def _ints(key, shape, lo=-3, hi=4):
    return jax.random.randint(key, shape, lo, hi).astype(jnp.float32)


# (m, k, n, layers): a ragged N edge (700 over the 512 block, as granite's
# 8512 in-projection), a K the plain schedule pads (3072 under a solved
# 2048 block, as mamba2's out-projection), and an exact fit
SHAPES = [(5, 256, 700, 3), (16, 3072, 200, 2), (16, 384, 128, 2)]


@pytest.mark.parametrize("m,k,n,layers", SHAPES)
@pytest.mark.parametrize("k_minor", [False, True])
def test_layer_matmul_kernel_equals_sliced_matmul(m, k, n, layers, k_minor):
    x = _ints(jax.random.PRNGKey(0), (m, k)).astype(jnp.bfloat16)
    w = _ints(jax.random.PRNGKey(1), (layers, k, n)).astype(jnp.bfloat16)
    for layer in range(layers):                    # the last one included
        got = ops.layer_matmul(x, w, layer, k_minor=k_minor, interpret=True,
                               out_dtype=jnp.float32)
        want = ops.matmul(x, w[layer], interpret=True, out_dtype=jnp.float32)
        assert got.shape == (m, n)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n,layers", SHAPES)
def test_layer_matmul_xla_path_equals_sliced_matmul(m, k, n, layers):
    x = jax.random.normal(jax.random.PRNGKey(2), (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(3), (layers, k, n),
                          jnp.bfloat16)
    for layer in range(layers):
        got = ops.layer_matmul(x, w, layer, hardware=CPU)
        want = ops.matmul(x, w[layer], hardware=CPU)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("interpret", [True, None])
def test_layer_matmul_layer_traced_inside_scan(interpret):
    """The layer is a scan's carried index: one executable reads every
    layer, the trailing weight dims come back as the output's."""
    m, k, layers = 4, 256, 3
    x = _ints(jax.random.PRNGKey(4), (m, 1, k))
    w = _ints(jax.random.PRNGKey(5), (layers, k, 4, 32))

    def body(acc, layer):
        y = ops.layer_matmul(x, w, layer, interpret=interpret,
                             out_dtype=jnp.float32)
        return acc + y, y
    acc, ys = jax.jit(lambda: jax.lax.scan(
        body, jnp.zeros((m, 1, 4, 32)), jnp.arange(layers)))()
    want = jnp.stack([jnp.einsum("msk,kab->msab", x, w[i])
                      for i in range(layers)])
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want.sum(0)))


@pytest.mark.parametrize("k,bk,want", [(3072, 2048, 1536), (2048, 2048, 2048),
                                       (8192, 2048, 2048), (1536, 1536, 1536),
                                       (100, 2048, 100)])
def test_in_place_k_block_divides_k(k, bk, want):
    assert sched_mod.in_place_k_block(k, bk) == want


@pytest.mark.parametrize("k_minor", [False, True])
def test_in_place_schedule_reads_the_stacked_leaf_unpadded(k_minor):
    """The weight operand is a runtime slab: a leading layer dim of block
    1 driven by no grid axis, K unpadded (the block divides it), N left
    ragged for the kernel's edge block; only the activation pads, in M."""
    m, k, n = 16, 3072, 8512
    plain = sched_mod.get_schedule(
        E.matmul_expr(m, k, n, transpose_b=k_minor), dtype="bfloat16",
        hardware=CPU)
    bundle = sched_mod.get_schedule(
        E.matmul_expr(m, k, n, transpose_b=k_minor), dtype="bfloat16",
        hardware=CPU, in_place=("B",))
    assert bundle is not plain and plain.padded[-1] == 4096
    a, b = bundle.schedule.ins
    assert not a.runtime_slab and b.runtime_slab and b.is_psi_view
    assert (b.block[0], b.grid_dims[0], b.offsets[0]) == (1, None, 0)
    kpos = b.axes.index(a.axes[-1])
    assert b.shape[kpos] == k and k % b.block[kpos] == 0
    assert bundle.blocks.bk == 1536
    assert a.shape == (128, k)                      # M pads to the row block
    stored = (5, n, k) if k_minor else (5, k, n)        # bound as it is
    assert _shape_ok(stored, b)


def test_layer_slab_matmul_routes_in_place_and_counts():
    """``matmul`` on a ``LayerSlab`` is the in-place read; the counter
    sees it as such, a whole weight as whole, and a GEMM inside
    ``gemm_repeats(n)`` n times."""
    w = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 64, 32))
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 64))
    with ops.count_gemm_reads() as reads:
        slab = ops.layer_slab(w, 4, lead=2)                 # period 1, 2nd
        assert slab.shape == (64, 32) and not slab.k_minor
        got = ops.matmul(x, slab, hardware=CPU)
        with ops.gemm_repeats(5):
            ops.matmul(x, w[0, 0], hardware=CPU)
    assert reads == {"in_place": 1, "whole": 5}
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ops.matmul(x, w[1, 1],
                                                        hardware=CPU)))
    with ops.count_gemm_reads() as outer_reads:
        ops.matmul(x, w[0, 0], hardware=CPU)
    assert outer_reads == {"in_place": 0, "whole": 1}
    assert not ops.stored_k_minor((40, 2048, 8512), jnp.bfloat16)  # the CPU


def _sliced_decode_layer(tree, j, lead=1):
    """The decode body's layer as the parent program took it: every leaf
    sliced out of the stacked params."""
    return jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
        t.reshape((-1,) + t.shape[lead:]), j, keepdims=False), tree)


@pytest.mark.parametrize("arch", ["mamba2-780m", "granite-4.0-h-micro"])
def test_decode_step_in_place_equals_sliced_weights(arch, monkeypatch):
    """At the rehearsal sizes, ``decode_step`` reading the stacked leaves
    in place gives the logits and the cache of slicing each layer's
    weights first, and hands every layer GEMM the stacked leaf."""
    cfg = get_config(arch, reduced=True)
    params, _ = registry.init(cfg, jax.random.PRNGKey(0))
    cache = transformer.init_cache(cfg, 3, 16)
    toks = jnp.asarray([1, 2, 3], jnp.int32)
    pos = jnp.asarray([0, 3, 5], jnp.int32)
    cache = jax.tree.map(lambda t: t + 0.01 * jnp.ones_like(t), cache)

    def step():
        return jax.jit(lambda p, t, q, c: transformer.decode_step(
            p, cfg, t, q, c))(params, toks, pos, cache)
    with ops.count_gemm_reads() as reads:
        logits, new_cache = step()
    monkeypatch.setattr(transformer, "_decode_layer", _sliced_decode_layer)
    with ops.count_gemm_reads() as sliced_reads:
        want_logits, want_cache = step()
    n_gemms = reads["in_place"]
    assert reads == {"in_place": n_gemms, "whole": 1} and n_gemms > 0
    assert sliced_reads == {"in_place": 0, "whole": n_gemms + 1}
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    for got, want in zip(jax.tree.leaves(new_cache),
                         jax.tree.leaves(want_cache)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("arch,in_place", [("mamba2-780m", 96),
                                           ("granite-4.0-h-micro", 168)])
def test_decode_gemm_reads_at_full_width(arch, in_place):
    """Traced from shapes alone: every layer GEMM of the published stack
    reads in place (mamba2: 48 x 2; granite: 36 Mamba x 2, 4 attention x
    q/k/v/o, 40 MLPs x 2); only the tied head is handed a whole weight."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda k: registry.init(cfg, k)[0],
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: transformer.init_cache(cfg, 16, 64))
    toks = jax.ShapeDtypeStruct((16,), jnp.int32)
    with ops.count_gemm_reads() as reads:
        jax.eval_shape(lambda p, t, c: transformer.decode_step(
            p, cfg, t, t, c), params, toks, cache)
    assert reads == {"in_place": in_place, "whole": 1}


@pytest.mark.parametrize("arch,per_layer", [("mamba2-780m", 2),
                                            ("granite-4.0-h-micro", 4.2)])
def test_engine_records_decode_gemm_reads(arch, per_layer):
    cfg = get_config(arch, reduced=True)
    params, _ = registry.init(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_slots=2, max_len=16)
    assert engine.decode_gemm_reads is None
    engine.submit([1, 2, 3], 3)
    engine.run()
    assert engine.decode_gemm_reads == {
        "in_place": round(per_layer * cfg.n_layers), "whole": 1}
