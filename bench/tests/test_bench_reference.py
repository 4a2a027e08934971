"""The float32 reference against the program's prefill and decode logits
at a small size on the CPU, with the tied head the cell serves and with an
untied one, on weights drawn by the benchmark; and the weights drawn again
layer by layer, bit for bit."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.core import spec, weights
from bench.reference import module

CELL = "mamba2.decode"
HEADS = {"tied": True, "untied": False}


def _setup(head="tied", seed=3):
    arch, config, _, _ = run.build(spec.cell(CELL), rehearsal=True)
    arch = arch.with_(dtype="float32", tie_embeddings=HEADS[head])
    # the program's own settings: the departures the configuration lists
    # (norm eps) are the chip comparison's to see, not this one's
    config = dict(config, tie_embeddings=HEADS[head], published={})
    ref = module(config["family"])
    layout = ref.layout(config)
    run.check_layout(arch, layout, jax.random.PRNGKey(0))
    params = weights.make(layout, seed, jnp.float32)
    return arch, config, ref, layout, params


def _ref_logits(config, ref, layout, seed, ids):
    heads = {p: weights.single(layout, seed, jnp.float32, p)
             for p, _, _ in layout if not weights.stacked(p)}
    t = -(-len(ids) // ref.pad_to(config, len(ids))) * ref.pad_to(config, len(ids))
    padded = np.zeros(t, np.int32)
    padded[:len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        x = ref.embed(config, heads["embed/table"], padded)
        for l in range(ref.dims(config)["L"]):
            x = ref.layer(config, weights.layer(layout, seed, jnp.float32, l),
                          x, False)
        return np.asarray(ref.head(config, heads, x[:len(ids)], False))


@pytest.mark.parametrize("head", sorted(HEADS))
def test_reference_matches_prefill_and_decode(head):
    from repro.models import registry, transformer
    arch, config, ref, layout, params = _setup(head)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, config["vocab_size"], 20)
    want = _ref_logits(config, ref, layout, 3, ids)
    with jax.default_matmul_precision("highest"):
        got, cache = registry.prefill(params, arch, {"tokens": jnp.asarray(ids[None, :16])})
        np.testing.assert_allclose(np.asarray(got[0]), want[15], atol=2e-3, rtol=2e-3)
        dc = transformer.prefill_cache_to_decode(arch, cache, 64)
        for t in range(16, 20):
            lg, dc = registry.decode_step(params, arch, jnp.asarray([ids[t]]),
                                          jnp.asarray([t]), dc)
            np.testing.assert_allclose(np.asarray(lg[0]), want[t],
                                       atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("head", sorted(HEADS))
def test_layers_drawn_again_are_bit_identical(head):
    _, config, ref, layout, params = _setup(head, seed=2**40 + 9)
    flat = weights.flatten(params)
    for l in range(ref.dims(config)["L"]):
        one = weights.layer(layout, 2**40 + 9, jnp.float32, l)
        for path, v in one.items():
            assert np.array_equal(np.asarray(v), np.asarray(flat[path][l])), path
    for path in (p for p, _, _ in layout if not weights.stacked(p)):
        assert np.array_equal(np.asarray(weights.single(layout, 2**40 + 9,
                                                        jnp.float32, path)),
                              np.asarray(flat[path]))


def test_seeds_draw_different_weights():
    _, _, _, layout, a = _setup(seed=1)
    b = weights.make(layout, 2, jnp.float32)
    x, y = weights.flatten(a), weights.flatten(b)
    assert not np.array_equal(np.asarray(x["embed/table"]), np.asarray(y["embed/table"]))
