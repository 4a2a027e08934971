"""The float32 reference of the Mamba-2/attention hybrid against the
program, on one whole period at the cell's CPU size, with weights drawn by
the benchmark: prefill, the forward->decode re-layout and decode steps
through the cache agree with the reference's full forward; the layers
drawn again are bit for bit those of ``weights.make``; and the published
order and multipliers are what both follow, since moving the attention
layer or dropping a multiplier in the program breaks the agreement."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.core import spec, weights
from bench.reference import module

CELL = "granite-h.doc_decode"
SEED = 5
PROMPT, TOTAL = 16, 22          # prefill 16 positions, then 6 decode steps
#: both sides in float32 at the highest matmul precision: what is left is
#: the order of the sums (the chunked SSD scan against the step-by-step
#: recurrence, blocked GEMMs, online softmax) on logits that spread by
#: about 1; the widest difference read 1.7e-5
TOL = 2e-4
#: what each altered program must differ by at least, 10 tolerances
BROKEN = 10 * TOL


def _setup(**program):
    arch, config, _, _ = run.build(spec.cell(CELL), rehearsal=True)
    arch = arch.with_(dtype="float32", remat=False, **program)
    ref = module(config["family"])
    layout = ref.layout(config)
    run.check_layout(arch, layout, jax.random.PRNGKey(0))
    return arch, config, ref, layout, weights.make(layout, SEED, jnp.float32)


def _ref_logits(config, ref, layout, ids):
    heads = {p: weights.single(layout, SEED, jnp.float32, p)
             for p, _, _ in layout if not weights.stacked(p)}
    block = ref.pad_to(config, len(ids))
    padded = np.zeros(-(-len(ids) // block) * block, np.int32)
    padded[:len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        x = ref.embed(config, heads["embed/table"], padded)
        for l in range(ref.dims(config)["L"]):
            x = ref.layer(config, weights.layer(layout, SEED, jnp.float32, l),
                          x, False)
        return np.asarray(ref.head(config, heads, x[:len(ids)], False))


def _program_logits(arch, params, ids):
    """Logits of positions PROMPT - 1 ... TOTAL - 1: the prefill's last,
    then each decode step's through the re-laid cache."""
    from repro.models import registry, transformer
    with jax.default_matmul_precision("highest"):
        lg, cache = registry.prefill(params, arch,
                                     {"tokens": jnp.asarray(ids[None, :PROMPT])})
        out = [np.asarray(lg[0])]
        dc = transformer.prefill_cache_to_decode(arch, cache, 32)
        step = jax.jit(lambda t, p, c: registry.decode_step(params, arch, t,
                                                            p, c))
        for t in range(PROMPT, TOTAL):
            lg, dc = step(jnp.asarray([ids[t]]), jnp.asarray([t]), dc)
            out.append(np.asarray(lg[0]))
    return np.stack(out)


@pytest.fixture(scope="module")
def reference():
    arch, config, ref, layout, _ = _setup()
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], TOTAL)
    return ids, _ref_logits(config, ref, layout, ids)[PROMPT - 1:]


# chunked: the prefill's attention through the chunked path the chip's
# prompts take (from 2048 tokens there), in blocks of 8 queries and keys
@pytest.mark.parametrize("attention", ["whole", "chunked"])
def test_prefill_and_decode_match_reference(reference, attention):
    ids, want = reference
    chunk = (dict(attn_chunk_min_seq=8, attn_q_chunk=8, attn_chunk=8)
             if attention == "chunked" else {})
    arch, _, _, _, params = _setup(**chunk)
    got = _program_logits(arch, params, ids)
    assert np.std(want) > 0.3                       # logits spread
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("altered", [
    "attention_moved", "no_residual_multiplier", "no_attention_multiplier",
    "no_embedding_multiplier", "no_logits_scaling"])
def test_published_order_and_multipliers_are_what_match(reference, altered):
    ids, want = reference
    arch, _, _, _, _ = _setup()
    pattern = list(arch.layer_pattern)
    pattern.insert(2, pattern.pop(pattern.index("attn")))
    change = {"attention_moved": dict(layer_pattern=tuple(pattern)),
              "no_residual_multiplier": dict(residual_multiplier=1.0),
              "no_attention_multiplier": dict(attention_multiplier=0.0),
              "no_embedding_multiplier": dict(embedding_multiplier=0.0),
              "no_logits_scaling": dict(logits_scaling=1.0)}[altered]
    arch, _, _, _, params = _setup(**change)
    got = _program_logits(arch, params, ids)
    assert np.max(np.abs(got - want)) > BROKEN, altered


def test_layers_drawn_again_are_bit_identical():
    _, config, ref, layout, params = _setup()
    flat = weights.flatten(params)
    assert ref.dims(config)["L"] == 1               # one whole period
    for l in range(ref.dims(config)["L"]):
        one = weights.layer(layout, SEED, jnp.float32, l)
        assert set(one) == {p for p, _, _ in layout if weights.stacked(p)}
        for path, v in one.items():
            assert np.array_equal(np.asarray(v), np.asarray(flat[path][l])), path
    for path in (p for p, _, _ in layout if not weights.stacked(p)):
        assert np.array_equal(np.asarray(weights.single(layout, SEED,
                                                        jnp.float32, path)),
                              np.asarray(flat[path]))


def test_costs_count_a_period_per_layer_call():
    """The harness multiplies one ``layer_gemms`` by ``dims()["L"]``: at
    the chip's size that is the published parameter count (3.19 B with the
    tied table), and the decode bytes a slot are its f32 state and conv
    tails plus its K/V up to the context."""
    from bench.core import costs
    config = spec.cell(CELL).config
    assert module(config["family"]).dims(config)["L"] == 4
    assert costs.weight_params(config) == 3_190_292_480
    state = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    kv = 4 * 2 * 8 * 64 * 2
    assert costs.slot_bytes(config, 6000) == 2 * state + kv * 6001
