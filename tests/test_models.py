"""Per-architecture smoke + decode-cache consistency tests (reduced configs,
one forward/train step on CPU, asserting shapes and finiteness — full configs
are exercised only via the dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import registry

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, b=2, s=32, seed=1):
    kt = jax.random.PRNGKey(seed)
    batch = {"tokens": jax.random.randint(kt, (b, s), 0, cfg.vocab_size),
             "targets": jax.random.randint(kt, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["tokens"] = batch["tokens"][:, :s - cfg.num_patches]
        batch["targets"] = batch["targets"][:, :s - cfg.num_patches]
        batch["patches"] = jax.random.normal(
            kt, (b, cfg.num_patches, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            kt, (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_loss_finite(arch):
    cfg = get_config(arch, reduced=True)
    params, axes = registry.init(cfg, KEY)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    batch = make_batch(cfg)
    loss, metrics = jax.jit(lambda p, b: registry.loss(p, cfg, b))(params, batch)
    assert np.isfinite(float(loss))
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_grads_finite(arch):
    cfg = get_config(arch, reduced=True)
    params, _ = registry.init(cfg, KEY)
    batch = make_batch(cfg)
    g = jax.jit(jax.grad(lambda p: registry.loss(p, cfg, batch)[0]))(params)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf, np.float32)).all(), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch, reduced=True)
    params, _ = registry.init(cfg, KEY)
    b, cache_len = 2, 32
    cache = registry.init_cache(cfg, b, cache_len, dtype=jnp.float32)
    logits, new_cache = jax.jit(
        lambda p, t, pos, c: registry.decode_step(p, cfg, t, pos, c))(
        params, jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32), cache)
    assert logits.shape == (b, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


# ---------------------------------------------------------------------------
# decode-cache consistency: token-by-token decode == full forward
# ---------------------------------------------------------------------------

CONSISTENCY_ARCHS = ["command-r-plus-104b", "minicpm3-4b", "gemma-2b",
                     "stablelm-1.6b", "mamba2-780m", "recurrentgemma-9b",
                     "whisper-base", "granite-4.0-h-micro"]


@pytest.mark.parametrize("arch", CONSISTENCY_ARCHS)
def test_decode_matches_forward(arch):
    """Greedy caches must reproduce teacher-forced logits — validates every
    cache type (KV, latent MLA, SSM state, RG-LRU state, ring buffers,
    enc-dec cross attention, SSM state beside KV)."""
    cfg = get_config(arch, reduced=True).with_(remat=False)
    params, _ = registry.init(cfg, KEY)
    b, s = 2, 16
    batch = make_batch(cfg, b, s)
    toks = batch["tokens"]

    from repro.models import encdec, transformer
    from repro.models.layers import logits_from_hidden
    if cfg.family == "audio":
        enc = encdec.encode(params, cfg, batch["frames"])
        hidden, _ = encdec.decoder_forward(params, cfg, toks, enc)
        full_logits = logits_from_hidden(params, hidden, cfg)
        cache = encdec.init_encdec_cache(cfg, b, s, dtype=jnp.float32)
        cache = cache._replace(cross_kv=jax.vmap(
            lambda lp: encdec._cross_kv(lp, enc, cfg))(
            params["decoder"]["cross_attn"]))
        step = jax.jit(lambda t, pos, c: encdec.encdec_decode_step(
            params, cfg, t, pos, c))
    else:
        hidden, _, _ = transformer.forward(params, cfg, toks)
        full_logits = logits_from_hidden(params, hidden, cfg)
        cache = registry.init_cache(cfg, b, s, dtype=jnp.float32)
        step = jax.jit(lambda t, pos, c: registry.decode_step(
            params, cfg, t, pos, c))

    errs = []
    for t in range(s):
        logits, cache = step(toks[:, t], jnp.full((b,), t, jnp.int32), cache)
        errs.append(float(jnp.max(jnp.abs(
            logits - full_logits[:, t, :]))))
    assert max(errs) < 2e-2, (arch, errs)


def test_vlm_prefix_attention_is_bidirectional():
    cfg = get_config("paligemma-3b", reduced=True).with_(remat=False)
    params, _ = registry.init(cfg, KEY)
    from repro.models import transformer
    b, s = 1, 24
    batch = make_batch(cfg, b, s)
    h1, _, _ = transformer.forward(params, cfg, batch["tokens"],
                                   patches=batch["patches"])
    # permuting patch 0/1 must change position-0 patch outputs (bidir prefix)
    patches2 = batch["patches"].at[:, [0, 1]].set(batch["patches"][:, [1, 0]])
    h2, _, _ = transformer.forward(params, cfg, batch["tokens"], patches=patches2)
    assert float(jnp.max(jnp.abs(h1[:, 0] - h2[:, 0]))) > 1e-6


def test_causality_dense():
    """Future-token perturbation cannot change past logits."""
    cfg = get_config("stablelm-1.6b", reduced=True).with_(remat=False)
    params, _ = registry.init(cfg, KEY)
    from repro.models import transformer
    from repro.models.layers import logits_from_hidden
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, 12), 0, cfg.vocab_size)
    h1, _, _ = transformer.forward(params, cfg, toks)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % cfg.vocab_size)
    h2, _, _ = transformer.forward(params, cfg, toks2)
    np.testing.assert_allclose(np.asarray(h1[:, :-1], np.float32),
                               np.asarray(h2[:, :-1], np.float32), atol=1e-5)


def test_ssm_chunked_matches_tiny_chunks():
    """SSD chunk size must not change semantics (chunking = lifting)."""
    cfg = get_config("mamba2-780m", reduced=True).with_(remat=False)
    params, _ = registry.init(cfg, KEY)
    from repro.models import transformer
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, cfg.vocab_size)
    h1, _, _ = transformer.forward(params, cfg.with_(ssm_chunk=4), toks)
    h2, _, _ = transformer.forward(params, cfg.with_(ssm_chunk=16), toks)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), atol=2e-3)


def test_param_counts_match_analytic():
    """Analytic param_count (used for MODEL_FLOPS) vs real init, per arch."""
    import numpy as np
    for arch in ["gemma-2b", "stablelm-1.6b", "granite-4.0-h-micro"]:
        cfg = get_config(arch)
        total, _ = cfg.param_count()
        # reduced check at full scale is too big to init; verify the analytic
        # formula on the reduced config against its own init instead
        r = get_config(arch, reduced=True)
        params, _ = registry.init(r, KEY)
        got = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        want, _ = r.param_count()
        assert abs(got - want) / got < 0.15, (arch, got, want)


# ---------------------------------------------------------------------------
# attention-layer regressions (PR 4 bugfixes)
# ---------------------------------------------------------------------------

def _attn_setup(cfg, b=1, s=24, seed=3):
    from repro.models import attention as attn
    from repro.models.common import Collector
    col = Collector(jax.random.PRNGKey(seed), dtype=jnp.float32)
    attn.init_attention(col, "a", cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (b, s, cfg.d_model), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return col.params["a"], x, positions


def test_rope_applied_on_noncausal_attention(monkeypatch):
    """Regression: bidirectional (encoder) passes with rope_pct > 0 must
    rotate q and k — the old gate silently skipped RoPE when causal=False."""
    import repro.models.attention as attn_mod
    cfg = get_config("stablelm-1.6b", reduced=True).with_(remat=False)
    assert cfg.rope_pct > 0
    p, x, positions = _attn_setup(cfg)

    calls = []
    orig = attn_mod.apply_rope
    monkeypatch.setattr(attn_mod, "apply_rope",
                        lambda *a, **kw: (calls.append(1), orig(*a, **kw))[1])
    out_rope, _ = attn_mod.attention_fwd(p, x, cfg, positions=positions,
                                         causal=False)
    assert len(calls) == 2                       # q and k both rotated
    out_norope, _ = attn_mod.attention_fwd(p, x, cfg.with_(rope_pct=0.0),
                                           positions=positions, causal=False)
    # with the old bug both paths were identical (RoPE dropped)
    assert float(jnp.max(jnp.abs(out_rope - out_norope))) > 1e-4


def test_kv_cache_is_mask_independent():
    """K/V leaving attention_fwd feed the decode cache, whose masking DOES
    apply RoPE — so the cache must not depend on the masking mode.  Under
    the old gate, causal=False returned un-rotated keys while causal=True
    returned rotated ones."""
    import repro.models.attention as attn_mod
    cfg = get_config("stablelm-1.6b", reduced=True).with_(remat=False)
    assert cfg.rope_pct > 0
    p, x, positions = _attn_setup(cfg)
    _, kv_causal = attn_mod.attention_fwd(p, x, cfg, positions=positions,
                                          causal=True)
    _, kv_bidir = attn_mod.attention_fwd(p, x, cfg, positions=positions,
                                         causal=False)
    np.testing.assert_array_equal(np.asarray(kv_causal.k, np.float32),
                                  np.asarray(kv_bidir.k, np.float32))
    np.testing.assert_array_equal(np.asarray(kv_causal.v, np.float32),
                                  np.asarray(kv_bidir.v, np.float32))


def test_pallas_impl_runs_kernel_on_any_causal_shape(monkeypatch):
    """Regression: attn_impl="pallas" used to silently fall back to the jnp
    path off 512-multiples.  Now every causal full-sequence shape routes
    through ops.attention (the pad/slice wrapper) and matches the XLA path."""
    import repro.models.attention as attn_mod
    from repro.models import transformer

    calls = []
    orig = attn_mod.ops.attention
    monkeypatch.setattr(
        attn_mod.ops, "attention",
        lambda *a, **kw: (calls.append(1), orig(*a, **kw))[1])

    cfg = get_config("stablelm-1.6b", reduced=True).with_(remat=False,
                                                          head_dim=32)
    params, _ = registry.init(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 300), 0,
                              cfg.vocab_size)   # NOT a 512 multiple
    h_x, _, _ = transformer.forward(params, cfg.with_(attn_impl="xla"), toks)
    assert not calls
    h_p, _, _ = transformer.forward(params, cfg.with_(attn_impl="pallas"),
                                    toks)
    assert calls                                 # kernel path engaged
    assert float(jnp.max(jnp.abs(h_x - h_p))) < 5e-3


def test_noncausal_window_raises_on_dense_branch():
    """The honor-or-raise contract covers the materialized (_attend) branch
    too: short non-causal sequences with window/prefix_len must raise, not
    silently attend to everything."""
    import repro.models.attention as attn_mod
    cfg = get_config("stablelm-1.6b", reduced=True).with_(remat=False)
    p, x, positions = _attn_setup(cfg, s=8)   # far below attn_chunk_min_seq
    with pytest.raises(ValueError, match="causal"):
        attn_mod.attention_fwd(p, x, cfg, positions=positions,
                               causal=False, window=4)
    with pytest.raises(ValueError, match="causal"):
        attn_mod.attention_fwd(p, x, cfg, positions=positions,
                               causal=False, prefix_len=3)
