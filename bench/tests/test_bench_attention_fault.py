"""The correctness check sees what the attention layers of the
Mamba-2/attention hybrid add: with their decode K/V zeroed as each request
is admitted (its recurrent state kept), the cell's CPU rehearsal must come
out ``correct: false``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import run

CELL = "granite-h.doc_decode"
SEED = 2**31 + 77


def test_attention_kv_zeroed_after_admission_fails(monkeypatch):
    from repro.models import transformer
    relayout = transformer.prefill_cache_to_decode

    def zeroed(cfg, cache, cache_len):
        out = relayout(cfg, cache, cache_len)
        return dict(out, attn=jax.tree.map(jnp.zeros_like, out["attn"]))
    monkeypatch.setattr(transformer, "prefill_cache_to_decode", zeroed)
    r = run.run_cell(CELL, SEED, 1.0, False, rehearsal=True)
    assert r["_window"]["tokens"] > 0
    assert r["correct"] is False
    assert r["_verdict"]["logit_gap"] > r["_verdict"]["limit"]
