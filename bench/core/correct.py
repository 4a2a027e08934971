"""Whether what the timed path served is right: the served tokens against
the float32 reference, run once over each sampled request's prompt and
served tokens.

For each served token the number compared is how far its logit lies below
the reference's best logit at that position (0 when the reference would
have chosen it too); a run is correct when the widest such gap over the
sample is within the cell's limit.  The control reads the same gap for
the token that the fp8 reference puts first.
"""
from __future__ import annotations

import gc
import math

import jax
import numpy as np

from bench.core import weights
from bench.reference import module


def sample(logs, prompts, seed: int, min_tokens: int, max_requests: int):
    """Finished requests drawn from the seed: the one with the most served
    tokens, then others at random until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [l for l in logs if l.rid >= 0 and l.done]
    if not done:
        return []
    longest = max(done, key=lambda l: (len(l.tokens), l.n_prompt))
    rest = [l for l in done if l is not longest]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    picked, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        n += len(rest[i].tokens)
    return [(np.asarray(prompts[l.idx]), np.asarray(l.tokens)) for l in picked]


def _bucket(n: int, m: int) -> int:
    return int(math.ceil(n / m) * m)


def reference_logits(config: dict, seed: int, dtype, seqs: list,
                     quant: bool = False) -> list:
    """Logits of the positions that chose each served token: for each
    ``(prompt, served)`` pair, rows ``len(prompt) - 1 ...`` of the
    reference over ``prompt + served[:-1]``.  Weights are drawn again from
    the seed one layer at a time."""
    ref = module(config["family"])
    layout = ref.layout(config)
    heads = {p: weights.single(layout, seed, dtype, p)
             for p, _, _ in layout if not weights.stacked(p)}
    layer_fn = jax.jit(lambda w, x: ref.layer(config, w, x, quant))
    head_fn = jax.jit(lambda h, x: ref.head(config, h, x, quant))
    xs, spans = [], []
    for prompt, served in seqs:
        ids = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        t = _bucket(len(ids), ref.pad_to(config, len(ids)))
        padded = np.zeros(t, np.int32)
        padded[:len(ids)] = ids
        xs.append(jax.jit(lambda tab, i: ref.embed(config, tab, i))(
            heads["embed/table"], padded))
        spans.append((len(prompt) - 1, len(prompt) - 1 + len(served)))
    for l in range(ref.dims(config)["L"]):
        w = weights.layer(layout, seed, dtype, l)
        xs = [layer_fn(w, x) for x in xs]
        del w
    out = [np.asarray(head_fn(heads, x[lo:hi]), np.float32)
           for x, (lo, hi) in zip(xs, spans)]
    del xs, heads
    gc.collect()
    return out


def served_gap(ref_logits: list, seqs: list) -> float:
    """Widest gap between the reference's best logit and the logit of the
    token that was served."""
    gap = 0.0
    for lg, (_, served) in zip(ref_logits, seqs):
        got = lg[np.arange(len(served)), served]
        gap = max(gap, float(np.max(lg.max(-1) - got)))
    return gap


def chosen_gap(ref_logits: list, other_logits: list) -> float:
    """Widest gap of the tokens that ``other_logits`` put first."""
    gap = 0.0
    for lg, ol in zip(ref_logits, other_logits):
        pick = ol.argmax(-1)
        got = lg[np.arange(len(pick)), pick]
        gap = max(gap, float(np.max(lg.max(-1) - got)))
    return gap


def check(config: dict, seed: int, dtype, seqs: list, limit: float,
          vocab: int, control: bool = False) -> dict:
    """The comparison that decides ``correct``; with ``control`` also the
    control's gap on the same positions, judged by the same limit
    (``control_correct``, which has to come out false)."""
    if not seqs:        # nothing finished: nothing shown right
        return {"correct": False, "logit_gap": None, "limit": limit,
                "served_tokens": 0, "requests": 0}
    ids_ok = all(((s >= 0) & (s < vocab)).all() for _, s in seqs)
    with jax.default_matmul_precision("highest"):
        ref = reference_logits(config, seed, dtype, seqs)
        gap = served_gap(ref, seqs) if ids_ok else None
        out = {"correct": gap is not None and gap <= limit, "logit_gap": gap,
               "limit": limit, "served_tokens": int(sum(len(s) for _, s in seqs)),
               "requests": len(seqs)}
        if control:
            ctl = reference_logits(config, seed, dtype, seqs, quant=True)
            out["control_gap"] = chosen_gap(ref, ctl)
            out["control_correct"] = out["control_gap"] <= limit
    return out
