"""Serving-engine benchmark: a seeded Poisson request trace through the
continuous-batching engine on two archs (gemma-2b paged / mamba2-780m
contiguous), reduced configs on this host (interpret-mode kernels on the
paged path).  Each row replays the trace twice against one engine and
measures the second pass, so every row reports warm steady-state
serving rather than whichever share of trace/compile cost the row
ordering happened to leave it.

Writes ``BENCH_serve.json``: per-arch throughput (``tok_s_*`` — gated
inverse-tolerant), p50/p99 request latency and time-to-first-token
(``us_*`` — gated 3x-tolerant), plus the deterministic quantities CI pins
exactly: trace/engine shape (page size, pool pages, eviction count, token
counts) and the modeled decode-step HBM bytes/token from ``core.energy``
at the cache capacity — the "Racing to Idle" ledger for the decode path,
mirroring what ``BENCH_schedule.json`` does for training kernels.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.blocking import RecurrenceBlockChoice, StreamBlockChoice
from repro.core.energy import attention_energy, scan_energy
from repro.models import registry
from repro.models.ssm import conv_dim, d_inner, n_ssd_heads
from repro.serving import ServeEngine

ARCHS = ("gemma-2b", "mamba2-780m")
#: seeded Poisson trace: exponential interarrivals at RATE req/s (virtual
#: time — arrival timestamps are data, the engine replays them against its
#: wall clock), prompt/new-token extents drawn per request.  10 requests
#: against <= 4 slots keeps the engine SATURATED for most of the replay —
#: the regime continuous batching exists for, and the one where the
#: batched launch's dispatch amortization is visible rather than washed
#: out by a drained-tail engine running one or two live slots.  The rate
#: puts every interarrival in the nanoseconds, so the whole burst is
#: queued before the engine's FIRST step and admission is purely
#: queue-driven — deterministic whatever the wall clock does, so the
#: warm measured pass re-traces nothing (a rate where arrivals straddle
#: step boundaries makes slab assignment, and hence the executor keys,
#: timing-dependent)
TRACE = dict(seed=0, n_requests=10, rate=1e9, prompt_lo=4,
             prompt_hi=12, new_lo=6, new_hi=12)
MAX_LEN = 64
PAGE = 8
MAX_SLOTS = 2
JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_serve.json")


def poisson_trace(vocab: int) -> list[dict]:
    """The seeded request trace: deterministic given TRACE."""
    rng = np.random.default_rng(TRACE["seed"])
    t = 0.0
    reqs = []
    for _ in range(TRACE["n_requests"]):
        t += float(rng.exponential(1.0 / TRACE["rate"]))
        s0 = int(rng.integers(TRACE["prompt_lo"], TRACE["prompt_hi"] + 1))
        n_new = int(rng.integers(TRACE["new_lo"], TRACE["new_hi"] + 1))
        prompt = rng.integers(0, vocab, s0).tolist()
        reqs.append(dict(arrival=t, prompt=prompt, max_new=n_new))
    return reqs


def _modeled_hbm_per_token(cfg) -> float:
    """Modeled decode-step HBM bytes per generated token at cache
    capacity — one engine decode step across all layers."""
    if cfg.family == "dense":
        g = cfg.n_heads // cfg.n_kv_heads
        blocks = StreamBlockChoice(g, PAGE, 0, 0.0, 1.0)
        rep = attention_energy(1, cfg.n_heads, 1, MAX_LEN, cfg.head_dim_,
                               blocks, dtype=cfg.dtype)
        return cfg.n_layers * rep.hbm_bytes
    if cfg.family == "ssm":
        h, p, n = n_ssd_heads(cfg), cfg.ssm_head_dim, cfg.ssm_state
        rep = scan_energy(1, 1, h, p, n,
                          RecurrenceBlockChoice(1, 0, 0.0, 1.0),
                          dtype=cfg.dtype)
        return cfg.n_layers * rep.hbm_bytes
    raise ValueError(cfg.family)


def _run_pass(engine, trace: list[dict]) -> dict:
    """Replay the trace against the engine once; metrics for THIS pass
    only (the engine keeps its jitted executables across passes)."""
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0
    pending = list(trace)
    rids = []
    n_decoded = 0
    decode_t0 = None
    calls0 = engine.kernel_calls
    while pending or not engine.idle:
        now = clock()
        while pending and pending[0]["arrival"] <= now:
            req = pending.pop(0)
            rids.append(engine.submit(req["prompt"], req["max_new"],
                                      now=now))
        emitted = engine.step(clock(), clock)
        if emitted and decode_t0 is None:
            decode_t0 = clock()
        n_decoded += len(emitted)
        if not emitted and pending and engine.idle:
            # idle gap before the next arrival: jump the wall clock by
            # sleeping to the arrival (virtual rates are fast; this is ms)
            time.sleep(max(0.0, pending[0]["arrival"] - clock()))
    results = engine.results()
    return dict(rids=rids, n_decoded=n_decoded, wall=clock(),
                decode_t0=decode_t0,
                kernel_calls=engine.kernel_calls - calls0,
                requests=[results[r]["request"] for r in rids])


def _replay(cfg, params, trace: list[dict], max_slots: int = MAX_SLOTS,
            batched=None) -> dict:
    paged = cfg.family == "dense"
    engine = ServeEngine(cfg, params, max_slots=max_slots, max_len=MAX_LEN,
                         page=PAGE if paged else None,
                         batched=batched)
    # warm-up replay: pays every trace/compile once so the measured pass
    # is warm steady-state serving for EVERY row — without it, a row
    # inherits whichever executors earlier rows happened to share (the
    # module-level kernel caches are keyed on shapes + tables) and the
    # comparison across rows is cold-start lottery, not serving rate
    _run_pass(engine, trace)
    p = _run_pass(engine, trace)
    n_decoded, wall, decode_t0 = p["n_decoded"], p["wall"], p["decode_t0"]
    lat = sorted(r.done_t - r.submit_t for r in p["requests"])
    ttft = sorted(r.first_tok_t - r.submit_t for r in p["requests"])
    pct = lambda xs, p: float(np.percentile(xs, p))
    return {
        "arch": cfg.name,
        "paged": engine.paged,
        "batched": engine.batched,
        "page": engine.page,
        "pool_pages": engine.pool.pool_pages if engine.pool else 0,
        "max_slots": engine.max_slots,
        "n_requests": len(trace),
        "n_tokens": n_decoded,
        "evictions": sum(r.evictions for r in p["requests"]),
        "kernel_calls_per_token": p["kernel_calls"] / max(n_decoded, 1),
        "tok_s_decode": n_decoded / max(wall - (decode_t0 or 0.0), 1e-9),
        "us_p50_latency": pct(lat, 50) * 1e6,
        "us_p99_latency": pct(lat, 99) * 1e6,
        "us_p50_ttft": pct(ttft, 50) * 1e6,
        "us_p99_ttft": pct(ttft, 99) * 1e6,
        "modeled_hbm_bytes_per_token": _modeled_hbm_per_token(cfg),
    }


#: (arch, max_slots, batched) per row: the legacy 2-slot rows, plus the
#: per-slot vs batched pair at 4 slots — the dispatch-amortization claim
#: the batched slot lift makes, benched side by side
ROWS = (("gemma-2b", MAX_SLOTS, False),
        ("gemma-2b", 4, False),
        ("gemma-2b", 4, True),
        ("mamba2-780m", MAX_SLOTS, None))


def run() -> dict:
    out = {"trace": dict(TRACE), "max_len": MAX_LEN,
           "max_slots": MAX_SLOTS, "rows": []}
    for arch, max_slots, batched in ROWS:
        cfg = get_config(arch, reduced=True)
        params, _ = registry.init(cfg, jax.random.PRNGKey(0))
        row = _replay(cfg, params, poisson_trace(cfg.vocab_size),
                      max_slots=max_slots, batched=batched)
        out["rows"].append(row)
        print(f"{arch} slots={max_slots} batched={row['batched']}: "
              f"{row['n_tokens']} tok, "
              f"{row['tok_s_decode']:.1f} tok/s, "
              f"{row['kernel_calls_per_token']:.2f} kernel calls/tok, "
              f"p50 {row['us_p50_latency'] / 1e3:.1f}ms "
              f"p99 {row['us_p99_latency'] / 1e3:.1f}ms, "
              f"{row['modeled_hbm_bytes_per_token'] / 1e6:.2f} modeled "
              f"MB/token")
    with open(JSON_PATH, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {os.path.normpath(JSON_PATH)}")
    return out


if __name__ == "__main__":
    run()
