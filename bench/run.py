"""Runs one benchmark cell once and prints its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and own settings are found by the
names in ``BENCHMARK.json`` (see ``bench/core/spec.py``).  Set-up makes
the weights on the device from the seed, warms every prompt length the
traffic will send and the decode step, and runs the cell's own traffic
until it is steady; then the window is measured for ``--seconds``.  After
the window the served tokens of a sample of finished requests are compared
with the float32 reference (``bench/core/correct.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` with
``--trace 1``), and last ``checks``, each number compared beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.core import correct, serve, spec, traffic, weights  # noqa: E402
from bench.reference import module  # noqa: E402


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What a per-layer metric's reader may read."""
    config: dict
    peaks: dict
    t0: float
    t1: float
    steps: list
    logs: list
    compiles: list
    trace: dict | None = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"need {n} TPU chip(s); JAX finds {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs


def build(cell: spec.Cell, rehearsal: bool):
    """(program config, bench config, engine settings, traffic scale).  A
    CPU rehearsal runs the registry's reduced config with the registry
    overrides and then the rehearsal's own; ``rehearsal.config`` gives the
    bench configuration's keys at that size."""
    from repro.configs import get_config
    reg = cell.config["registry"]
    if rehearsal:
        r = cell.own["rehearsal"]
        arch = get_config(reg["base"], reduced=True).with_(
            **reg["overrides"]).with_(**r.get("overrides", {}))
        config = dict(cell.config, **r["config"])
        eng = dict(max_slots=r["max_slots"], max_len=r["max_len"],
                   page=r.get("page"))
        return arch, config, eng, r
    arch = get_config(reg["base"]).with_(**reg["overrides"])
    return arch, cell.config, dict(cell.own["engine"]), None


def check_layout(arch, layout: list, key) -> None:
    """The reference's layout must be the program's parameter tree."""
    import jax
    from repro.models import registry
    shapes = jax.eval_shape(lambda k: registry.init(arch, k)[0], key)
    got = {p: (tuple(v.shape), str(v.dtype))
           for p, v in weights.flatten(shapes).items()}
    want = {p: (tuple(s), str(arch.dtype)) for p, s, _ in layout}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"reference layout differs from the program: {diff}")


def make_engine(cell: spec.Cell, seed: int, rehearsal: bool):
    """The cell's program config, bench config, engine settings, traffic
    scale and an engine serving weights drawn from ``seed``."""
    import jax
    import jax.numpy as jnp
    from repro.serving import ServeEngine
    arch, config, eng, scale = build(cell, rehearsal)
    layout = module(config["family"]).layout(config)
    dtype = jnp.dtype(arch.dtype)
    check_layout(arch, layout, jax.random.PRNGKey(0))
    params = weights.make(layout, seed, dtype)
    jax.block_until_ready(params)
    engine = ServeEngine(arch, params, max_slots=eng["max_slots"],
                         max_len=eng["max_len"], page=eng.get("page"),
                         dtype=dtype, interpret=True if rehearsal else None)
    return arch, config, eng, scale, engine


def warm(engine, lengths, vocab: int, rng, occupancy: int, contiguous: bool):
    """Compile what the window will run: a prefill of every prompt length,
    and the decode step at each occupancy the window can see (the
    contiguous path stacks one token per live slot)."""
    for n in sorted(set(lengths)):
        engine.submit(rng.integers(0, vocab, n).tolist(), 1)
        engine.step()
    if contiguous:
        n = min(lengths)
        for k in range(occupancy):
            engine.submit(rng.integers(0, vocab, n).tolist(), 2 + k)
        while not engine.idle:
            engine.step()


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool = False, control: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.cache import enable_compile_cache

    clock = time.perf_counter
    compiles = serve.CompileLog(clock)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = spec.cell(name)
    # a traced window is capped, on the same traffic: the profiler's device
    # events of a per-slot decode come at ~200k a second, and reading 51 s
    # of them back would outlast the run's time limit
    window_s = (min(seconds, float(cell.own.get("trace_seconds", seconds)))
                if trace else seconds)
    arch, config, eng, scale, engine = make_engine(cell, seed, rehearsal)
    dtype = jnp.dtype(arch.dtype)
    vocab = int(config["vocab_size"])     # ids of real tokens, not padding
    reqs = traffic.generate(cell.traffic, seed, seconds, vocab, scale)
    rng = np.random.default_rng([int(seed), 1])
    offline = cell.traffic["arrival"]["kind"] == "offline"
    warm(engine, [len(r.prompt) for r in reqs], vocab, rng,
         eng["max_slots"] if not offline else 1, not engine.batched)

    loop = serve.Loop(engine, reqs, clock, spans=trace)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def open_window():
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        return clock()

    if offline:
        for r in loop.logs:
            r.due = clock()
        loop.submit_due(clock())
        for _ in range(int(cell.own.get("warm_steps", 1))):
            loop.step()
        t0 = open_window()
        with loop.span("bench.window"):
            loop.run_steps(t0 + window_s)
    else:
        a0 = clock()
        for r in loop.logs:
            r.due += a0
        loop.run_open(a0 + float(cell.traffic.get("warm_s", 0.0)))
        t0 = open_window()
        with loop.span("bench.window"):
            loop.run_open(t0 + window_s)
    t1 = clock()
    setup_s = t0 - T_START
    win_compiles = compiles.between(t0, t1)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace_reduce
        tr = trace_reduce.from_xplane(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, _ = trace_reduce.window(tr)
        extra = [("compile", lo + (t - d - t0) * 1e9, lo + (t - t0) * 1e9)
                 for t, d in win_compiles]
        reduced = trace_reduce.reduce(tr, extra)

    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell.entry["chips"]]) if stats else 0
    steps = [s for s in loop.steps if s.start >= t0 and s.end <= t1]
    run = Run(config=config,
              peaks=spec.peaks(devs[0].device_kind) if not rehearsal
              else spec.peaks("TPU v5 lite"),
              t0=t0, t1=t1, steps=steps, logs=loop.logs,
              compiles=win_compiles, trace=reduced)
    lat = serve.window_latencies(loop.logs, t0, t1)
    if offline:
        attempted = sum(1 for l in loop.logs
                        if any(t0 <= t <= t1 for t in l.times))
    else:
        attempted = len(lat["ttft"])

    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = {
            "setup_s": lambda: setup_s,
            "out_tok_per_s": lambda: sum(s.tokens for s in steps) / run.seconds,
            "ttft_p95_ms": lambda: 1e3 * serve.percentile(lat["ttft"], 95),
            "itl_p95_ms": lambda: 1e3 * serve.percentile(lat["itl"], 95),
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]()),
                                  "unit": m["unit"]}

    # finish enough requests to compare: a minute past the close at most
    need = int(cell.own["check"]["sample_requests"])
    deadline = clock() + 60.0
    while (sum(1 for l in loop.logs if l.rid >= 0 and l.done) < need
           and clock() < deadline and not engine.idle):
        loop.step()
    # the program's state goes before the reference runs
    seqs = correct.sample(loop.logs, loop._prompts, seed,
                          int(cell.own["check"]["sample_tokens"]),
                          int(cell.own["check"]["sample_requests"]))
    loop.engine = None
    del engine
    gc.collect()
    verdict = correct.check(config, seed, dtype, seqs,
                            float(cell.own["check"]["logit_gap_limit"]),
                            arch.vocab_size, control=control)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": 0, "metrics": metrics, "device": device}
    if reduced is not None:
        from bench import trace_reduce
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["checks"] = {"logit_gap": {"value": verdict["logit_gap"],
                                      "limit": verdict["limit"]}}
    result["_verdict"] = verdict
    result["_window"] = {"compiles": len(win_compiles),
                         "compile_s": sum(d for _, d in win_compiles),
                         "tokens": sum(s.tokens for s in steps),
                         "steps": len(steps), "launches": sum(s.launches for s in steps),
                         "setup_s": setup_s}
    return result


def emit(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, then the result line as the last line of standard output."""
    verdict = result.pop("_verdict")
    result.pop("_window", None)
    print(f"sample: {verdict['requests']} requests, "
          f"{verdict['served_tokens']} served tokens", file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        require_chips(int(cell.entry["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    emit(run_cell(args.workload, args.seed, args.seconds, bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
