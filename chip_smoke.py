"""Smoke test of the system's main paths on the chip.

    python3 chip_smoke.py             # one TPU chip: serve + kernel oracles
    python3 chip_smoke.py --chips 4   # four chips: sharded train + matmul
    JAX_PLATFORMS=cpu python3 chip_smoke.py --reduced   # CPU rehearsal

One chip (the default) runs three phases in one process:

* gemma-2b at full width (18 layers, d_model 2048, vocab 256000, bf16,
  random weights from ``--seed``) serves 6 requests through
  ``ServeEngine`` on the paged, batched decode path: prompts of 128 and
  384 tokens, 32 new tokens each;
* mamba2-780m at full width (48 layers) serves 4 requests through the
  same engine on its contiguous path (SSD prefill);
* the kernels just served — batched paged decode, the SSD scan and one
  GEMM — against their oracles at the served shapes, each compiled program
  holding a ``tpu_custom_call`` and tracing a ``pallas_call`` (the
  ``no-silent-fallback`` lint rule).

``--chips 4`` instead runs only the multi-chip path and its comparison:
three ``train_step``s of stablelm-1.6b at full width (4 layers) on a
``dp=2, tp=2`` mesh against the same steps on one device, and a
contraction-sharded ``ops.matmul`` against the one-device result.

Each phase prints one line of facts.  The last line of standard output is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
Without a TPU the script exits 2 and prints no result, unless
``--reduced`` asks for the CPU rehearsal (reduced configs, Pallas
interpret mode).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MAX_SLOTS = 4
MAX_LEN = 1024
NEW_TOKENS = 32
GEMMA_PROMPTS = (128, 384, 128, 384, 128, 384)
MAMBA_PROMPTS = (128, 384, 128, 384)
TRAIN_STEPS = 3


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def fact(**kw) -> None:
    print(" ".join(f"{k}={v}" for k, v in kw.items()), flush=True)


class CompileClock:
    """Seconds XLA spent in backend compilation since construction."""

    def __init__(self):
        import jax
        self.total = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += duration
        jax.monitoring.register_event_duration_secs_listener(listen)

    def lap(self) -> float:
        t, self.total = self.total, 0.0
        return t


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def close(got, want, rel: float) -> float:
    """Max abs error over the reference's max magnitude; raises above
    ``rel``."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))
    check(err <= rel, f"relative error {err:.3e} over {rel:.0e}")
    return err


# ---------------------------------------------------------------------------
# one chip: serving + kernels
# ---------------------------------------------------------------------------

def serve(arch: str, prompts: tuple, args, clock: CompileClock):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import registry
    from repro.serving import ServeEngine

    cfg = get_config(arch, reduced=args.reduced)
    params, _ = registry.init(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, max_slots=MAX_SLOTS, max_len=MAX_LEN,
                         dtype=jnp.dtype(cfg.dtype))
    rng = np.random.default_rng(args.seed)
    rids = [engine.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                          NEW_TOKENS) for n in prompts]
    t0 = time.perf_counter()
    results = engine.run(clock=lambda: time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    for rid in rids:
        toks = results[rid]["tokens"]
        check(len(toks) == NEW_TOKENS,
              f"{arch} request {rid} got {len(toks)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"{arch} request {rid} emitted an id outside the vocabulary")
    # the engine's own prefill executable (cached, no new compile): its
    # logits are the values every first token was sampled from
    logits, _ = engine._prefill(list(range(prompts[0])))
    check(logits.shape == (1, cfg.vocab_size)
          and bool(jnp.isfinite(logits).all()), f"{arch} prefill logits")
    fact(phase="serve", model=cfg.name, layers=cfg.n_layers,
         d_model=cfg.d_model, vocab=cfg.vocab_size, dtype=cfg.dtype,
         path=("paged-batched" if engine.batched else
               "paged" if engine.paged else "contiguous"),
         page=engine.page if engine.paged else "-",
         requests=len(rids), prompt_lengths=",".join(map(str, prompts)),
         tokens_served=sum(len(results[r]["tokens"]) for r in rids),
         decode_launches=engine.kernel_calls,
         compile_s=f"{clock.lap():.1f}", wall_s=f"{wall:.1f}",
         peak_bytes_in_use=peak_bytes())
    return cfg, engine


def kernel_check(name: str, fn, oracle, args_, rel: float, on_tpu: bool,
                 clock: CompileClock) -> None:
    """Run ``fn`` against ``oracle`` on ``args_``; the traced program must
    reach ``pallas_call`` and, on a TPU, compile to a ``tpu_custom_call``."""
    import jax
    from repro import analysis

    findings = analysis.lint(fn, *args_, rules=("no-silent-fallback",))
    check(not findings, f"{name}: {findings}")
    jitted = jax.jit(fn)
    if on_tpu:
        hlo = jitted.lower(*args_).compile().as_text()
        check("tpu_custom_call" in hlo, f"{name}: no tpu_custom_call")
    err = close(jitted(*args_), jax.jit(oracle)(*args_), rel)
    fact(phase="kernel", kernel=name,
         shapes="/".join("x".join(map(str, a.shape)) for a in args_),
         rel_err=f"{err:.2e}", tol=f"{rel:.0e}", tpu_custom_call=on_tpu,
         compile_s=f"{clock.lap():.1f}")


def kernels(gemma, gemma_page: int, mamba, on_tpu: bool,
            clock: CompileClock, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    dt = jnp.dtype(gemma.dtype)

    # batched paged decode at the served gemma shapes: every slot one
    # page of the pool, positions inside the page
    hkv, hd = gemma.n_kv_heads, gemma.head_dim_
    g = gemma.n_heads // hkv
    pool = MAX_SLOTS * 2 * gemma_page
    tables = tuple((s,) for s in range(MAX_SLOTS))
    q = jax.random.normal(keys[0], (MAX_SLOTS, hkv, g, hd), dt)
    kp = jax.random.normal(keys[1], (pool, hkv, hd), dt)
    vp = jax.random.normal(keys[2], (pool, hkv, hd), dt)
    pos = jnp.stack([jnp.asarray([min(p, gemma_page) - 1 for p in
                                  GEMMA_PROMPTS[:MAX_SLOTS]], jnp.int32),
                     jnp.zeros((MAX_SLOTS,), jnp.int32)], axis=-1)
    kw = dict(page=gemma_page, scale=hd ** -0.5, window=0)
    kernel_check(
        "paged_decode_batched",
        lambda *a: ops.paged_decode_batched(*a, page_tables=tables, **kw),
        lambda *a: ops._batched_oracle(*a, tables, kw["page"], kw["scale"],
                                       0),
        (q, kp, vp, pos), 2e-2, on_tpu, clock)

    # the SSD scan at the served mamba shapes (the longer prompt, and the
    # chunk the model asks for at that length)
    from repro.models import ssm
    s = max(MAMBA_PROMPTS)
    h, p, n = ssm.n_ssd_heads(mamba), mamba.ssm_head_dim, mamba.ssm_state
    chunk = min(mamba.ssm_chunk, s)
    xdt = jax.random.normal(keys[3], (1, s, h, p), jnp.float32)
    dA = -0.5 * jnp.abs(jax.random.normal(keys[4], (1, s, h), jnp.float32))
    B = jax.random.normal(keys[5], (1, s, n), jnp.float32)
    C = jax.random.normal(keys[6], (1, s, n), jnp.float32)

    def ssd_oracle(*a):
        with jax.default_matmul_precision("highest"):
            return ops._ssd_oracle(*a, jnp.zeros((1, h, p, n)), chunk)[0]
    kernel_check("scan_ssd",
                 lambda *a: ops.scan_ssd(*a, chunk=chunk)[0], ssd_oracle,
                 (xdt, dA, B, C), 2e-2, on_tpu, clock)

    # one GEMM of the served gemma MLP: the longest prompt's rows against
    # the gate/up projection
    x = jax.random.normal(keys[7], (max(GEMMA_PROMPTS), gemma.d_model), dt)
    w = jax.random.normal(keys[0], (gemma.d_model, 2 * gemma.d_ff), dt)
    # (off the chip the cpu entry's GEMM is the XLA oracle unless asked)
    interpret = None if on_tpu else True
    kernel_check(
        "matmul",
        lambda a, b: ops.matmul(a, b, out_dtype=jnp.float32,
                                interpret=interpret),
        lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32),
        (x, w), 2e-3, on_tpu, clock)


def one_chip(args, on_tpu: bool, clock: CompileClock) -> None:
    gemma, engine = serve("gemma-2b", GEMMA_PROMPTS, args, clock)
    gemma_page = engine.page
    del engine
    gc.collect()
    mamba, engine = serve("mamba2-780m", MAMBA_PROMPTS, args, clock)
    del engine
    gc.collect()
    kernels(gemma, gemma_page, mamba, on_tpu, clock, args.seed)


# ---------------------------------------------------------------------------
# four chips: the sharded train step and a sharded matmul
# ---------------------------------------------------------------------------

def train_losses(cfg, dp: int, tp: int, batches, seed: int) -> list[float]:
    """``TRAIN_STEPS`` train steps on a ``dp x tp`` host mesh with the
    lifting-derived shardings — the path ``repro.launch.train`` takes."""
    import jax
    from repro.core.hardware import detect_hardware
    from repro.distributed import sharding as rules
    from repro.launch.mesh import make_host_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.train import train_step as ts

    mesh = make_host_mesh(dp=dp, tp=tp)
    losses = []
    with mesh:
        state, axes = ts.init_state(cfg, jax.random.PRNGKey(seed))
        shardings = rules.param_shardings(
            state, ts.state_logical_axes(state, axes), mesh)
        state = jax.tree.map(jax.device_put, state, shardings)
        step = jax.jit(
            ts.make_train_step(cfg, AdamWConfig(lr_peak=1e-3,
                                                warmup_steps=1)),
            donate_argnums=(0,),
            compiler_options=detect_hardware().xla_options())
        for batch in batches:
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    del state
    gc.collect()
    return losses


def four_chips(args, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.data import PipelineConfig, SyntheticLM
    from repro.kernels import ops

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    cfg = get_config("stablelm-1.6b", reduced=args.reduced).with_(
        n_layers=2 if args.reduced else 4)
    data = SyntheticLM(PipelineConfig(cfg.vocab_size, 256, 8,
                                      seed=args.seed), cfg)
    batches = [jax.tree.map(jnp.asarray, data.global_batch(i))
               for i in range(TRAIN_STEPS)]
    t0 = time.perf_counter()
    one = train_losses(cfg, 1, 1, batches, args.seed)
    four = train_losses(cfg, 2, 2, batches, args.seed)
    for i, (a, b) in enumerate(zip(one, four)):
        check(np.isfinite(a) and np.isfinite(b), f"step {i} loss not finite")
        # bf16 activations: a few units of bf16 rounding (2^-8) relative
        check(abs(a - b) <= 1e-2 * abs(a),
              f"step {i}: sharded loss {b} vs one-device {a}")
    fact(phase="train", model=cfg.name, layers=cfg.n_layers,
         d_model=cfg.d_model, vocab=cfg.vocab_size, mesh="dp=2,tp=2",
         steps=TRAIN_STEPS,
         loss_one_device=",".join(f"{x:.6f}" for x in one),
         loss_sharded=",".join(f"{x:.6f}" for x in four),
         compile_s=f"{clock.lap():.1f}",
         wall_s=f"{time.perf_counter() - t0:.1f}",
         peak_bytes_in_use=peak_bytes())

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
    x = jax.random.normal(keys[0], (512, cfg.d_model), jnp.bfloat16)
    w = jax.random.normal(keys[1], (cfg.d_model, 4 * cfg.d_model),
                          jnp.bfloat16)
    mesh = jax.make_mesh((4,), ("x",), devices=jax.devices()[:4],
                         axis_types=(jax.sharding.AxisType.Auto,))
    sharded = jax.jit(lambda a, b: ops.matmul(
        a, b, out_dtype=jnp.float32, mesh=mesh, shard={"k": "x"}))
    single = jax.jit(lambda a, b: ops.matmul(a, b, out_dtype=jnp.float32))
    hlo = sharded.lower(x, w).compile().as_text()
    check(args.reduced or "tpu_custom_call" in hlo,
          "sharded matmul: no tpu_custom_call")
    check("all-reduce" in hlo, "sharded matmul: no all-reduce for the "
          "contraction split")
    err = close(sharded(x, w), single(x, w), 2e-3)
    fact(phase="sharded_matmul", shapes=f"{x.shape}@{w.shape}",
         shard="k->x(4)", rel_err=f"{err:.2e}", tol="2e-03",
         compile_s=f"{clock.lap():.1f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced configs; accepts a CPU backend (rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.reduced:
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2

    from repro.core.hardware import detect_hardware
    from repro.launch.cache import enable_compile_cache

    entry = detect_hardware()
    check(entry.backend == ("pallas" if on_tpu else "interpret"),
          f"hardware entry {entry.name} runs backend {entry.backend!r}")
    fact(phase="device", platform=dev.platform,
         kind=json.dumps(dev.device_kind), count=len(jax.devices()),
         entry=entry.name, backend=entry.backend,
         compile_cache=enable_compile_cache())
    clock = CompileClock()
    if args.chips == 4:
        four_chips(args, clock)
    else:
        one_chip(args, on_tpu, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
