import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede any jax import: jax locks the device count on first init.
# (No __future__ import in this file for the same reason: these two lines
# must be the first statements.)

_DOC = """Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against abstract inputs, and extract the roofline terms.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. derives every sharding from the lifting rules (repro.distributed.sharding),
  3. ``jax.jit(fn, in_shardings, out_shardings).lower(*abstract).compile()``,
  4. records ``memory_analysis()`` (fits-per-device proof),
     ``cost_analysis()`` (FLOPs/bytes) and the collective-byte breakdown
     parsed from the post-SPMD HLO,
  5. emits one JSON record per cell into --out (consumed by
     benchmarks/bench_roofline.py and EXPERIMENTS.md).

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out results/dryrun
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_cells, cell_applicable, get_config
from repro.core import cost as cost_mod
from repro.core.cost import collective_bytes_from_hlo, from_quantities
from repro.core.lifting import TPU_V5E, TPU_V5E_2POD
from repro.distributed import sharding as shard_rules
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.models.common import ArchConfig, ShapeConfig
from repro.optim import adamw
from repro.train import train_step as ts_mod


def _abstract_init(cfg: ArchConfig, key):
    """Abstract param shapes + the logical-axes tree (no allocation)."""
    captured = {}

    def f(k):
        p, a = registry.init(cfg, k)
        captured["axes"] = a
        return p

    shapes = jax.eval_shape(f, key)
    return shapes, captured["axes"]


def _batch_pspec(batch_specs: dict, mesh) -> dict:
    out = {}
    for k, v in batch_specs.items():
        axes = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = shard_rules.act_spec(axes, v.shape, mesh)
    return out


def _named(tree, mesh):
    """PartitionSpec pytree -> NamedSharding pytree (jit in 0.8 wants
    Shardings unless a context mesh is set)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, tree,
        is_leaf=lambda s: isinstance(s, P) or s is None)


def lower_cell(cfg, shape_name: str, multi_pod: bool,
               microbatches: int | None = None, donate: bool = True):
    """Returns (lowered, aux_info).  ``cfg`` may be an ArchConfig or an
    arch-id string."""
    if isinstance(cfg, str):
        cfg = get_config(cfg)
    shp = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    key = jax.random.PRNGKey(0)
    specs = registry.input_specs(cfg, shp)

    with mesh:
        p_shapes, p_axes = _abstract_init(cfg, key)
        p_pspecs = shard_rules.param_pspecs(p_shapes, p_axes, mesh)

        if shp.kind == "train":
            mb = microbatches if microbatches is not None else default_microbatches(cfg, shp)
            # each microbatch must still shard over the DP axes
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            dp_total = sizes.get("pod", 1) * sizes.get("data", 1)
            while mb > 1 and (shp.global_batch // mb) % dp_total:
                mb -= 1
            step_fn = ts_mod.make_train_step(cfg, microbatches=mb)
            state_shapes = jax.eval_shape(
                lambda p: ts_mod.TrainState(
                    params=p, opt=adamw.init(p), err_fb=None,
                    step=jnp.zeros((), jnp.int32)), p_shapes)
            state_pspecs = ts_mod.TrainState(
                params=p_pspecs,
                opt=adamw.AdamWState(step=P(), master=p_pspecs, m=p_pspecs,
                                     v=p_pspecs),
                err_fb=None, step=P())
            batch_ps = _batch_pspec(specs["batch"], mesh)
            jf = jax.jit(step_fn,
                         in_shardings=_named((state_pspecs, batch_ps), mesh),
                         out_shardings=_named((state_pspecs, None), mesh),
                         donate_argnums=(0,) if donate else ())
            lowered = jf.lower(state_shapes, specs["batch"])
            extra = {"microbatches": mb}
        elif shp.kind == "prefill":
            def prefill_fn(params, batch):
                return registry.prefill(params, cfg, batch)
            batch_ps = _batch_pspec(specs["batch"], mesh)
            jf = jax.jit(prefill_fn,
                         in_shardings=_named((p_pspecs, batch_ps), mesh))
            lowered = jf.lower(p_shapes, specs["batch"])
            extra = {}
        else:  # decode
            cache_shapes = specs["cache"]
            cache_axes = registry.cache_logical_axes(cache_shapes)
            cache_ps = jax.tree.map(
                lambda leaf, ax: shard_rules.act_spec(ax, leaf.shape, mesh),
                cache_shapes, cache_axes)

            def decode_fn(params, tokens, pos, cache):
                return registry.decode_step(params, cfg, tokens, pos, cache)
            tok_ps = shard_rules.act_spec(("batch",), specs["tokens"].shape, mesh)
            jf = jax.jit(decode_fn,
                         in_shardings=_named((p_pspecs, tok_ps, tok_ps, cache_ps), mesh),
                         out_shardings=_named((None, cache_ps), mesh),
                         donate_argnums=(3,) if donate else ())
            lowered = jf.lower(p_shapes, specs["tokens"], specs["pos"],
                               cache_shapes)
            extra = {}
    return lowered, {"cfg": cfg, "shape": shp, "mesh": mesh, **extra}


def default_microbatches(cfg: ArchConfig, shp: ShapeConfig,
                         dp: int = 32, tp: int = 16,
                         logit_budget: int = 2 * 2**30) -> int:
    """Activation-memory heuristic (the lifting view of the batch axis).

    The dominant per-device temp for training is the f32 logits+grad buffer
    ~ 2 x B_local x S x vocab/tp x 4B; choose the microbatch count that
    keeps it under ``logit_budget``, then round to a divisor of B_local."""
    if cfg.train_microbatches:
        return cfg.train_microbatches
    b_local = max(shp.global_batch // dp, 1)
    logit_bytes = 2.0 * b_local * shp.seq_len * (cfg.vocab_size / tp) * 4
    act_bytes = 0.0
    if cfg.moe:
        # dispatch replicates tokens x top_k: (t_loc*k, d) gather/scatter
        # buffers live through the layer backward
        act_bytes = 6.0 * b_local * shp.seq_len * cfg.top_k * cfg.d_model * 2
    mb = max(1, int(-(-max(logit_bytes, act_bytes) // logit_budget)))
    while b_local % mb:
        mb += 1
    return min(mb, b_local)


def layer_variants(cfg: ArchConfig) -> tuple[list[tuple[ArchConfig, int]], int]:
    """Two reduced-depth configs + the full unit count, for the linear
    cost regression (XLA cost_analysis counts a scanned layer body ONCE —
    metric(units) = a + b*units recovers the per-layer slope, then we
    extrapolate to full depth)."""
    if cfg.family == "audio":
        mk = lambda k: cfg.with_(n_layers=k, encoder_layers=k, scan_unroll=True)
        return [(mk(1), 1), (mk(2), 2)], cfg.n_layers
    if cfg.family == "hybrid" and cfg.layer_pattern:
        per = len(cfg.layer_pattern)
        tail = cfg.n_layers % per
        mk = lambda g: cfg.with_(n_layers=per * g + tail, scan_unroll=True)
        return [(mk(1), 1), (mk(2), 2)], (cfg.n_layers - tail) // per
    if cfg.layer_pattern:
        per = len(cfg.layer_pattern)
        mk = lambda g: cfg.with_(n_layers=per * g, scan_unroll=True)
        return [(mk(1), 1), (mk(2), 2)], cfg.n_layers // per
    base = cfg.first_dense_layers
    mk = lambda L: cfg.with_(n_layers=L, scan_unroll=True)
    return [(mk(base + 1), base + 1), (mk(base + 2), base + 2)], cfg.n_layers


def analyze(lowered, info, hardware) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    cfg, shp, mesh = info["cfg"], info["shape"], info["mesh"]
    n_chips = mesh.devices.size

    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    bytes_ = float(ca.get("bytes accessed", 0.0))

    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:                      # CPU backend may not support
        mem["error"] = str(e)

    total, active = cfg.param_count()
    if shp.kind == "train":
        tokens = shp.tokens
        mf = cost_mod.model_flops_lm(total, tokens, active_params=active,
                                     training=True)
    elif shp.kind == "prefill":
        mf = cost_mod.model_flops_lm(total, shp.tokens, active_params=active,
                                     training=False)
    else:
        mf = cost_mod.model_flops_lm(total, shp.global_batch,
                                     active_params=active, training=False)

    rl = from_quantities(f"{cfg.name}/{shp.name}", n_chips=n_chips,
                         per_device_flops=flops, per_device_hbm_bytes=bytes_,
                         collective_stats=coll, hardware=hardware,
                         model_flops=mf)
    rec = {
        "arch": cfg.name, "shape": shp.name, "kind": shp.kind,
        "n_chips": n_chips, "compile_s": round(compile_s, 1),
        "params_total": total, "params_active": active,
        "memory": mem, "cost_analysis": {k: ca[k] for k in
                                         ("flops", "bytes accessed")
                                         if k in ca},
        "collectives_bytes": coll.bytes_by_op,
        "collectives_count": coll.count_by_op,
        "roofline": rl.to_dict(),
    }
    for k, v in info.items():
        if k in ("microbatches",):
            rec[k] = v
    return rec


def _cost_metrics(lowered) -> dict:
    """flops / bytes / per-op collective bytes of one compiled variant."""
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": dict(coll.bytes_by_op)}


def _extrapolate(m_small: dict, u_small: int, m_mid: dict, u_mid: int,
                 u_full: int) -> dict:
    """Linear metric(units) = a + b*units -> value at u_full (clamped >=0)."""
    du = max(u_mid - u_small, 1)

    def ext(a, b):
        slope = (b - a) / du
        return max(a + slope * (u_full - u_small), 0.0)

    ops = set(m_small["coll"]) | set(m_mid["coll"])
    return {
        "flops": ext(m_small["flops"], m_mid["flops"]),
        "bytes": ext(m_small["bytes"], m_mid["bytes"]),
        "coll": {op: ext(m_small["coll"].get(op, 0.0),
                         m_mid["coll"].get(op, 0.0)) for op in ops},
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str | None,
             donate: bool = True, regress: bool = True) -> dict:
    multi = mesh_kind == "multi"
    hardware = TPU_V5E_2POD if multi else TPU_V5E
    ok, why = cell_applicable(arch, shape_name)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "SKIP", "reason": why}
    else:
        try:
            cfg = get_config(arch)
            lowered, info = lower_cell(cfg, shape_name, multi, donate=donate)
            rec = analyze(lowered, info, hardware)
            rec.update(mesh=mesh_kind, status="OK")
            if regress:
                # depth regression: XLA counts scanned layer bodies once, so
                # extract per-layer slopes from two reduced-depth compiles
                # and extrapolate flops/bytes/collectives to full depth.
                variants, u_full = layer_variants(cfg)
                (vcfg_s, us), (vcfg_m, um) = variants
                ls, _ = lower_cell(vcfg_s, shape_name, multi,
                                   microbatches=1, donate=False)
                lm, _ = lower_cell(vcfg_m, shape_name, multi,
                                   microbatches=1, donate=False)
                ext = _extrapolate(_cost_metrics(ls), us, _cost_metrics(lm),
                                   um, u_full)
                stats = cost_mod.CollectiveStats(
                    bytes_by_op={k: int(v) for k, v in ext["coll"].items()})
                n_chips = rec["n_chips"]
                rl = from_quantities(
                    f"{arch}/{shape_name}", n_chips=n_chips,
                    per_device_flops=ext["flops"],
                    per_device_hbm_bytes=ext["bytes"],
                    collective_stats=stats, hardware=hardware,
                    model_flops=rec["roofline"]["model_flops"])
                rec["roofline_raw_scan_body"] = rec["roofline"]
                rec["roofline"] = rl.to_dict()
                rec["regression"] = {"units": [us, um, u_full],
                                     "extrapolated": ext}
        except Exception as e:
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                   "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[None, *SHAPES])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-donate", action="store_true")
    args = ap.parse_args()

    cells = all_cells() if args.all else [
        (a, s) for a, s in all_cells()
        if (args.arch in (None, a)) and (args.shape in (None, s))]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for arch, shape_name in cells:
        for mk in meshes:
            t0 = time.time()
            rec = run_cell(arch, shape_name, mk, args.out,
                           donate=not args.no_donate)
            status = rec.get("status")
            dom = rec.get("roofline", {}).get("dominant", "-")
            print(f"[{time.time()-t0:7.1f}s] {arch:28s} {shape_name:12s} "
                  f"{mk:6s} {status:5s} dominant={dom}", flush=True)
            if status == "FAIL":
                print(rec.get("error"), flush=True)


if __name__ == "__main__":
    main()
