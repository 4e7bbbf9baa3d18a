import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_EXTRA", "") + " --xla_force_host_platform_device_count=512"
# Compile-only tool on host devices: never take an accelerator, which may
# belong to another process (the launcher that re-execs this module).
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we:
  1. build the FULL model config, eval_shape the step function inputs
     (ShapeDtypeStruct only — no allocation),
  2. jit with explicit in_shardings from the rules tables,
  3. .lower().compile() under the production mesh,
  4. record memory_analysis / cost_analysis / per-collective bytes into
     results/dryrun/<arch>__<shape>__<mesh>.json for §Dry-run + §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeSpec
from repro.distributed.sharding import use_sharding_rules
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train.loop import build_train_step, init_train_state

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def _lower_compile(cfg: ModelConfig, shape: ShapeSpec, mesh, rules, opt_cfg):
    """Lower + compile one step function; returns (compiled, n_params)."""
    model = build_model(cfg)
    with jax.set_mesh(mesh), use_sharding_rules(rules):
        if shape.kind == "train":
            state_specs = jax.eval_shape(
                lambda: init_train_state(model, jax.random.PRNGKey(0), opt_cfg)
            )
            in_specs = model.input_specs(shape)
            fn = build_train_step(model, opt_cfg)
            in_sh = (
                SP.param_shardings(mesh, rules, state_specs),
                SP.batch_shardings(mesh, rules, in_specs),
            )
            lowered = jax.jit(fn, in_shardings=in_sh).lower(state_specs, in_specs)
            n_params = SP.count_params(state_specs["params"])
        elif shape.kind == "prefill":
            p_specs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            in_specs = model.input_specs(shape)
            fn = lambda params, batch: model.prefill(params, batch)
            in_sh = (
                SP.param_shardings(mesh, rules, p_specs),
                SP.batch_shardings(mesh, rules, in_specs),
            )
            lowered = jax.jit(fn, in_shardings=in_sh).lower(p_specs, in_specs)
            n_params = SP.count_params(p_specs)
        else:  # decode
            p_specs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            cache_specs = model.cache_specs(shape)
            in_specs = model.input_specs(shape)
            fn = model.decode_step
            in_sh = (
                SP.param_shardings(mesh, rules, p_specs),
                SP.cache_shardings(mesh, rules, cache_specs),
                SP.batch_shardings(mesh, rules, in_specs)["token"],
                SP.batch_shardings(mesh, rules, in_specs)["pos"],
            )
            lowered = jax.jit(fn, in_shardings=in_sh).lower(
                p_specs, cache_specs, in_specs["token"], in_specs["pos"]
            )
            n_params = SP.count_params(p_specs)
        compiled = lowered.compile()
    return compiled, n_params


def _cost_variant(cfg: ModelConfig, k: int) -> ModelConfig:
    """Unrolled k-group config for per-group cost extraction (XLA counts
    while-loop bodies once, so the scanned program undercounts FLOPs and
    collective bytes; we extrapolate from unrolled 1- and 2-group builds)."""
    import dataclasses

    if cfg.enc_dec:
        return dataclasses.replace(cfg, n_layers=k, n_enc_layers=k, unroll_layers=True)
    tail = len(cfg.tail_blocks)
    return dataclasses.replace(
        cfg, n_layers=k * cfg.pattern_len + tail, unroll_layers=True
    )


def _costs(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    coll = SP.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": {k: float(v) for k, v in coll.items()},
    }


def _extrapolate(c1: dict, c2: dict, g: int) -> dict:
    """total = cost(1 group) + (cost(2) - cost(1)) * (G - 1)."""
    out = {
        "flops": c1["flops"] + (c2["flops"] - c1["flops"]) * (g - 1),
        "bytes": c1["bytes"] + (c2["bytes"] - c1["bytes"]) * (g - 1),
    }
    kinds = set(c1["coll"]) | set(c2["coll"])
    out["coll"] = {
        k: c1["coll"].get(k, 0.0) + (c2["coll"].get(k, 0.0) - c1["coll"].get(k, 0.0)) * (g - 1)
        for k in kinds
    }
    return out


def dryrun_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    overrides: dict | None = None,
    tag: str = "",
    verbose: bool = True,
    cfg_override: ModelConfig | None = None,
) -> dict:
    """Lower+compile one cell (full scanned program for memory/compile
    proof + two unrolled variants for roofline costing)."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = SP.rules_for(mesh, shape, overrides)
    opt_cfg = AdamWConfig()
    t0 = time.time()

    compiled, n_params = _lower_compile(cfg, shape, mesh, rules, opt_cfg)
    g = cfg.n_layers if cfg.enc_dec else cfg.n_groups
    c1 = _costs(_lower_compile(_cost_variant(cfg, 1), shape, mesh, rules, opt_cfg)[0])
    c2 = _costs(_lower_compile(_cost_variant(cfg, 2), shape, mesh, rules, opt_cfg)[0])
    tot = _extrapolate(c1, c2, g)

    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    coll = tot["coll"]
    n_chips = mesh.devices.size
    n_active = SP.active_params(cfg, n_params)

    flops_dev = tot["flops"]
    bytes_dev = tot["bytes"]
    coll_dev = float(sum(coll.values()))
    t_compute = flops_dev / SP.PEAK_FLOPS
    t_memory = bytes_dev / SP.HBM_BW
    t_coll = coll_dev / SP.ICI_BW
    mflops = SP.model_flops(cfg, shape, n_params, n_active)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": _mesh_tag(multi_pod),
        "tag": tag,
        "status": "ok",
        "compile_s": round(compile_s, 1),
        "n_chips": n_chips,
        "n_params": n_params,
        "n_active_params": n_active,
        "per_device": {
            "hlo_flops": flops_dev,
            "hlo_bytes": bytes_dev,
            "collective_bytes": coll_dev,
            "collectives": coll,
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        "roofline": {
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "t_collective_s": t_coll,
            "bottleneck": max(
                [("compute", t_compute), ("memory", t_memory), ("collective", t_coll)],
                key=lambda kv: kv[1],
            )[0],
            "model_flops_total": mflops,
            "useful_flops_ratio": (mflops / (flops_dev * n_chips)) if flops_dev else 0.0,
            "roofline_fraction": (
                (mflops / SP.PEAK_FLOPS / n_chips)
                / max(t_compute, t_memory, t_coll)
                if max(t_compute, t_memory, t_coll) > 0
                else 0.0
            ),
        },
    }
    if verbose:
        r = rec["roofline"]
        print(
            f"[{rec['mesh']}] {arch:26s} {shape_name:12s} ok "
            f"compile={compile_s:6.1f}s compute={r['t_compute_s']*1e3:8.2f}ms "
            f"mem={r['t_memory_s']*1e3:8.2f}ms coll={r['t_collective_s']*1e3:8.2f}ms "
            f"bound={r['bottleneck']:10s} useful={r['useful_flops_ratio']:.2f} "
            f"roofline={r['roofline_fraction']:.3f}",
            flush=True,
        )
    return rec


def save_record(rec: dict) -> pathlib.Path:
    RESULTS.mkdir(parents=True, exist_ok=True)
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    f = RESULTS / f"{rec['arch']}__{rec['shape']}__{rec.get('mesh','-')}{tag}.json"
    f.write_text(json.dumps(rec, indent=2))
    return f


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_ok = n_skip = n_fail = 0
    for arch, shape in cells:
        out = RESULTS / f"{arch}__{shape}__{_mesh_tag(args.multi_pod)}.json"
        if out.exists() and not args.force:
            rec = json.loads(out.read_text())
            print(f"[cached] {arch} {shape} -> {rec['status']}", flush=True)
            n_ok += rec["status"] == "ok"
            n_skip += rec["status"] == "skipped"
            continue
        try:
            rec = dryrun_cell(arch, shape, multi_pod=args.multi_pod)
        except Exception as e:
            traceback.print_exc()
            rec = {
                "arch": arch, "shape": shape, "mesh": _mesh_tag(args.multi_pod),
                "status": "fail", "error": f"{type(e).__name__}: {e}"[:2000],
            }
        save_record(rec)
        n_ok += rec["status"] == "ok"
        n_skip += rec["status"] == "skipped"
        n_fail += rec["status"] == "fail"
    print(f"dry-run done: ok={n_ok} skipped={n_skip} failed={n_fail}")


if __name__ == "__main__":
    main()
