import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
placeholder devices stand in for 2 pods x 256 chips of TPU v5e.  For every
cell we report ``memory_analysis()`` (fits-in-HBM evidence) and
``cost_analysis()`` (FLOPs/bytes for the §Roofline terms), and optionally
dump the optimized HLO for the collective-bytes parser
(benchmarks/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
      --out results/dryrun
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, SHAPES, get_config
from ..dist.sharding import ShardingPolicy
from ..models.registry import (
    build_model,
    cache_specs,
    input_specs,
    model_flops,
    param_counts,
    supports_shape,
)
from ..train.optimizer import make_optimizer
from ..train.train_loop import make_train_step
from .mesh import make_production_mesh

# archs big enough to need ZeRO-3 weight sharding on the data axis
FSDP_ARCHS = {"qwen2-72b", "qwen1.5-32b", "grok-1-314b", "llama-3.2-vision-90b",
              "deepseek-v2-lite-16b", "qwen1.5-4b"}
# sub-1B archs: the 16-wide TP axis only replicates compute; use 256-way DP
# (§Perf A3).  Overridable per-cell via build_cell(pure_dp=...).
PURE_DP_ARCHS = {"smollm-360m", "mamba2-780m", "seamless-m4t-medium"}


def abstract_init(model, seed: int = 0):
    """(ShapeDtypeStruct params, specs) without allocating anything."""
    captured = {}

    def f(key):
        p, s = model.init(key)
        captured["specs"] = s
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(seed))
    return shapes, captured["specs"]


def build_cell(arch: str, shape_name: str, mesh, fsdp=None, pure_dp=None):
    """Returns (fn, example_args, in_shardings, out_shardings, donate)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if pure_dp is None:
        pure_dp = arch in PURE_DP_ARCHS and shape.kind == "train"
    policy = ShardingPolicy(mesh, fsdp=(arch in FSDP_ARCHS if fsdp is None else fsdp),
                            pure_dp=pure_dp)
    dp_total = 1
    for ax in policy.batch_axes():
        dp_total *= mesh.shape[ax]
    model = build_model(cfg, mesh=mesh, batch_axes=policy.batch_axes(),
                        data_size=mesh.shape["data"],
                        use_sharded_moe=cfg.moe is not None)
    p_shapes, p_specs = abstract_init(model)
    p_sh = policy.param_shardings(p_specs)

    ins = input_specs(cfg, shape)
    batch_shapes = {k: v[0] for k, v in ins.items()}

    def in_sharding(sds, spec):
        resolved = policy.act_spec(spec)
        # small-batch decode (long_500k): batch cannot shard -> replicate it
        if resolved and resolved[0] is not None and sds.shape[0] % dp_total != 0:
            resolved = P(None, *tuple(resolved)[1:])
        return NamedSharding(mesh, resolved)

    batch_sh = {k: in_sharding(v[0], v[1]) for k, v in ins.items()}

    if shape.kind == "train":
        opt = make_optimizer(cfg.optimizer)
        opt_shapes = jax.eval_shape(opt.init, p_shapes)
        opt_specs = opt.state_specs(p_specs)
        opt_sh = policy.param_shardings(opt_specs)
        step_fn = make_train_step(model, opt)
        args = (p_shapes, opt_shapes, batch_shapes,
                jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (p_sh, opt_sh, batch_sh, NamedSharding(mesh, P()))
        out_sh = (p_sh, opt_sh, None)
        donate = (0, 1)
        return step_fn, args, in_sh, out_sh, donate

    if shape.kind == "prefill":
        c_shapes, c_specs = cache_specs(cfg, shape, dp_total)
        c_sh = policy.act_shardings(c_specs)

        def prefill_fn(params, batch):
            return model.prefill(params, batch)

        args = (p_shapes, batch_shapes)
        in_sh = (p_sh, batch_sh)
        out_sh = (None, c_sh) if _cache_matches(model, cfg) else None
        return prefill_fn, args, in_sh, None, ()

    # decode
    c_shapes, c_specs = cache_specs(cfg, shape, dp_total)
    c_sh = policy.act_shardings(c_specs)

    def decode_fn(params, cache, tokens):
        return model.decode_step(params, cache, tokens)

    args = (p_shapes, c_shapes, batch_shapes["tokens"])
    in_sh = (p_sh, c_sh, batch_sh["tokens"])
    out_sh = (None, c_sh)
    donate = (1,)
    return decode_fn, args, in_sh, out_sh, donate


def _cache_matches(model, cfg):
    return False  # prefill output shardings left to GSPMD (documented)


def run_cell(arch: str, shape_name: str, multi_pod: bool, hlo_dir=None, fsdp=None):
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    ok, why = supports_shape(cfg, shape)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
    }
    if not ok:
        result["status"] = "skipped"
        result["reason"] = why
        return result
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    try:
        with jax.set_mesh(mesh):
            fn, args, in_sh, out_sh, donate = build_cell(arch, shape_name, mesh, fsdp=fsdp)
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        total, active = param_counts(cfg)
        result.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "hlo_flops": float(cost.get("flops", -1)),
            "hlo_bytes": float(cost.get("bytes accessed", -1)),
            "model_flops": model_flops(cfg, shape),
            "params_total": total,
            "params_active": active,
            "bytes_per_device": {
                "argument": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp": int(getattr(mem, "temp_size_in_bytes", 0)),
                "peak": int(getattr(mem, "peak_memory_in_bytes", 0)),
            },
            "n_chips": n_chips,
        })
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            fname = os.path.join(hlo_dir, f"{arch}__{shape_name}__{result['mesh']}.hlo")
            with open(fname, "w") as f:
                f.write(compiled.as_text())
            result["hlo_file"] = fname
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        result["status"] = "fail"
        result["error"] = f"{type(e).__name__}: {str(e)[:500]}"
        result["traceback"] = traceback.format_exc()[-2000:]
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--out", default=None, help="directory for results json + hlo")
    ap.add_argument("--hlo", action="store_true", help="dump optimized HLO")
    args = ap.parse_args()

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]
    hlo_dir = os.path.join(args.out, "hlo") if (args.out and args.hlo) else None

    results = []
    for mp in pods:
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, mp, hlo_dir=hlo_dir)
                status = r["status"]
                extra = (f"flops={r.get('hlo_flops', 0):.3e} "
                         f"peak={r.get('bytes_per_device', {}).get('peak', 0)/2**30:.2f}GiB "
                         f"compile={r.get('compile_s', 0)}s"
                         if status == "ok" else r.get("reason", r.get("error", "")))
                print(f"[{r['mesh']}] {arch:24s} {shape:12s} {status:8s} {extra}",
                      flush=True)
                results.append(r)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        mode = "all" if args.all else f"{args.arch}_{args.shape}"
        with open(os.path.join(args.out, f"dryrun_{mode}_{args.multi_pod}.json"), "w") as f:
            json.dump(results, f, indent=1, default=str)
    n_fail = sum(1 for r in results if r["status"] == "fail")
    print(f"\n{len(results)} cells: "
          f"{sum(1 for r in results if r['status']=='ok')} ok, "
          f"{sum(1 for r in results if r['status']=='skipped')} skipped, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
