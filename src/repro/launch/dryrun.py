import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax-importing import: jax locks
the device count at first init, and the production meshes need 512
placeholder host devices.  (Smoke tests / benches never import this
module, so they see 1 device.)

Per cell this script:
  1. builds the production mesh (16x16 or 2x16x16),
  2. constructs the jitted step (train_step / prefill / serve_step) with
     explicit in/out shardings from the model's logical spec trees,
  3. ``.lower(**input_specs).compile()`` -- ShapeDtypeStruct only, no
     arrays are ever allocated,
  4. records memory_analysis(), cost_analysis(), and the collective
     schedule parsed from the optimized HLO into a JSON artifact for
     EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k \
      --mesh pod1 --out experiments/dryrun
  python -m repro.launch.dryrun --list        # enumerate runnable cells
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, SHAPES, ARCH_NAMES, cell_runnable, SKIPS
from repro.models import build_model
from repro.models.api import Model
from repro.optim import AdamWConfig
from repro.runtime import make_train_step
from repro.launch.mesh import make_production_mesh, data_axes
from repro.launch import sharding as shd
from repro.launch import roofline, hlo_cost


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)


def build_train(model: Model, shape, mesh):
    step = make_train_step(model, AdamWConfig(), mesh)
    params = model.abstract_params()
    opt = {"step": jax.ShapeDtypeStruct((), jnp.int32),
           "m": jax.tree_util.tree_map(
               lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params),
           "v": jax.tree_util.tree_map(
               lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)}
    batch = model.train_input_specs(shape)
    return step, (params, opt, batch)


def build_prefill(model: Model, shape, mesh):
    batch = model.prefill_input_specs(shape)
    pspecs = model.param_specs(mesh)
    ns = lambda t: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), t)
    in_sh = (ns(pspecs), shd.batch_shardings(batch, mesh))
    s_cap = shape.seq_len

    def fn(params, inputs):
        return model.prefill(params, inputs, mesh, s_cap=s_cap)

    if model.cfg.family == "encoder":
        out_sh = None
    else:
        cache_sds = model.cache_spec(shape.global_batch, s_cap)
        out_sh = (ns(shd.cache_specs(cache_sds, mesh)),
                  NamedSharding(mesh, shd.batch_spec(
                      mesh, 2, shape.global_batch)))
    step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    return step, (model.abstract_params(), batch)


def build_decode(model: Model, shape, mesh):
    b, s_cap = shape.global_batch, shape.seq_len
    pspecs = model.param_specs(mesh)
    ns = lambda t: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), t)
    cache_sds = _sds(model.cache_spec(b, s_cap))
    cache_sh = ns(shd.cache_specs(cache_sds, mesh))
    tok_sh = NamedSharding(mesh, shd.batch_spec(mesh, 1, b))

    def fn(params, caches, token, pos):
        return model.decode_step(params, caches, token, pos, mesh)

    step = jax.jit(
        fn,
        in_shardings=(ns(pspecs), cache_sh, tok_sh, tok_sh),
        out_shardings=(cache_sh,
                       NamedSharding(mesh, shd.batch_spec(mesh, 2, b))),
        donate_argnums=(1,))
    args = (model.abstract_params(), cache_sds,
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32))
    return step, args


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: dict | None = None, *, mesh=None,
             shape_cfg=None, smoke: bool = False) -> dict:
    shape = shape_cfg or SHAPES[shape_name]
    if mesh is None:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    cfg = get_config(arch, smoke=smoke, **(overrides or {}))
    model = build_model(cfg)

    with mesh:
        if shape.kind == "train":
            step, args = build_train(model, shape, mesh)
        elif shape.kind == "prefill":
            step, args = build_prefill(model, shape, mesh)
        else:
            step, args = build_decode(model, shape, mesh)

        t0 = time.perf_counter()
        lowered = step.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    # Scan-aware accounting from the compiled artifact (hlo_cost): XLA's
    # own cost_analysis counts while bodies once, so scanned layer stacks
    # are undercounted by ~n_layers; hlo_cost propagates trip counts.
    hc = hlo_cost.analyze(hlo)
    coll = hc["collectives"]
    link_bytes = hc["link_bytes"]
    flops = float(hc["flops"])
    raw_flops = float((cost or {}).get("flops", 0.0))
    raw_bytes = float((cost or {}).get("bytes accessed", 0.0))
    # bytes: scale XLA's (loop-undercounted) traffic by the same factor
    # the dot-flops were undercounted -- loop bodies dominate both.
    scale = max(1.0, flops / raw_flops) if raw_flops > 0 else 1.0
    bytes_acc = raw_bytes * scale
    terms = roofline.roofline_terms(flops, bytes_acc, link_bytes)

    n_active = model.active_param_count()
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind in ("train", "prefill") else shape.global_batch)
    mflops = roofline.model_flops(n_active, tokens, shape.kind)

    mem_fields = {}
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            try:
                mem_fields[f] = int(getattr(mem, f))
            except Exception:
                pass

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "kind": shape.kind,
        "n_devices": mesh.size,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "params": model.param_count(),
        "active_params": n_active,
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "raw_cost_analysis": {"flops": raw_flops, "bytes": raw_bytes,
                              "loop_scale": scale},
        "unknown_trip_whiles": hc["unknown_trip_whiles"],
        "collectives": coll,
        "link_bytes_per_device": link_bytes,
        "roofline": terms,
        "model_flops_global": mflops,
        "model_flops_per_device": mflops / mesh.size,
        "useful_flops_ratio": (mflops / mesh.size) / flops if flops else 0.0,
        "memory_analysis": mem_fields,
        "overrides": overrides or {},
    }
    return result


def all_cells():
    for arch in ARCH_NAMES:
        for shape in SHAPES:
            if cell_runnable(arch, shape):
                yield arch, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod1", "pod2"], default="pod1")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--override", default="",
                    help="comma k=v config overrides (perf experiments)")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    args = ap.parse_args()

    if args.list:
        for arch, shape in all_cells():
            print(f"{arch} {shape}")
        for (arch, shape), why in SKIPS.items():
            print(f"SKIP {arch} {shape}: {why}", file=sys.stderr)
        return

    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = (v if not v.replace("-", "").isdigit() else int(v))
        if v in ("true", "false"):
            overrides[k] = v == "true"

    os.makedirs(args.out, exist_ok=True)
    res = run_cell(args.arch, args.shape, args.mesh, overrides or None)
    tag = f"_{args.tag}" if args.tag else ""
    path = os.path.join(args.out,
                        f"{args.arch}_{args.shape}_{args.mesh}{tag}.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    r = res["roofline"]
    print(f"OK {args.arch} {args.shape} {args.mesh}: "
          f"compile {res['compile_s']}s "
          f"compute {r['compute_s']:.2e}s memory {r['memory_s']:.2e}s "
          f"collective {r['collective_s']:.2e}s dominant={r['dominant']} "
          f"useful={res['useful_flops_ratio']:.2f}")


if __name__ == "__main__":
    main()
