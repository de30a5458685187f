import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before jax initializes (they pin the fake
# device count for the production meshes); everything else follows.

# Multi-pod dry-run: lower + compile every (architecture x input shape) on
# the production meshes, print memory/cost analysis, and write the roofline
# inputs to results/dryrun/*.json.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
#   PYTHONPATH=src python -m repro.launch.dryrun ... --test-mesh 2,4  (CI scale)

import argparse
import json
import time
import traceback

import jax

from repro.common.config import SHAPES, TrainConfig
from repro.common.sharding import make_rules, use_rules
from repro.configs import ASSIGNED, get_config, supports_shape
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import io as mio
from repro.models.model import build_model
from repro.nn.core import abstract_params
from repro.serve.engine import make_serve_step
from repro.train.loop import make_train_step
from repro.train.optim import adamw_init


def _opt_abstract(params_sds):
    """AdamW state SDS tree with m/v inheriting the param shardings."""
    sds = jax.eval_shape(adamw_init, params_sds)

    def like(p, s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=p.sharding)

    m = jax.tree.map(like, params_sds, sds["m"])
    v = jax.tree.map(like, params_sds, sds["v"])
    return {"m": m, "v": v, "step": sds["step"]}


def _serve_params_sds(model, mesh):
    """Serving parameter layout: bf16-resident, tensor-parallel only (no
    FSDP/layer-stack sharding, which would all-gather weights every decode
    step). The trainer keeps fp32 + FSDP; the server keeps bf16 + TP —
    standard disaggregation, and a measured §Perf win (see EXPERIMENTS)."""
    import jax.numpy as jnp

    from repro.common.sharding import make_rules as _mk

    serve_rules = _mk(mesh, overrides={"embed": None, "layers": None})
    sds = abstract_params(model.param_specs(), serve_rules)

    def bf16(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16,
                                        sharding=leaf.sharding)
        return leaf

    return jax.tree.map(bf16, sds)


def lower_one(arch: str, shape_name: str, mesh, rules,
              serve_layout: str = "train", microbatches: int = 1,
              loss_chunk: int = 0):
    """Returns (lowered, cfg).

    serve_layout: 'train' keeps decode on the training parameter layout
    (fp32 + FSDP) — the paper-faithful baseline; 'serve' uses the optimized
    bf16/TP-resident layout (§Perf hillclimb, decode shapes only).
    """
    import dataclasses as _dc

    cfg = get_config(arch)
    if loss_chunk:
        cfg = _dc.replace(cfg, loss_chunk=loss_chunk)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    params_sds = abstract_params(model.param_specs(), rules)

    with use_rules(rules):
        if shape.mode == "train":
            step = make_train_step(model, TrainConfig(),
                                   microbatches=microbatches)
            opt_sds = _opt_abstract(params_sds)
            batch_sds = mio.batch_struct(cfg, shape, rules)
            # params/opt donated: updated in place, as any real trainer does
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params_sds, opt_sds, batch_sds)
        elif shape.mode == "prefill":
            batch_sds = mio.batch_struct(cfg, shape, rules)

            def prefill(params, batch):
                return model.prefill(params, batch)

            lowered = jax.jit(prefill).lower(params_sds, batch_sds)
        else:  # decode
            if serve_layout == "serve":
                params_sds = _serve_params_sds(model, mesh)
            serve_step = make_serve_step(model)
            state_sds = mio.decode_state_struct(model, shape, rules)
            tok_sds = mio.decode_tokens_struct(cfg, shape, rules)
            # the decode state is donated: caches update in place
            lowered = jax.jit(serve_step, donate_argnums=(1,)).lower(
                params_sds, state_sds, tok_sds)
    return lowered, cfg


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            test_mesh=None, out_dir: str = "results/dryrun",
            verbose: bool = True, serve_layout: str = "train",
            microbatches: int = 1, loss_chunk: int = 0,
            tag: str = "") -> dict:
    if test_mesh is not None:
        import numpy as np
        from jax.sharding import Mesh

        shape_t = tuple(test_mesh)
        axes = ("data", "model") if len(shape_t) == 2 \
            else ("pod", "data", "model")
        devs = np.array(jax.devices()[: np.prod(shape_t)]).reshape(shape_t)
        mesh = Mesh(devs, axes)
        mesh_name = f"test{shape_t}"
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = int(mesh.devices.size)
    rules = make_rules(mesh)
    shape = SHAPES[shape_name]
    cfg = get_config(arch)

    t0 = time.perf_counter()
    lowered, cfg = lower_one(arch, shape_name, mesh, rules,
                             serve_layout=serve_layout,
                             microbatches=microbatches,
                             loss_chunk=loss_chunk)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # backend without memory analysis
        mem_info = {"error": str(e)}

    hlo = compiled.as_text()
    coll = rl.collective_bytes_by_kind(hlo)
    report = rl.build_report(arch, shape, mesh_name, chips, cost, coll, cfg)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": chips,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": mem_info,
        "collective_bytes_per_device": coll,
        "roofline": report.to_dict(),
        "param_count": rl.param_count(cfg),
        "active_param_count": rl.active_param_count(cfg),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "" if serve_layout == "train" else f"_{serve_layout}"
        if microbatches > 1:
            suffix += f"_mb{microbatches}"
        if tag:
            suffix += f"_{tag}"
        fname = f"{arch}_{shape_name}_{mesh_name}{suffix}.json".replace(
            "/", "-")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=2)
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem_info}")
        print(f"  cost_analysis flops/device: {cost.get('flops', 0):.3e}  "
              f"bytes/device: {cost.get('bytes accessed', 0):.3e}")
        print(f"  collectives (bytes/device): {coll}")
        r = report
        print(f"  roofline: compute {r.compute_s*1e3:.2f}ms | memory "
              f"{r.memory_s*1e3:.2f}ms | collective {r.collective_s*1e3:.2f}ms"
              f" -> dominant: {r.dominant} (useful ratio {r.useful_ratio:.2f})")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every supported (arch x shape)")
    ap.add_argument("--test-mesh", default=None,
                    help="small mesh for CI, e.g. 2,4")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--serve-layout", default="train",
                    choices=["train", "serve"],
                    help="decode-shape parameter layout (serve = bf16/TP)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches (train shapes)")
    ap.add_argument("--loss-chunk", type=int, default=0,
                    help=">0: sequence-chunked unembed+xent")
    ap.add_argument("--tag", default="", help="suffix for the result json")
    args = ap.parse_args()

    test_mesh = (tuple(int(x) for x in args.test_mesh.split(","))
                 if args.test_mesh else None)

    combos = []
    if args.all:
        for arch in ASSIGNED:
            cfg = get_config(arch)
            for shape_name, shape in SHAPES.items():
                if supports_shape(cfg, shape):
                    combos.append((arch, shape_name))
        # the sliding-window dense variant covers long_500k for dense archs
        combos.append(("qwen3-4b-sw", "long_500k"))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in combos:
        try:
            run_one(arch, shape_name, multi_pod=args.multi_pod,
                    test_mesh=test_mesh, out_dir=args.out,
                    serve_layout=args.serve_layout,
                    microbatches=args.microbatches,
                    loss_chunk=args.loss_chunk, tag=args.tag)
        except Exception:
            failures.append((arch, shape_name))
            traceback.print_exc()
    if failures:
        print(f"FAILED combos: {failures}")
        raise SystemExit(1)
    print(f"dry-run OK: {len(combos)} combos")


if __name__ == "__main__":
    main()
