"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces the compiled artifact's
  * memory_analysis()  — proves the cell fits per-device HBM,
  * cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective bytes   — parsed from the optimized HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute),
and writes one JSON per cell under --out (default dryrun_out/).

Shape kinds: train_4k lowers train_step; prefill_32k lowers forward;
decode_32k / long_500k lower serve (decode_step) with a materialized-shape
KV cache/state.  long_500k cells exist only for sub-quadratic archs
(DESIGN.md §Arch-applicability); the others record status='skipped'.
"""
import argparse
import json
import os
import re
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, SHAPES, get_config, shape_applicable
from ..core.cache import fingerprint_obj
from ..models import model as M
from ..optim.adamw import AdamWConfig, adamw_init
from ..train.train_loop import make_train_step
from .mesh import dp_axes, make_production_mesh, set_mesh
from .sharding import batch_specs, param_specs, replicated, state_specs

# 512 host devices back both the single-pod (16x16) and multi-pod (2x16x16)
# production meshes.  ``main`` sets the flag before jax initializes a backend;
# importing this module leaves the process's devices alone.
HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\]\S*)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Bytes moved by collectives, from the optimized (post-SPMD) HLO.

    Ring-cost convention: all-reduce counts 2x its result bytes
    (reduce-scatter + all-gather phases); everything else 1x result bytes.
    """
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_txt)
        out[op] += 2 * b if op == "all-reduce" else b
    return out


# Bump whenever the cell record gains/changes fields, so JSONs written by an
# older revision are recomputed instead of skip-cached without the new data
# (v2: kernel_plans from the compiler pass pipeline).
_RECORD_SCHEMA = 2


def cell_cache_key(arch: str, shape_name: str, multi_pod: bool,
                   fsdp: bool = True, variant: str = "base") -> str:
    """Content address of one dry-run cell: the full config, shape, mesh,
    jax version and record schema.  A cached JSON whose key differs (config
    edit, toolchain bump, schema change) is recomputed instead of silently
    served stale."""
    return fingerprint_obj(
        get_config(arch), SHAPES[shape_name], multi_pod, fsdp, variant,
        jax.__version__, _RECORD_SCHEMA,
    )


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg = get_config(arch)
    shp = SHAPES[shape_name]
    b, s = shp.global_batch, shp.seq_len
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    batch = {"tokens": tok, "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.frontend is not None:
        batch["embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.frontend_len, cfg.d_model), jnp.bfloat16
        )
    return batch


def _eval_shapes(cfg, shape):
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    out = {"params": params}
    if shape.kind == "train":
        out["opt"] = jax.eval_shape(partial(adamw_init), params)
    if shape.kind == "decode":
        out["state"] = jax.eval_shape(
            lambda: M.init_decode_state(cfg, shape.global_batch, shape.seq_len)
        )
    return out


def _with_shardings(struct_tree, spec_tree):
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sp),
        struct_tree, spec_tree,
    )


def lower_cell(arch: str, shape_name: str, multi_pod: bool, opt_cfg=None,
               fsdp: bool = True, variant: str = "base",
               explain: bool = False) -> dict:
    """variant: 'base' | 'dp_only' (no TP: params replicated, batch over all
    axes) | 'seq_parallel' (Megatron SP) | 'save_moe' (keep MoE dispatch
    across the backward) — the §Perf hillclimb knobs."""
    from dataclasses import replace as _replace

    cfg = get_config(arch)
    if variant == "seq_parallel":
        cfg = _replace(cfg, seq_parallel=True)
    elif variant == "save_moe":
        cfg = _replace(cfg, remat="block_save_moe")
    elif variant == "layer_remat":
        cfg = _replace(cfg, remat="layer")
    dp_only = variant == "dp_only"

    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "kind": shape.kind, "sharding": "fsdp" if fsdp else "tp",
                 "variant": variant,
                 "cache_key": cell_cache_key(arch, shape_name, multi_pod, fsdp, variant)}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    shapes = _eval_shapes(cfg, shape)
    if dp_only:
        pspecs = replicated(mesh, shapes["params"])
    else:
        pspecs = param_specs(shapes["params"], mesh,
                             fsdp=fsdp and shape.kind == "train", cfg=cfg)
    batch = input_specs(arch, shape_name)

    with set_mesh(mesh):
        if shape.kind == "train":
            # opt state m/v shaped like params -> same specs; step scalar repl
            ospecs = {
                "m": pspecs, "v": pspecs,
                "step": NamedSharding(mesh, P()),
            }
            all_axes = tuple(mesh.axis_names)
            bspecs = batch_specs(cfg, shape, mesh, batch,
                                 axes=all_axes if dp_only else None)
            accum = 4 if variant == "accum4" else 1
            step = make_train_step(cfg, opt_cfg or AdamWConfig(), accum_steps=accum)
            metrics_specs = {
                k: NamedSharding(mesh, P())
                for k in ("grad_norm", "lr", "skipped", "loss")
            }
            jitted = jax.jit(
                step,
                out_shardings=(pspecs, ospecs, metrics_specs),
                donate_argnums=(0, 1),
            )
            args = (
                _with_shardings(shapes["params"], pspecs),
                _with_shardings(shapes["opt"], ospecs),
                _with_shardings(batch, bspecs),
            )
        elif shape.kind == "prefill":
            bspecs = batch_specs(cfg, shape, mesh, batch)
            fwd = partial(M.forward, cfg)
            jitted = jax.jit(fwd)
            args = (
                _with_shardings(shapes["params"], pspecs),
                _with_shardings(batch, bspecs),
            )
        else:  # decode
            sspecs = state_specs(cfg, mesh, shapes["state"])
            tok = jax.ShapeDtypeStruct(
                (shape.global_batch, 1), jnp.int32,
                sharding=NamedSharding(
                    mesh,
                    P(dp_axes(mesh) if shape.global_batch % (
                        mesh.devices.size // mesh.shape["model"]) == 0 else None, None),
                ),
            )
            stepf = partial(M.decode_step, cfg)
            jitted = jax.jit(stepf)
            args = (
                _with_shardings(shapes["params"], pspecs),
                _with_shardings(shapes["state"], sspecs),
                tok,
            )

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    print(mem)  # proves it fits (bytes per device)
    cost = compiled.cost_analysis()
    print({k: cost.get(k) for k in ("flops", "bytes accessed")} if cost else cost)
    coll = collective_bytes(compiled.as_text())

    rec.update(status="ok", lower_s=round(t_lower, 1), compile_s=round(t_compile, 1))
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            rec[attr] = int(getattr(mem, attr, 0) or 0)
    if cost:
        rec["hlo_flops"] = float(cost.get("flops", 0.0))
        rec["hlo_bytes"] = float(cost.get("bytes accessed", 0.0))
    rec["collective_bytes"] = coll
    rec["collective_total"] = int(sum(coll.values()))
    rec["n_devices"] = int(mesh.devices.size)

    # kernel plans from the compiler pass pipeline: which idiom/recipe each
    # per-layer contraction resolves to at this cell's shape (content-keyed
    # memo: cells differing only in mesh/variant share one pipeline run)
    from ..core.cache import jit_cache
    from ..models.lowering import kernel_report, plan_model

    plans = jit_cache.get_or_build(
        ("dryrun.plans", fingerprint_obj(cfg, shape.seq_len, shape.global_batch)),
        lambda: plan_model(cfg, shape.seq_len, shape.global_batch),
    )
    rec["kernel_plans"] = [
        {"name": p.name, "mnk": list(p.mnk), "idiom": p.idiom,
         "recipe": p.recipe.kind, "source": p.source, "mesh_axis": p.mesh_axis}
        for p in plans
    ]
    if explain:
        print(kernel_report(cfg, shape.seq_len, shape.global_batch, plans=plans))
    return rec


def main() -> None:
    os.environ["XLA_FLAGS"] = HOST_DEVICES_FLAG
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--sharding", default="fsdp", choices=["fsdp", "tp"])
    ap.add_argument("--out", default="dryrun_out")
    ap.add_argument("--explain", action="store_true",
                    help="print the per-pass pipeline report for each cell")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                path = outdir / f"{tag}.json"
                if path.exists():
                    try:
                        prev = json.loads(path.read_text())
                    except (json.JSONDecodeError, OSError):
                        prev = {}
                    want = cell_cache_key(arch, shape, mp, fsdp=args.sharding == "fsdp")
                    if prev.get("cache_key") == want and prev.get("status") != "failed":
                        print(f"[skip-cached] {tag}")
                        continue
                    print(f"[stale-cache] {tag}: recomputing")
                print(f"[lower] {tag}", flush=True)
                try:
                    rec = lower_cell(arch, shape, mp, fsdp=args.sharding == "fsdp",
                                     explain=args.explain)
                except Exception as e:  # a failure here is a bug in the system
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "failed", "error": repr(e)[:500]}
                    failures += 1
                path.write_text(json.dumps(rec, indent=1))
                print(f"[done] {tag}: {rec['status']}", flush=True)
    print(f"dry-run complete, failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
