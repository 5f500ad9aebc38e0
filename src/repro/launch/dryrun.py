import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST be the first lines: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the step function (train / prefill / decode) and its
     ShapeDtypeStruct inputs (no allocation),
  2. jits with explicit in_shardings from repro.launch.sharding,
  3. ``.lower(...).compile()`` — a failure here (sharding mismatch,
     unsupported collective) is a bug in the framework,
  4. records memory_analysis / cost_analysis / parsed collective bytes into
     a JSON file consumed by the roofline report and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k \
      --partitions 4          # paper-technique partitioned program + sync
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, ARCH_IDS, applicable_shapes, get_config
from repro.core import roofline
from repro.launch import sharding as SH
from repro.launch.mesh import make_production_mesh, batch_axes
from repro.models import api as mapi
from repro.models import pspec
from repro.optim.adamw import adamw_init
from repro.runtime import steps as RS

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _mem_dict(ma):
    return {
        "argument_size_bytes": ma.argument_size_in_bytes,
        "output_size_bytes": ma.output_size_in_bytes,
        "temp_size_bytes": ma.temp_size_in_bytes,
        "alias_size_bytes": ma.alias_size_in_bytes,
    }


def serving_layout_fits(params_sds, mesh) -> bool:
    """True when model-sharded-only (TP) weights fit comfortably per device
    (serving layout: replicate over data, move activations not weights)."""
    import numpy as np
    total = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree.leaves(params_sds))
    return total / mesh.shape.get("model", 1) <= 8 * 2**30


def want_seq_shard(cfg, shape, mesh, accum: int) -> bool:
    """Sequence-parallel residuals only when the saved layer carries would
    otherwise blow HBM (large-d models); for small models the seq-shard
    gathers inside the rematted attention dominate collectives (measured
    2.68 TB -> 1.51 TB/step on qwen2-7b by disabling it)."""
    if shape.kind != "train":
        return False
    n_data = 1
    for a in ("pod", "part", "data"):
        n_data *= mesh.shape.get(a, 1)
    b_dev = max(shape.global_batch // max(accum, 1) // n_data, 1)
    carries = cfg.n_layers * b_dev * shape.seq_len * cfg.d_model * 2
    return carries > 8 * 2**30


def build_cell(arch: str, shape_name: str, mesh, partitions: int = 1,
               accum: int = 4, auto_kv: bool = True):
    """Returns (fn, args_sds, in_shardings, donate) for the cell.
    ``accum``: gradient-accumulation microbatches for train cells (4 fits
    the 4k-seq cells in 16 GB HBM; recorded in the cell JSON)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    api = mapi.build(cfg)
    params_sds = jax.eval_shape(api.init, jax.random.PRNGKey(0))

    stack = None
    if partitions > 1:
        stack = "part" if "part" in mesh.shape else "pod"

    if shape.kind == "train":
        opt_sds = jax.eval_shape(adamw_init, params_sds)
        in_specs = api.input_specs(shape)
        if partitions > 1:
            n = partitions
            params_sds = jax.eval_shape(lambda t: RS.stack_tree(t, n),
                                        params_sds)
            opt_sds = jax.eval_shape(lambda t: RS.stack_tree(t, n), opt_sds)
            in_specs = {k: jax.ShapeDtypeStruct(
                (n, v.shape[0] // n) + v.shape[1:], v.dtype)
                for k, v in in_specs.items()}
            fn = RS.make_partitioned_train_step(api, stack_axis=stack,
                                                accum=accum)
        else:
            fn = RS.make_train_step(api, accum=accum)
        p_shard = SH.param_shardings(params_sds, cfg, mesh, stack_axis=stack)
        o_shard = SH.param_shardings(opt_sds, cfg, mesh, stack_axis=stack)
        # AdamWState.step: scalar (or (P,) when stacked)
        from jax.sharding import NamedSharding, PartitionSpec as P
        o_shard = o_shard._replace(step=NamedSharding(
            mesh, P(*((stack,) if stack else ()))))
        b_shard = SH.batch_shardings(in_specs, mesh, shape.global_batch,
                                     stack_axis=stack)
        args = (params_sds, opt_sds, in_specs)
        shards = (p_shard, o_shard, b_shard)
        return fn, args, shards, (0, 1)

    p_shard = SH.param_shardings(params_sds, cfg, mesh)

    if shape.kind == "prefill":
        in_specs = api.input_specs(shape)
        b_shard = SH.batch_shardings(in_specs, mesh, shape.global_batch)
        fn = RS.make_prefill_step(api, shape.seq_len)
        return fn, (params_sds, in_specs), (p_shard, b_shard), ()

    # decode: serving layout when TP-only weights fit (80x fewer collective
    # bytes, measured on qwen2-7b: 16.4 -> 0.2 GiB/step); cache layout is
    # XLA-chosen (auto_kv).
    if shape.kind == "decode" and serving_layout_fits(params_sds, mesh):
        p_shard = SH.param_shardings(params_sds, cfg, mesh, fsdp=False)
    tok = api.input_specs(shape)["token"]
    cache_sds = api.cache_specs(shape)
    c_shard = SH.cache_shardings(cache_sds, cfg, mesh, shape.global_batch,
                                 auto_kv=auto_kv)
    t_shard = SH.batch_shardings({"token": tok}, mesh,
                                 shape.global_batch)["token"]
    fn = RS.make_decode_step(api)
    return fn, (params_sds, tok, cache_sds), (p_shard, t_shard, c_shard), (2,)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             partitions: int = 1, verbose: bool = True,
             dump_hlo: str | None = None, accum: int = 4) -> dict:
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "partitions": partitions, "accum": accum, "ok": False}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"),
                                    partitions=partitions)
        rec["mesh_shape"] = dict(mesh.shape)
        shape = SHAPES[shape_name]
        bax = batch_axes(mesh, shape.global_batch)
        if partitions > 1:
            stackax = "part" if "part" in mesh.shape else "pod"
            bax = tuple(a for a in bax if a != stackax)
        msz = mesh.shape.get("model", 1)
        cfg_ = get_config(arch)

        # ---- layout autotune: compile both variants, pick by
        # (fits 16 GiB HBM, then min scan-aware collective bytes) ----
        if shape.kind == "decode":
            variants = [{"auto_kv": True}, {"auto_kv": False}]
        else:
            base_ss = want_seq_shard(cfg_, shape, mesh, 4)
            variants = [{"seq_shard": base_ss}, {"seq_shard": not base_ss}]

        budget = 16 * 2**30
        trials = []
        for var in variants:
            fn, args, shards, donate = build_cell(
                arch, shape_name, mesh, partitions, accum=accum,
                auto_kv=var.get("auto_kv", True))
            ss = var.get("seq_shard", False)
            with jax.set_mesh(mesh), pspec.axes(batch=bax, model_size=msz,
                                                seq_shard=ss):
                jitted = jax.jit(fn, in_shardings=shards,
                                 donate_argnums=donate)
                lowered = jitted.lower(*args)
                t1 = time.time()
                compiled = lowered.compile()
                t2 = time.time()
            mem = _mem_dict(compiled.memory_analysis())
            hlo_text = compiled.as_text()
            aware = roofline.scan_aware_collectives(hlo_text)
            used = mem["argument_size_bytes"] + mem["temp_size_bytes"]
            trials.append({
                "variant": var, "memory": mem, "mem_used": used,
                "collectives_scan_aware": aware,
                "collectives": roofline.parse_collectives(hlo_text),
                "compile_s": round(t2 - t1, 2),
                "cost_analysis": {k: float(compiled.cost_analysis().get(
                                               v, 0.0))
                                  for k, v in [("flops", "flops"),
                                               ("bytes_accessed",
                                                "bytes accessed")]},
                "hlo_text": hlo_text,
            })
        feasible = [t for t in trials if t["mem_used"] <= budget]
        pool = feasible or trials
        best = min(pool, key=lambda t:
                   t["collectives_scan_aware"]["total_bytes"]
                   if feasible else t["mem_used"])
        rec["variant_chosen"] = best["variant"]
        rec["variants"] = [
            {"variant": t["variant"], "mem_gib": t["mem_used"] / 2**30,
             "coll_gib": t["collectives_scan_aware"]["total_bytes"] / 2**30}
            for t in trials]
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = best["compile_s"]
        rec["memory"] = best["memory"]
        rec["cost_analysis"] = best["cost_analysis"]
        rec["collectives"] = best["collectives"]
        rec["collectives_scan_aware"] = {
            k: v for k, v in best["collectives_scan_aware"].items()}
        hlo_text = best["hlo_text"]
        rec["n_devices"] = jax.device_count()
        rec["ok"] = True
        if dump_hlo:
            Path(dump_hlo).write_text(hlo_text)
        if verbose:
            m = rec["memory"]
            per_dev = (m["argument_size_bytes"] + m["temp_size_bytes"]) / 2**30
            print(f"OK  {arch:>18s} {shape_name:>12s} {mesh_kind:>6s} P={partitions} "
                  f"compile={rec['compile_s']:.1f}s mem/dev={per_dev:.2f}GiB "
                  f"colls={rec['collectives']['total_count']}")
    except Exception as e:  # noqa: BLE001 — record and continue
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"FAIL {arch} {shape_name} {mesh_kind} P={partitions}: "
                  f"{rec['error'][:200]}")
    return rec


def cell_path(arch, shape, mesh_kind, partitions=1) -> Path:
    from repro.configs import canonical
    p = f"_p{partitions}" if partitions > 1 else ""
    return OUT_DIR / f"{canonical(arch)}__{shape}__{mesh_kind}{p}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--partitions", type=int, default=1)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--dump-hlo", default=None)
    ap.add_argument("--accum", type=int, default=4)
    args = ap.parse_args()

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape in applicable_shapes(cfg):
                for mk in meshes:
                    cells.append((arch, shape, mk))
        # cheapest first: decode < prefill < train, small models first
        size_rank = {a: i for i, a in enumerate(
            ["whisper_base", "mamba2_130m", "hymba_1p5b", "qwen1p5_4b",
             "qwen2_7b", "mistral_nemo_12b", "internvl2_26b",
             "qwen3_moe_30b_a3b", "dbrx_132b", "qwen1p5_110b"])}
        kind_rank = {"decode_32k": 0, "long_500k": 0, "prefill_32k": 1,
                     "train_4k": 2}
        cells.sort(key=lambda c: (kind_rank[c[1]], size_rank[c[0]]))
    else:
        arch = args.arch
        shapes = [args.shape] if args.shape else applicable_shapes(get_config(arch))
        for shape in shapes:
            for mk in meshes:
                cells.append((arch, shape, mk))

    n_ok = 0
    for arch, shape, mk in cells:
        path = cell_path(arch, shape, mk, args.partitions)
        if args.skip_existing and path.exists():
            rec = json.loads(path.read_text())
            if rec.get("ok"):
                n_ok += 1
                continue
        rec = run_cell(arch, shape, mk, args.partitions,
                       dump_hlo=args.dump_hlo, accum=args.accum)
        path.write_text(json.dumps(rec, indent=1))
        n_ok += rec["ok"]
    print(f"dryrun: {n_ok}/{len(cells)} cells OK")


if __name__ == "__main__":
    main()
