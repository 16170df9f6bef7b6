import os
_DUMP = os.environ.setdefault("REPRO_XLA_DUMP",
                              f"/tmp/repro_xla_dump_{os.getpid()}")
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512"
    # CPU-host-compile artifact mitigation: XLA-CPU's while-loop LICM hoists
    # a convert() of the ENTIRE saved layer stack out of the backward scan
    # (e.g. +21.5 GB/device on rwkv6-3b train_4k). The TPU pipeline does not
    # do this; disabling keeps memory_analysis() representative.
    " --xla_disable_hlo_passes=while-loop-invariant-code-motion"
    # dump post-SPMD HLO: collective dtypes there are the TPU-target ones
    # (the CPU backend's f32-GEMM promotion would otherwise double apparent
    # collective bytes); roofline.compile_with_spmd_dump reads these.
    f" --xla_dump_to={_DUMP} --xla_dump_hlo_pass_re=spmd-partitioning")

"""Multi-pod dry-run launcher (deliverable e) + roofline extraction (g).

For every (architecture × input-shape × mesh) cell:
  1. build the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. lower + compile the cell's step function with the real shardings
     (ShapeDtypeStruct inputs — no allocation),
  3. print/record memory_analysis() and cost_analysis(),
  4. lower the roofline segments and derive the three terms (§Roofline).

Results go to results/dryrun/<cell>.json; EXPERIMENTS.md tables are built
from these via benchmarks/report.py.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-110b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all --mesh single
  python -m repro.launch.dryrun --arch rwkv6-3b --shape long_500k \
      --set remat=dots --set fsdp=data,pod --tag myvariant
"""
import argparse
import json
import time
import traceback


def rules_for_mesh(mesh, mode: str = "sfl", fsdp_override=None,
                   expert_override=None):
    from repro.common.sharding import ShardingRules
    axes = tuple(mesh.axis_names)
    batch = tuple(a for a in ("pod", "data") if a in axes)
    fsdp = "data"
    if fsdp_override is not None:
        fsdp = fsdp_override
    rules = ShardingRules(batch=batch, fsdp=fsdp, tensor="model",
                          expert=expert_override or "model")
    if mode == "classical":
        rules = rules.replicated()
    return rules


def runnable_cells(cfg):
    from repro.models.config import ALL_SHAPES
    cells = []
    for shp in ALL_SHAPES:
        if shp.name == "long_500k" and not cfg.is_subquadratic:
            continue  # full-attention archs skip (DESIGN.md §4)
        cells.append(shp)
    return cells


def apply_overrides(cfg, sets):
    import dataclasses as dc
    fsdp_override = None
    expert_override = None
    kw = {}
    for s in sets or []:
        k, v = s.split("=", 1)
        if k == "fsdp":
            fsdp_override = tuple(v.split(",")) if "," in v else (v or None)
            continue
        if k == "expert":
            expert_override = v
            continue
        field = {f.name: f for f in dc.fields(cfg)}.get(k)
        if field is None:
            raise SystemExit(f"unknown config field {k}")
        ftype = type(getattr(cfg, k))
        if ftype is bool:
            kw[k] = v.lower() in ("1", "true", "yes")
        elif ftype is int:
            kw[k] = int(v)
        elif ftype is float:
            kw[k] = float(v)
        else:
            kw[k] = v
    return dc.replace(cfg, **kw), fsdp_override, expert_override


# per-arch gradient-accumulation defaults (bounds the remat-boundary stack
# L×B_micro×S×d; chosen so the per-device microbatch is 1-2 sequences)
MICRO_DEFAULT = {
    "arctic_480b": 8, "qwen3_moe_30b_a3b": 4, "musicgen_large": 4,
    "qwen1_5_110b": 16, "deepseek_coder_33b": 8, "olmo_1b": 1,
    "qwen2_0_5b": 1, "llama3_2_vision_90b": 16, "recurrentgemma_9b": 4,
    "rwkv6_3b": 4,
}


def run_cell(arch: str, shape_name: str, multi_pod: bool, mode: str,
             opt_name: str, sets, tag: str, out_dir: str,
             skip_existing: bool = False, segments: bool = True,
             microbatches: int = 0, transport: str = "gspmd"):
    import jax
    from repro import configs
    from repro.launch import specs as S
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import roofline_terms
    from repro.models.config import shape_by_name

    mesh_name = "multi" if multi_pod else "single"
    cell_id = f"{configs.canonical(arch)}__{shape_name}__{mesh_name}__{mode}"
    if tag:
        cell_id += f"__{tag}"
    out_path = os.path.join(out_dir, cell_id + ".json")
    if skip_existing and os.path.exists(out_path):
        print(f"[skip] {cell_id}")
        return json.load(open(out_path))

    cfg = configs.get(arch)
    cfg, fsdp_o, exp_o = apply_overrides(cfg, sets)
    shp = shape_by_name(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for_mesh(mesh, mode, fsdp_o, exp_o)
    if transport == "two_step_int8":
        # XLA SPMD CHECK-crash (ExpandDeviceGroupsWithIota) when partitioning
        # the embedding gather inside manual-'pod' subgroups: keep the table
        # rows unsharded under this transport (~0.5 GB transient)
        rules = rules.with_(table={"vocab_rows": None})
    micro = microbatches or MICRO_DEFAULT.get(configs.canonical(arch), 1)
    if shp.kind != "train":
        micro = 1

    rec = {"arch": cfg.name, "shape": shape_name, "mesh": mesh_name,
           "mode": mode, "opt": opt_name, "tag": tag, "micro": micro,
           "transport": transport,
           "overrides": list(sets or []),
           "params": cfg.param_count, "active_params": cfg.active_param_count}
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, args, _ = S.input_specs(cfg, shp, mesh, rules, opt_name, micro,
                                    transport=transport)
        lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    rec["compile_s"] = round(time.time() - t0, 2)
    rec["memory"] = {
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
    }
    rec["whole_program"] = {
        "flops_per_dev": float(ca.get("flops", 0.0)),
        "bytes_per_dev": float(ca.get("bytes accessed", 0.0)),
        "note": "scan bodies counted once; roofline uses segments",
    }
    print(f"[ok] {cell_id}: compile {rec['compile_s']}s  "
          f"args {rec['memory']['argument_gb']:.2f} GB/dev  "
          f"temp {rec['memory']['temp_gb']:.2f} GB/dev")

    if segments:
        from repro.launch.segments import cell_cost
        from repro.launch import hw
        t1 = time.time()
        segs = cell_cost(cfg, shp, mesh, rules, opt_name, microbatches=micro,
                         transport=transport)
        total = segs["total"]
        rec["roofline"] = roofline_terms(total, mesh)
        rec["roofline"]["segment_compile_s"] = round(time.time() - t1, 2)
        # kernel-fused memory term (Pallas mixers keep S²/pair intermediates
        # in VMEM on the TPU target); dominant/fraction recomputed with it
        mem_fused_s = segs["fused_bytes"] / hw.HBM_BW
        rec["roofline"]["memory_fused_s"] = mem_fused_s
        r = rec["roofline"]
        terms = {"compute": r["compute_s"], "memory": mem_fused_s,
                 "collective": r["collective_s"]}
        r["dominant_fused"] = max(terms, key=terms.get)
        bound = max(terms.values())
        r["roofline_frac_fused"] = r["compute_s"] / bound if bound else 0.0
        rec["per_device"] = {
            "flops": total.flops, "bytes": total.bytes_hbm,
            "bytes_fused": segs["fused_bytes"],
            "coll_bytes_by_axis": total.coll,
            "mixer_penalties": segs["mixer_penalties"],
        }
        # MODEL_FLOPS = 6·N_active·D tokens (fwd+bwd) per device
        import numpy as np
        n_dev = int(np.prod(list(mesh.shape.values())))
        tokens = shp.global_batch * (1 if shp.kind == "decode" else shp.seq_len)
        mult = 6.0 if shp.kind == "train" else 2.0
        model_flops = mult * cfg.active_param_count * tokens / n_dev
        rec["model_flops_per_dev"] = model_flops
        rec["useful_ratio"] = model_flops / total.flops if total.flops else 0.0
        r = rec["roofline"]
        print(f"     roofline: compute {r['compute_s']*1e3:.2f} ms | "
              f"memory {r['memory_s']*1e3:.2f} ms "
              f"(fused {r['memory_fused_s']*1e3:.2f}) | "
              f"collective {r['collective_s']*1e3:.2f} ms | "
              f"dominant {r['dominant_fused']} | useful {rec['useful_ratio']:.2f} | "
              f"frac {r['roofline_frac_fused']:.3f}")

    os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    # keep the dump dir bounded (arctic full-program texts are ~100 MB each)
    import shutil
    shutil.rmtree(_DUMP, ignore_errors=True)
    os.makedirs(_DUMP, exist_ok=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--mode", default="sfl", choices=["sfl", "classical"])
    ap.add_argument("--opt", default=None, help="sgd|sgdm|adamw (per-arch default)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--set", action="append", default=[], dest="sets",
                    metavar="key=value")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-segments", action="store_true")
    ap.add_argument("--micro", type=int, default=0,
                    help="gradient-accumulation microbatches (0 = per-arch default)")
    ap.add_argument("--transport", default="gspmd",
                    choices=["gspmd", "two_step_int8"],
                    help="gradient transport (two_step_int8 = explicit SFL "
                         "schedule with compressed cross-pod hop)")
    args = ap.parse_args()

    from repro import configs

    arch_list = [a for a in configs.ARCH_IDS if a != "femnist_cnn"]
    if args.list:
        for a in arch_list:
            cfg = configs.get(a)
            cells = [s.name for s in runnable_cells(cfg)]
            print(f"{a:24s} {cells}")
        return

    # per-arch optimizer defaults: the giants use sgdm (memory: DESIGN.md §5)
    OPT_DEFAULT = {"arctic_480b": "sgdm", "llama3_2_vision_90b": "sgdm",
                   "qwen1_5_110b": "adamw"}

    targets = arch_list if (args.all or args.arch is None) else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch in targets:
        cfg = configs.get(arch)
        shapes = ([args.shape] if args.shape
                  else [s.name for s in runnable_cells(cfg)])
        for shape_name in shapes:
            for mp in meshes:
                opt = args.opt or OPT_DEFAULT.get(configs.canonical(arch), "adamw")
                try:
                    run_cell(arch, shape_name, mp, args.mode, opt, args.sets,
                             args.tag, args.out, args.skip_existing,
                             segments=not args.no_segments,
                             microbatches=args.micro,
                             transport=args.transport)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape_name, mp, repr(e)))
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()
