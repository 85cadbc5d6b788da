import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

Lowers + compiles every (architecture × input shape) cell on the production
meshes — 16×16 single-pod and 2×16×16 multi-pod — and records
``memory_analysis`` / ``cost_analysis`` / collective-bytes for §Dry-run and
§Roofline.  ShapeDtypeStruct stand-ins everywhere: nothing is allocated.

Usage:
  python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out runs/dryrun]
  python -m repro.launch.dryrun --sim          # the engine as a workload
"""
import argparse
import json
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             hillclimb: dict | None = None) -> dict:
    import jax

    from repro.configs import SHAPES, applicable, get_config
    from repro.launch import roofline
    from repro.launch.mesh import make_production_mesh
    from repro.serve.step import assemble_decode, assemble_prefill
    from repro.train.step import TrainHParams, assemble_train

    cfg = get_config(arch, **(hillclimb.get("cfg", {}) if hillclimb else {}))
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "params_total": cfg.param_count(),
           "params_active": cfg.active_param_count(),
           "override": hillclimb}
    ok, why = applicable(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    from repro.parallel.sharding import (activation_sharding,
                                         make_rules_for_mesh)

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    if shape.kind == "train":
        hp = TrainHParams(**(hillclimb.get("hp", {}) if hillclimb else {}))
        jitted, args = assemble_train(cfg, mesh, shape, hp)
    elif shape.kind == "prefill":
        jitted, args = assemble_prefill(cfg, mesh, shape)
    else:
        jitted, args = assemble_decode(cfg, mesh, shape)
    with mesh, activation_sharding(mesh, make_rules_for_mesh(cfg, mesh)):
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    rec["lower_compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    print(f"[{arch} x {shape_name} x {rec['mesh']}] memory_analysis:")
    print(f"  args={mem.argument_size_in_bytes/2**30:.2f}GiB "
          f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
          f"out={mem.output_size_in_bytes/2**30:.2f}GiB")
    cost = compiled.cost_analysis()
    print(f"  cost_analysis: flops={cost.get('flops', 0):.3e} "
          f"bytes={cost.get('bytes accessed', 0):.3e}")
    rec.update(status="ok", **roofline.analyze(compiled, cfg, shape, n_dev))
    return rec


def run_sim_cell(multi_pod: bool) -> dict:
    """The paper's engine itself as a multi-pod workload: the sharded-PDES
    memsys simulation lowered on the production mesh ('sim' = all chips)."""
    import jax

    from repro.launch import roofline
    from repro.launch.mesh import make_sim_mesh
    from repro.sims.memsys import build_sharded_memsys

    n = len(jax.devices())
    mesh = make_sim_mesh(n)
    t0 = time.time()
    ss = build_sharded_memsys(mesh=mesh, n_shards=n, tiles_per_shard=4)
    lowered = ss.lower(until=4096.0)
    compiled = lowered.compile()
    rec = {"arch": "akita-memsys-pdes", "shape": f"{n}shards",
           "mesh": f"{n}", "status": "ok",
           "lower_compile_s": round(time.time() - t0, 1)}
    mem = compiled.memory_analysis()
    print(f"[akita-memsys-pdes x {n} shards] "
          f"args={mem.argument_size_in_bytes/2**20:.1f}MiB "
          f"temp={mem.temp_size_in_bytes/2**20:.1f}MiB")
    coll = roofline.parse_collectives(compiled.as_text(), n)
    rec["collective_bytes_per_chip"] = coll.total_bytes
    rec["collective_by_op"] = coll.bytes_by_op
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sim", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--override", default=None,
                    help='hillclimb JSON, e.g. \'{"hp":{"micro_batches":8},'
                         '"cfg":{"remat":"none"},"tag":"mb8"}\'')
    args = ap.parse_args()
    override = json.loads(args.override) if args.override else None

    from repro.configs import ARCH_IDS, SHAPES

    os.makedirs(args.out, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = []
    if args.sim:
        for mp in meshes:
            cells.append(("__sim__", "", mp))
    elif args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                for mp in meshes:
                    cells.append((a, s, mp))
    else:
        assert args.arch and args.shape
        for mp in meshes:
            cells.append((args.arch, args.shape, mp))

    results = []
    for a, s, mp in cells:
        tag = f"{a}_{s}_{'mp' if mp else 'sp'}".replace("__sim___", "sim_")
        if override and override.get("tag"):
            tag += "_" + override["tag"]
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"== {tag}: cached, skipping")
            results.append(json.load(open(path)))
            continue
        print(f"== {tag}")
        try:
            rec = run_sim_cell(mp) if a == "__sim__" else \
                run_cell(a, s, mp, hillclimb=override)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": a, "shape": s, "mesh": "mp" if mp else "sp",
                   "status": "error", "error": repr(e)}
        with open(path, "w") as fh:
            json.dump(rec, fh, indent=1, default=str)
        results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\nDONE: {n_ok} ok, {n_skip} skipped (documented), {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
