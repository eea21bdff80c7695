import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / roofline terms.

MUST be run as a script (the XLA_FLAGS line above executes before any jax
import, including the ones below).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]

With no filters it sweeps all 40 cells on the single-pod (16, 16) mesh and
then the multi-pod (2, 16, 16) mesh.  Results land in one JSON per cell.
"""

import argparse
import json
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = False) -> dict:
    import jax
    from repro import configs as config_registry
    from repro.launch.mesh import make_production_mesh
    from repro.launch import roofline as RL
    from repro.launch.steps import build_cell, build_lm_probe

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16", "ok": False}
    try:
        cell = build_cell(arch, shape_name, mesh)
        with mesh:
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             donate_argnums=cell.donate)
            lowered = jitted.lower(*cell.args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            cost = compiled.cost_analysis()
            full_flops = float(cost.get("flops", 0.0))
            full_bytes = float(cost.get("bytes accessed", 0.0))
            full_coll = RL.collective_bytes_from_hlo(hlo)

            # LM layer-scan correction: + (L-1) x exact single-block probe
            probe_info = None
            family = getattr(cell.cfg, "family", "lm")
            if family == "lm":
                probe = build_lm_probe(arch, shape_name, mesh)
                pc = jax.jit(probe.fn, in_shardings=probe.in_shardings
                             ).lower(*probe.args).compile()
                p_cost = pc.cost_analysis()
                p_hlo = pc.as_text()
                p_coll = RL.collective_bytes_from_hlo(p_hlo)
                lcount = cell.cfg.n_layers
                full_flops += (lcount - 1) * float(p_cost.get("flops", 0.0))
                full_bytes += (lcount - 1) * float(p_cost.get("bytes accessed", 0.0))
                for k in full_coll:
                    full_coll[k] += (lcount - 1) * p_coll.get(k, 0)
                probe_info = {
                    "probe_flops": float(p_cost.get("flops", 0.0)),
                    "probe_bytes": float(p_cost.get("bytes accessed", 0.0)),
                    "probe_collective": p_coll,
                    "layers": lcount,
                }

            resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
            mf = RL.model_flops_for(cell.cfg, cell.shape)
            roof = RL.analyze_terms(full_flops, full_bytes, full_coll,
                                    n_chips, model_flops=mf,
                                    resident_bytes=float(resident))
        record.update(
            ok=True,
            lower_s=round(t_lower - t0, 1),
            compile_s=round(t_compile - t_lower, 1),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "per_device_total": (mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     - mem.alias_size_in_bytes),
            },
            roofline=roof.to_dict(),
            probe=probe_info,
        )
        if save_hlo:
            with open(os.path.join(out_dir, f"{arch}__{shape_name}__{record['mesh']}.hlo"),
                      "w") as f:
                f.write(hlo)
    except Exception as e:  # noqa: BLE001 — dry-run reports, doesn't die
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["total_s"] = round(time.time() - t0, 1)
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{record['mesh']}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=2, default=str)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip cells whose JSON already records ok=true")
    args = ap.parse_args()

    from repro.launch.steps import all_cells

    cells = all_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    n_fail = 0
    for mp in meshes:
        for arch, shape in cells:
            mesh_tag = "2x16x16" if mp else "16x16"
            if args.skip_existing:
                p = os.path.join(args.out, f"{arch}__{shape}__{mesh_tag}.json")
                if os.path.exists(p):
                    with open(p) as f:
                        if json.load(f).get("ok"):
                            print(f"[SKIP] {arch:24s} {shape:16s} {mesh_tag}",
                                  flush=True)
                            continue
            rec = run_cell(arch, shape, mp, args.out, args.save_hlo)
            status = "OK " if rec["ok"] else "FAIL"
            extra = ""
            if rec["ok"]:
                r = rec["roofline"]
                extra = (f" mem/dev={rec['memory']['per_device_total']/2**30:.2f}GiB"
                         f" compute={r['compute_s']*1e3:.2f}ms"
                         f" mem[{(r['memory_lower_s'] or 0)*1e3:.2f}"
                         f",{r['memory_s']*1e3:.2f}]ms"
                         f" coll={r['collective_s']*1e3:.2f}ms"
                         f" bound={r['bottleneck_lower']}/{r['bottleneck']}"
                         f" useful={r['useful_ratio'] and round(r['useful_ratio'],3)}")
            else:
                n_fail += 1
                extra = " " + rec["error"][:160]
            print(f"[{status}] {arch:24s} {shape:16s} {rec['mesh']:8s}"
                  f" {rec['total_s']:7.1f}s{extra}", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
