import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path # noqa: E402

import jax               # noqa: E402

from repro.configs import registry, shapes as S               # noqa: E402
from repro.launch import analysis, flops as flops_mod, hlo_costs, sharding, steps  # noqa: E402
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.optim import adamw                                 # noqa: E402

"""Multi-pod dry-run: ``.lower().compile()`` for every
(architecture × input shape × mesh) cell — 40 cells × {1-pod 16×16, 2-pod
2×16×16}.  Proves the distribution config is coherent: sharding mismatches,
compile-time OOM, and unsupported collectives all fail here.

Outputs one JSON per cell under experiments/dryrun/ with memory analysis,
cost analysis, per-collective wire bytes, and the three roofline terms.
"""


def param_tree_for(arch_id: str, cfg):
    fam = registry.ARCHS[arch_id].family
    if fam == "lm":
        from repro.models.lm import transformer as T
        return T.param_specs(cfg)
    if fam == "gnn":
        if arch_id.startswith("gcn"):
            from repro.models.gnn import gcn as m
        elif arch_id.startswith("gat"):
            from repro.models.gnn import gat as m
        elif arch_id == "schnet":
            from repro.models.gnn import schnet as m
        else:
            from repro.models.gnn import dimenet as m
        return jax.eval_shape(lambda k: m.init_params(k, cfg),
                              jax.random.key(0))
    from repro.models.recsys import dlrm
    return jax.eval_shape(lambda k: dlrm.init_params(k, cfg),
                          jax.random.key(0))


def lower_cell(arch_id: str, shape_name: str, multi_pod: bool,
               override_pspecs=None):
    """Lower + compile one cell; returns (record dict, compiled)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    shape = registry.shapes_for(arch_id)[shape_name]
    cfg = registry.get_config(arch_id, shape=shape)
    import dataclasses as _dc
    _dp = tuple(a for a in mesh.axis_names if a != "model")
    if registry.ARCHS[arch_id].family == "lm":
        cfg = _dc.replace(cfg, dp_axes=_dp, tp_axis="model")
    elif hasattr(cfg, "dp_axes"):
        cfg = _dc.replace(cfg, dp_axes=_dp)
    specs, statics = registry.input_specs(arch_id, shape_name)
    step = steps.build_step(arch_id, cfg, shape, statics)

    params = param_tree_for(arch_id, cfg)
    p_pspec = sharding.param_pspecs(arch_id, params, mesh)
    if override_pspecs is not None:
        p_pspec = override_pspecs(p_pspec)
    in_pspec = sharding.input_pspecs(arch_id, shape, specs, mesh)
    p_sh = sharding.to_named(p_pspec, mesh)
    in_sh = sharding.to_named(in_pspec, mesh)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if steps.needs_optimizer(arch_id, shape):
            opt = jax.eval_shape(adamw.init_state, params)
            opt_pspec = sharding.opt_state_pspecs(p_pspec)
            opt_sh = sharding.to_named(opt_pspec, mesh)
            jitted = jax.jit(step, in_shardings=(p_sh, opt_sh, in_sh),
                             out_shardings=(p_sh, opt_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt, specs)
        else:
            jitted = jax.jit(step, in_shardings=(p_sh, in_sh))
            lowered = jitted.lower(params, specs)
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    cflops, cbytes, ccoll = hlo_costs.corrected_costs(hlo, n_dev)
    mf = flops_mod.model_flops(arch_id, shape_name, statics)

    mem_rec = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            mem_rec[attr] = getattr(mem, attr, None)

    roof = analysis.make_roofline(
        arch_id, shape_name, "2x16x16" if multi_pod else "16x16", n_dev,
        cflops, cbytes, sum(ccoll.values()), mf,
        mem_per_device=float(mem_rec.get("temp_size_in_bytes") or 0)
        + float(mem_rec.get("argument_size_in_bytes") or 0))
    record = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_rec,
        "cost_analysis_raw": {k: v for k, v in (cost or {}).items()
                              if isinstance(v, (int, float))
                              and not k.startswith("utilization")},
        "collectives": {k: v for k, v in ccoll.items()},
        "roofline": roof.to_json(),
    }
    return record, compiled


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    cells = [(a, s) for a, s in registry.all_cells()
             if (args.arch in ("all", a)) and (args.shape in ("all", s))]
    n_fail = 0
    for arch_id, shape_name in cells:
        for multi_pod in meshes:
            mesh_name = "2x16x16" if multi_pod else "16x16"
            fname = out_dir / f"{arch_id}__{shape_name}__{mesh_name}.json"
            if args.skip_existing and fname.exists():
                print(f"[skip] {fname.name}")
                continue
            print(f"[dryrun] {arch_id} × {shape_name} × {mesh_name} ...",
                  flush=True)
            try:
                rec, compiled = lower_cell(arch_id, shape_name, multi_pod)
                print(f"  ok: compile {rec['compile_s']}s  "
                      f"flops/dev {rec['roofline']['hlo_flops']:.3e}  "
                      f"coll {rec['roofline']['coll_bytes']:.3e}B  "
                      f"bottleneck {rec['roofline']['bottleneck']}")
                del compiled
            except Exception as e:  # noqa: BLE001 — record and continue
                n_fail += 1
                rec = {"arch": arch_id, "shape": shape_name,
                       "mesh": mesh_name, "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"  FAIL: {type(e).__name__}: {str(e)[:300]}")
            fname.write_text(json.dumps(rec, indent=1))
    print(f"done; {n_fail} failures")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
