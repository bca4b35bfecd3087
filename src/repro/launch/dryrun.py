import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch × shape) on the
production meshes, record memory / cost / collective analysis.

MUST be run as its own process (the XLA flag above must precede any jax
device initialization — hence the unusual import order).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b \
        --shape train_4k --mesh pod --out experiments/dryrun/
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_by_name  # noqa: E402
from repro.launch import sharding as SH                       # noqa: E402
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.launch.roofline import model_flops, roofline_terms  # noqa: E402
from repro.launch.steps import (abstract_inputs, abstract_train_state,  # noqa: E402
                                build_decode_step, build_prefill_step,
                                build_train_step, input_shardings,
                                train_state_shardings)

SKIP_LONG_CONTEXT = {
    # pure full-attention archs: long_500k requires sub-quadratic attention
    "nemotron-4-340b", "mistral-large-123b", "mistral-nemo-12b",
    "phi3.5-moe-42b-a6.6b", "internvl2-26b", "seamless-m4t-large-v2",
}


def applicable(arch_id: str, shape_name: str) -> bool:
    if shape_name == "long_500k" and arch_id in SKIP_LONG_CONTEXT:
        return False
    return True


def dryrun_cell(arch_id: str, shape_name: str, mesh_name: str,
                rules=None, verbose: bool = True) -> dict:
    """Lower + compile one cell; returns the record dict."""
    cfg = get_config(arch_id)
    shape = shape_by_name(shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = int(np.prod(list(mesh.shape.values())))
    if shape.kind in ("prefill", "decode") and rules is None:
        # serving: TP-only weights where they fit (see steps.serve_rules)
        from repro.launch.steps import serve_rules
        rules = serve_rules(cfg, tp=mesh.shape["model"]) or None
    t0 = time.time()

    with jax.set_mesh(mesh):
        batch_abs = abstract_inputs(cfg, shape)
        batch_sh = input_shardings(cfg, shape, mesh, rules)

        if shape.kind == "train":
            params_abs, opt_abs, opt = abstract_train_state(cfg)
            params_sh, opt_sh = train_state_shardings(cfg, mesh, rules)
            step_fn = build_train_step(cfg, shape, mesh, opt)
            step_abs = jax.ShapeDtypeStruct((), jax.numpy.int32)
            from jax.sharding import NamedSharding, PartitionSpec as P
            scalar_sh = NamedSharding(mesh, P())
            jitted = jax.jit(
                step_fn,
                in_shardings=(params_sh, opt_sh, scalar_sh, batch_sh),
                out_shardings=(params_sh, opt_sh, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, step_abs, batch_abs)
        elif shape.kind == "prefill":
            params_abs, _, _ = abstract_train_state(cfg)
            params_sh, _ = train_state_shardings(cfg, mesh, rules)
            step_fn = build_prefill_step(cfg)
            from jax.sharding import NamedSharding, PartitionSpec as P
            out_abs = jax.eval_shape(step_fn, params_abs, batch_abs)
            logits_sh = NamedSharding(
                mesh, SH.resolve_axes(("batch", "vocab"), out_abs[0].shape,
                                      mesh, rules))
            caches_sh = SH.cache_sharding_rules(mesh, out_abs[1], rules)
            jitted = jax.jit(step_fn, in_shardings=(params_sh, batch_sh),
                             out_shardings=(logits_sh, caches_sh))
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            params_abs, _, _ = abstract_train_state(cfg)
            params_sh, _ = train_state_shardings(cfg, mesh, rules)
            step_fn = build_decode_step(cfg)
            jitted = jax.jit(
                step_fn,
                in_shardings=(params_sh, batch_sh),
                out_shardings=(batch_sh["token"],
                               batch_sh["caches"]),
                donate_argnums=(1,))   # donate caches: in-place update
            lowered = jitted.lower(params_abs, batch_abs)

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # donated args alias outputs; peak residency ≈ args + temps
    peak_resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    mf = model_flops(cfg, shape)
    # trip-aware re-derivation: cost_analysis counts while bodies once, so
    # scale FLOPs by the HLO-walk dot count and bytes by max(XLA, dot
    # operand traffic) — see roofline.hlo_cost.
    from repro.launch.roofline import hlo_cost
    t_flops, t_dot_bytes = hlo_cost(hlo)
    cost_fixed = dict(cost)
    cost_fixed["flops"] = max(float(cost.get("flops", 0.0)), t_flops)
    cost_fixed["bytes accessed"] = max(float(cost.get("bytes accessed", 0.0)),
                                       t_dot_bytes)
    rl = roofline_terms(arch_id, shape_name, mesh_name, chips, cost_fixed,
                        hlo, float(peak_resident), mf)

    record = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "chips": chips, "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
            "peak_resident_bytes": peak_resident,
        },
        "cost": {k: float(v) for k, v in cost.items()
                 if k in ("flops", "bytes accessed")},
        "roofline": rl.to_dict(),
        "status": "ok",
    }
    if verbose:
        print(f"[dryrun] {arch_id} × {shape_name} × {mesh_name}: "
              f"compile={record['compile_s']}s "
              f"mem/chip={peak_resident/1e9:.2f}GB "
              f"flops/chip={cost.get('flops', 0):.3e} "
              f"coll/chip={rl.coll_bytes_per_chip:.3e}B "
              f"dominant={rl.dominant} "
              f"roofline_frac={rl.roofline_fraction:.3f}")
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/1e9:.2f}GB "
              f"out={mem.output_size_in_bytes/1e9:.2f}GB "
              f"temp={mem.temp_size_in_bytes/1e9:.2f}GB")
        print(f"  cost_analysis: {record['cost']}")
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None,
                    choices=[s.name for s in SHAPES])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch × shape)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    archs = list(ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) \
        else [args.shape]

    failures = 0
    for arch in archs:
        for shp in shapes:
            for mesh_name in meshes:
                tag = f"{arch}__{shp}__{mesh_name}"
                path = os.path.join(args.out, tag + ".json")
                if not applicable(arch, shp):
                    rec = {"arch": arch, "shape": shp, "mesh": mesh_name,
                           "status": "skip", "reason": "full-attention arch; "
                           "long_500k needs sub-quadratic attention"}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=2)
                    print(f"[dryrun] SKIP {tag} (full attention)")
                    continue
                try:
                    rec = dryrun_cell(arch, shp, mesh_name)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shp, "mesh": mesh_name,
                           "status": "fail", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
    print(f"[dryrun] done; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
