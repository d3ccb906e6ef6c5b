import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

The two lines above MUST stay first — jax locks the device count on first
init, and only the dry-run wants 512 placeholder devices.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--mode pipeline]

Writes one JSON per combo under experiments/dryrun/ with memory analysis,
cost analysis, collective bytes and roofline terms (read by
benchmarks/roofline and EXPERIMENTS.md).
"""

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, get_arch, get_shape, SHAPES  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.jaxpr_cost import step_cost  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    adapt_config,
    batch_specs,
    build_model,
    cache_spec_tree,
    make_decode_step,
    make_prefill_step,
    make_sgld_train_step,
    param_structs,
)

OUTDIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                      "experiments", "dryrun")


def lower_combo(arch_id: str, shape_name: str, *, multi_pod: bool = False,
                mode: str = "sync", opts: tuple = (), micro: int = 0,
                verbose: bool = True):
    """Lower+compile one combination; returns result dict.

    opts/micro are the §Perf hillclimb switches; mode "sync" + empty opts is
    the paper-faithful baseline."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    num_devices = mesh.size
    shape = get_shape(shape_name)
    if micro:
        from dataclasses import replace as _replace
        shape = _replace(shape, num_microbatches=micro)
    cfg0 = get_arch(arch_id)
    model, cfg, baxes, faxes = build_model(cfg0, shape, mesh, opts)

    pstructs, pshard = param_structs(cfg, mesh, faxes)
    bstructs = batch_specs(cfg, shape, mesh, baxes)
    rep = NamedSharding(mesh, P())
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = make_sgld_train_step(model, shape, mode=mode)
            key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
            if mode == "pipeline":
                args = (pstructs, pstructs, bstructs, key)
                lowered = jax.jit(
                    step, out_shardings=(pshard, pshard, rep)).lower(*args)
            else:
                args = (pstructs, bstructs, key)
                lowered = jax.jit(
                    step, out_shardings=(pshard, rep)).lower(*args)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            args = (pstructs, bstructs)
            lowered = jax.jit(step).lower(*args)
        else:  # decode
            step = make_decode_step(model)
            cstructs, cshard = cache_spec_tree(model, cfg, shape, mesh, baxes)
            bstructs_d = batch_specs(cfg, shape, mesh, baxes, kind="decode")
            args = (pstructs, cstructs, bstructs_d)
            lowered = jax.jit(step, out_shardings=(None, cshard)).lower(*args)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        acost = step_cost(step, *args, num_devices=num_devices)

    mem = rl.memory_report(compiled)
    mf = rl.model_flops(cfg, shape)
    hlo = compiled.as_text()
    roof = rl.analyze(f"{arch_id}/{shape_name}", compiled, num_devices, mf,
                      hlo_text=hlo, jaxpr_cost=acost)

    tag = mode + ("" if not opts else "+" + "+".join(opts)) \
        + (f"+micro{micro}" if micro else "")
    from repro.configs.base import ALIASES
    canon = ALIASES.get(arch_id, arch_id).replace("-", "_").replace(".", "p")
    result = {
        "arch": canon,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": tag,
        "kind": shape.kind,
        "num_devices": num_devices,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "roofline": {
            "flops_per_device": roof.flops_per_device,
            "bytes_per_device": roof.bytes_per_device,
            "collective_bytes_per_device": roof.collective_bytes_per_device,
            "collective_breakdown": roof.collective_breakdown,
            "t_compute": roof.t_compute,
            "t_memory": roof.t_memory,
            "t_collective": roof.t_collective,
            "dominant": roof.dominant,
            "model_flops_global": roof.model_flops_global,
            "hlo_flops_global": roof.hlo_flops_global,
            "useful_ratio": roof.useful_ratio,
        },
    }
    if verbose:
        print(json.dumps({k: result[k] for k in
                          ("arch", "shape", "mesh", "mode", "compile_s")}),
              flush=True)
        print("  memory:", {k: f"{v/2**30:.2f}GiB" for k, v in mem.items()
                            if "size" in k}, flush=True)
        print(" ", roof.summary(), flush=True)
    return result


def save_result(result: dict, outdir: str = OUTDIR, suffix: str = ""):
    os.makedirs(outdir, exist_ok=True)
    fname = (f"{result['arch']}__{result['shape']}__{result['mesh']}"
             f"__{result['mode']}{suffix}.json")
    with open(os.path.join(outdir, fname), "w") as f:
        json.dump(result, f, indent=2)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (see configs)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="sync", choices=["sync", "pipeline"])
    ap.add_argument("--opts", default="",
                    help="comma list: attn_shard,fsdp,unroll,padvocab")
    ap.add_argument("--micro", type=int, default=0,
                    help="override num_microbatches (train shapes)")
    ap.add_argument("--outdir", default=OUTDIR)
    args = ap.parse_args(argv)

    combos = []
    if args.all:
        combos = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        try:
            res = lower_combo(arch, shape, multi_pod=args.multi_pod,
                              mode=args.mode,
                              opts=tuple(o for o in args.opts.split(",") if o),
                              micro=args.micro)
            save_result(res, args.outdir)
        except Exception as e:  # noqa: BLE001 — report and continue
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print(f"FAILED {len(failures)}/{len(combos)}:", failures)
        sys.exit(1)
    print(f"OK: {len(combos)} combinations lowered+compiled")


if __name__ == "__main__":
    main()
