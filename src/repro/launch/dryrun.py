import os
os.environ["JAX_PLATFORMS"] = "cpu"      # host devices only: never a chip
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + (
    " --xla_force_host_platform_device_count=512")).strip()

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (``python -m repro.launch.dryrun ...``): the
first lines pin the CPU and force 512 host platform devices BEFORE any
other import so ``jax.make_mesh`` can build the production meshes; smoke
tests and benchmarks must never import this module.

Per cell it lowers the right step function (train_step / prefill_step /
decode_step) against ShapeDtypeStruct inputs (no allocation), compiles it,
and dumps to ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``:
  - memory_analysis (bytes per device: args/outputs/temps/peak)
  - cost_analysis (XLA's own numbers, while-bodies counted once)
  - while-aware per-device costs (repro.roofline.hlo_analysis): HLO_FLOPs,
    HBM bytes, per-kind collective bytes — the §Roofline inputs
  - MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE; 2·N·tokens for serve)
  - lower/compile wall times and status.
"""

import argparse
import dataclasses
import glob
import json
import shutil
import tempfile
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, applicable, input_specs
from repro.launch import mesh as mesh_lib
from repro.models import sharding
from repro.models.lm import LM
from repro.roofline import hlo_analysis
from repro.roofline.model_flops import model_flops
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step
from repro.serve.serve_step import make_decode_step, make_prefill_step


def _sds(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    """Lower + compile one cell; returns (lowered, compiled, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    # serve cells keep the 2d profile (decode/prefill have no optimizer state
    # and benefit from TP); train cells honor the arch's parallelism profile.
    # ZeRO-3 ("fsdp") additionally requires batch >= device count — on the
    # 2-pod mesh train_4k's 256 batch < 512 chips, so it falls back to 2d
    # (measured regression otherwise; EXPERIMENTS.md §Perf profile note).
    profile = cfg.parallelism if shape.kind == "train" else "2d"
    if profile == "fsdp" and shape.global_batch % mesh.devices.size != 0:
        profile = "2d"
    mesh_lib.activate(mesh, multi_pod, profile)
    model = LM(cfg)

    params_sds, specs = model.abstract_init(jax.random.PRNGKey(0))
    param_sh = sharding.physical_shardings(specs, params_sds)
    batch_sds = input_specs(cfg, shape)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "n_devices": mesh.devices.size,
            "params": float(sum(np.prod(a.shape)
                                for a in jax.tree.leaves(params_sds)))}

    with mesh:
        if shape.kind == "train":
            opt_sds = jax.eval_shape(init_opt_state, params_sds)
            opt_sh = type(opt_sds)(
                mu=param_sh, nu=param_sh,
                step=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
            step_fn = make_train_step(model, AdamWConfig())
            lowered = jax.jit(
                step_fn,
                in_shardings=(param_sh, opt_sh, None),
                out_shardings=(param_sh, opt_sh, None),
            ).lower(params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            step_fn = make_prefill_step(model)
            lowered = jax.jit(step_fn, in_shardings=(param_sh, None, None)
                              ).lower(params_sds, batch_sds, cache_sds)
        else:  # decode
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            step_fn = make_decode_step(model)
            lowered = jax.jit(step_fn, in_shardings=(param_sh, None, None, None)
                              ).lower(params_sds, tokens, cache_sds, pos)
    return lowered, meta, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: str):
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16", "status": "error"}
    tag = f"{arch}__{shape_name}__{record['mesh']}"
    try:
        lowered, meta, cfg, shape = lower_cell(arch, shape_name, multi_pod)
        t_lower = time.time() - t0
        t1 = time.time()
        # dump the post-SPMD-partitioning HLO: per-device, still bf16 (the
        # CPU backend legalizes bf16->f32 later, which would inflate byte
        # counts 2x vs the TPU target), still while-structured
        dump_dir = tempfile.mkdtemp(prefix="dryrun_hlo_")
        compiled = lowered.compile(compiler_options={
            "xla_dump_to": dump_dir,
            "xla_dump_hlo_pass_re": "spmd-partitioning",
        })
        t_compile = time.time() - t1
        record.update(meta)

        mem = compiled.memory_analysis()
        mem_d = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes", "peak_memory_in_bytes"):
            if mem is not None and hasattr(mem, attr):
                mem_d[attr] = int(getattr(mem, attr))
        ca = compiled.cost_analysis() or {}
        spmd_files = sorted(glob.glob(
            os.path.join(dump_dir, "*after_spmd-partitioning*.txt")))
        if not spmd_files:
            raise RuntimeError("no spmd-partitioning dump found")
        with open(spmd_files[-1]) as f:
            costs = hlo_analysis.analyze(f.read())
        shutil.rmtree(dump_dir, ignore_errors=True)

        n_dev = meta["n_devices"]
        mf = model_flops(cfg, shape)
        record.update({
            "status": "ok",
            "t_lower_s": t_lower, "t_compile_s": t_compile,
            "memory_analysis": mem_d,
            "xla_cost_analysis": {k: float(v) for k, v in ca.items()
                                  if isinstance(v, (int, float))},
            "per_device": {
                "hlo_flops": costs.flops,
                "hbm_bytes": costs.hbm_bytes,
                "collective_bytes": costs.collective_bytes,
                "collectives": costs.collectives,
            },
            "while_trips": costs.while_trips,
            "model_flops_global": mf,
            "model_flops_per_device": mf / n_dev,
        })
        print(f"[dryrun] OK  {tag}: lower {t_lower:.1f}s compile "
              f"{t_compile:.1f}s flops/dev {costs.flops:.3e} "
              f"coll/dev {costs.collective_bytes:.3e}B")
    except Exception as e:  # noqa: BLE001 — record and continue the campaign
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] FAIL {tag}: {record['error']}")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"{tag}.json"), "w") as f:
        json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        cfg = get_config(arch)
        for shape_name in shapes:
            if not applicable(cfg, shape_name):
                print(f"[dryrun] SKIP {arch}__{shape_name} "
                      f"(long-context requires sub-quadratic arch)")
                n_skip += 1
                continue
            for mp in meshes:
                tag = (f"{arch}__{shape_name}__"
                       f"{'2x16x16' if mp else '16x16'}")
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") == "ok":
                            n_skip += 1
                            continue
                rec = run_cell(arch, shape_name, mp, args.out)
                n_ok += rec["status"] == "ok"
                n_fail += rec["status"] != "ok"
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed, {n_skip} skipped")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
