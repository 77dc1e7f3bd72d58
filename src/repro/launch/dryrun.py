"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh).

This is the deliverable proving the distribution config is coherent without
hardware: ``jax.jit(step).lower(**abstract_inputs).compile()`` must succeed
on the single-pod (16×16) and multi-pod (2×16×16) production meshes for
every assigned architecture and input shape; memory_analysis() proves the
footprint fits a 16 GiB v5e chip and cost_analysis() + collective parsing
feed the §Roofline tables.

Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_8b \
          --shape train_4k --mesh single
      PYTHONPATH=src python -m repro.launch.dryrun --all
"""

# The host platform must expose 512 fake devices BEFORE jax initializes,
# and the dry run must never take an attached chip: it lowers for described
# pods on host devices. These statements must stay first in this module.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (ARCH_IDS, INPUT_SHAPES, get_arch,
                                supports_shape)
from repro.core.fedlite import make_train_step
from repro.launch import analysis
from repro.launch.mesh import (PRODUCTION_DEVICE_KIND, device_peaks,
                               make_production_mesh)
from repro.launch.specs import (cache_specs, decode_token_specs, input_specs,
                                make_model, state_specs)
from repro.optim import get_optimizer
from repro.sharding import use_mesh

PEAKS = device_peaks(PRODUCTION_DEVICE_KIND)
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def lower_combo(arch_id: str, shape_id: str, mesh, *, with_pq: bool = True,
                save_hlo: str | None = None, force_f32: bool = False,
                inference_layout: bool = False):
    # inference_layout=False by default: §Perf C1 measured the TP-only
    # serving layout NEUTRAL on dense decode and WORSE on jamba (256-way
    # column splits cut attention heads below head granularity)
    """Lower + compile one (arch, shape) on ``mesh``; return the record."""
    import dataclasses as _dc
    cfg = get_arch(arch_id)
    if force_f32:
        cfg = _dc.replace(cfg, dtype="float32", param_dtype="float32")
    shape = INPUT_SHAPES[shape_id]
    model = make_model(cfg, with_pq=with_pq)
    world = mesh.devices.size

    t0 = time.time()
    with use_mesh(mesh):
        if shape.kind == "train":
            opt = get_optimizer(cfg.optimizer, 1e-4)
            step = make_train_step(model, opt, quantize=with_pq,
                                   microbatches=cfg.train_microbatches)
            state_s = state_specs(model, opt, mesh)
            batch_s = input_specs(cfg, shape, mesh)
            lowered = step.lower(state_s, batch_s)
        elif shape.kind == "prefill":
            batch_s = input_specs(cfg, shape, mesh, with_labels=False)
            caches_s = cache_specs(model, shape.global_batch, shape.seq_len, mesh)
            params_s = state_specs(model, get_optimizer("sgd", 0.0), mesh).params

            def prefill_fn(params, batch, caches):
                return model.prefill(params, batch, caches, quantize=with_pq)

            lowered = jax.jit(prefill_fn, donate_argnums=(2,)).lower(
                params_s, batch_s, caches_s)
        else:  # decode (optionally with the TP-only serving layout — C1)
            caches_s = cache_specs(model, shape.global_batch, shape.seq_len, mesh)
            params_s = state_specs(model, get_optimizer("sgd", 0.0), mesh,
                                   inference=inference_layout).params
            tok_s = decode_token_specs(cfg, shape, mesh)

            def decode_fn(params, caches, toks, pos):
                return model.decode_step(params, caches, toks, pos)

            pos_s = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(decode_fn, donate_argnums=(1,)).lower(
                params_s, caches_s, tok_s, pos_s)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = analysis.cost_summary(compiled)
    mem = analysis.memory_summary(compiled)
    coll = analysis.collective_stats(compiled.as_text(), world)
    wire = analysis.total_wire_bytes(coll)
    roof = analysis.roofline_terms(
        cost.get("flops", 0.0), cost.get("bytes_accessed", 0.0), wire,
        peak_flops=PEAKS.flops_bf16, hbm_bw=PEAKS.hbm_bw,
        ici_bw=PEAKS.ici_bw_per_link)

    # MODEL_FLOPS: 6·N_active·tokens (train fwd+bwd) or 2·N_active·tokens
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        model_flops = 2 * n_active * shape.global_batch
    model_flops_per_device = model_flops / world

    device_bytes = (mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
                    + mem["temp_size_in_bytes"] - mem["alias_size_in_bytes"])

    rec = {
        "arch": arch_id, "shape": shape_id,
        "inference_layout": inference_layout if shape.kind == "decode" else None,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "world": world, "kind": shape.kind, "with_pq": with_pq,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost": cost, "memory": mem, "collectives": coll,
        "wire_bytes_per_device": wire,
        "device_bytes": device_bytes,
        "fits_16GiB": device_bytes <= PEAKS.hbm_bytes,
        "model_flops_per_device": model_flops_per_device,
        "useful_flops_fraction": (model_flops_per_device /
                                  max(cost.get("flops", 1.0), 1.0)),
        "roofline": roof,
        "params_total": cfg.param_count(),
        "params_active": n_active,
    }
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())
        rec["hlo_path"] = save_hlo
    return rec


def run_one(arch_id, shape_id, mesh_kind, out_dir, *, with_pq=True,
            force=False, save_hlo=False, inference_layout=False):
    tag = f"{arch_id}__{shape_id}__{mesh_kind}" + ("" if with_pq else "__nopq")
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        print(f"[skip] {tag} (exists)")
        return json.load(open(path))
    if not supports_shape(arch_id, shape_id):
        rec = {"arch": arch_id, "shape": shape_id, "mesh": mesh_kind,
               "skipped": "long_500k requires sub-quadratic attention "
                          "(see DESIGN.md §3)"}
        json.dump(rec, open(path, "w"), indent=1)
        print(f"[skip-noted] {tag}")
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        hlo_path = path.replace(".json", ".hlo.txt") if save_hlo else None
        rec = lower_combo(arch_id, shape_id, mesh, with_pq=with_pq,
                          save_hlo=hlo_path,
                          inference_layout=inference_layout)
        cfg = get_arch(arch_id)
        if not rec["fits_16GiB"] and cfg.dtype == "bfloat16":
            # The CPU backend legalizes bf16 compute to f32, materializing
            # f32 copies + layout copies of every large bf16 buffer (verified
            # on a minimal repro; see EXPERIMENTS.md §Dry-run). Estimate the
            # TPU-native footprint by compiling the same program in f32
            # (which CPU executes natively, no copies) and halving the temp.
            try:
                rec32 = lower_combo(arch_id, shape_id, mesh, with_pq=with_pq,
                                    force_f32=True)
                temp_est = rec32["memory"]["temp_size_in_bytes"] / 2
                dev_est = (rec["memory"]["argument_size_in_bytes"]
                           + rec["memory"]["output_size_in_bytes"]
                           - rec["memory"]["alias_size_in_bytes"] + temp_est)
                rec["tpu_bf16_estimate"] = {
                    "f32_temp_bytes": rec32["memory"]["temp_size_in_bytes"],
                    "device_bytes_estimate": dev_est,
                    "fits_16GiB_estimate": dev_est <= PEAKS.hbm_bytes,
                }
            except Exception as e:  # noqa: BLE001
                rec["tpu_bf16_estimate"] = {"error": str(e)[:200]}
        json.dump(rec, open(path, "w"), indent=1)
        r = rec["roofline"]
        est = rec.get("tpu_bf16_estimate", {})
        est_s = (f" tpu_est={est['device_bytes_estimate']/2**30:.1f}GiB"
                 f"(fits={est['fits_16GiB_estimate']})"
                 if "device_bytes_estimate" in est else "")
        print(f"[ok] {tag}: compile={rec['compile_s']:.0f}s "
              f"bytes/dev={rec['device_bytes']/2**30:.2f}GiB "
              f"fits={rec['fits_16GiB']}{est_s} bound={r['bound']} "
              f"t=(c {r['compute_s']*1e3:.2f} | m {r['memory_s']*1e3:.2f} | "
              f"coll {r['collective_s']*1e3:.2f}) ms")
        return rec
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec = {"arch": arch_id, "shape": shape_id, "mesh": mesh_kind,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        json.dump(rec, open(path, "w"), indent=1)
        print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:200]}")
        return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id or 'all'")
    ap.add_argument("--shape", default=None, help="input shape id or 'all'")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-pq", action="store_true",
                    help="lower the SplitFed baseline (no quantizer)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--inference-layout-decode", action="store_true",
                    help="decode with the TP-only serving param layout "
                         "(measured neutral-to-worse; see §Perf C1)")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if (args.all or args.arch in (None, "all")) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape in (None, "all")) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_one(arch, shape, mesh_kind, args.out,
                              with_pq=not args.no_pq, force=args.force,
                              save_hlo=args.save_hlo,
                              inference_layout=args.inference_layout_decode)
                failures += 1 if "error" in rec else 0
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
