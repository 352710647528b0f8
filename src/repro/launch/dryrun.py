"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, print memory/cost analysis, and emit the
roofline artifacts consumed by EXPERIMENTS.md and benchmarks/roofline.

MUST set the device-count override before ANY other import (jax locks the
device count on first init).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax.numpy as jnp

from repro import configs, models
from repro.analysis import hlo as hloa
from repro.configs.shapes import SHAPES
from repro.hwmodel.platforms import (TPU_V5E_HBM_BW, TPU_V5E_ICI_BW,
                                     TPU_V5E_PEAK_FLOPS)
from repro.hwmodel.roofline import three_term
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.optim import AdamWConfig
from repro.runtime.steps import (TrainStepConfig, make_prefill_step,
                                 make_serve_step, make_train_step)

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "../../../benchmarks/artifacts/dryrun")


def _opt_cfg(cfg) -> AdamWConfig:
    big = models.param_count(cfg) > 100e9
    return AdamWConfig(moment_dtype=jnp.bfloat16 if big else jnp.float32)


def lower_cell(arch: str, shape_name: str, mesh, *, scheme: str = "rc",
               impl: str = "chunked", loss_chunk: int = 256,
               shard_cache_seq: Optional[bool] = None,
               policy: Optional[str] = None,
               compute_dtype=jnp.bfloat16):
    """Returns (lowered, meta) for one cell.

    ``policy``: sharding policy override ('train'/'serve' baseline;
    'serve_2dtp' resident-weight serving TP; 'dp' replicated weights)."""
    cfg = configs.full(arch)
    shape = SHAPES[shape_name]
    if cfg.attn_kind != "mla":
        scheme = "seq"   # scheme only affects MLA archs
    batch = S.batch_specs(cfg, shape, compute_dtype)
    params = S.param_specs(cfg, compute_dtype)

    if shape.kind == "train":
        step_fn, _ = make_train_step(
            cfg, mesh, _opt_cfg(cfg),
            TrainStepConfig(compute_dtype=compute_dtype, impl=impl,
                            scheme=scheme, loss_chunk=loss_chunk),
            policy=policy or "train")
        opt = S.opt_specs(cfg, _opt_cfg(cfg), compute_dtype)
        lowered = step_fn.lower(params, opt, batch)
    elif shape.kind == "prefill":
        fn = make_prefill_step(cfg, mesh, batch=shape.global_batch,
                               capacity=shape.seq_len + 8,
                               compute_dtype=compute_dtype, impl=impl,
                               scheme=scheme, policy=policy or "serve")
        args = [params, batch["tokens"]]
        if "embeds" in batch:
            args.append(batch["embeds"])
        lowered = fn.lower(*args)
    else:  # decode
        if shard_cache_seq is None:
            shard_cache_seq = shape.global_batch == 1
        maker = make_serve_step(cfg, mesh, compute_dtype=compute_dtype,
                                impl=impl, scheme=scheme,
                                shard_cache_seq=shard_cache_seq,
                                policy=policy or "serve")
        fn = maker(batch["cache"], shape.global_batch, shape.seq_len)
        lowered = fn.lower(params, batch["token"], batch["cache"],
                           batch["index"])
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "scheme": scheme if cfg.attn_kind == "mla" else None,
            "impl": impl, "chips": int(mesh.devices.size),
            "policy": policy or ("train" if shape.kind == "train" else "serve"),
            "mesh": "x".join(map(str, mesh.devices.shape))}
    return lowered, meta


def analyze_compiled(lowered, compiled, chips: int) -> Dict[str, Any]:
    ca = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hc = hloa.analyze(compiled.as_text(), num_partitions=chips)
    terms = three_term(
        hlo_flops=hc.flops * chips, hlo_bytes=hc.bytes * chips,
        coll_bytes=hc.collective_bytes, chips=chips,
        peak=TPU_V5E_PEAK_FLOPS, hbm_bw=TPU_V5E_HBM_BW, ici_bw=TPU_V5E_ICI_BW)
    out = {
        "xla_cost_analysis": {k: float(ca[k]) for k in
                              ("flops", "bytes accessed") if k in ca},
        "hlo_flops_per_chip": hc.flops,
        "hlo_bytes_per_chip": hc.bytes,
        "collective_bytes_per_chip": hc.collective_bytes,
        "collective_by_kind": hc.collective_by_kind,
        "while_trip_counts": len(hc.while_trip_counts),
        "hlo_warnings": hc.warnings[:5],
        "t_compute": terms.t_compute,
        "t_memory": terms.t_memory,
        "t_collective": terms.t_collective,
        "bound": terms.bound,
        "roofline_fraction": terms.roofline_fraction,
    }
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                out[f"mem_{attr}"] = int(v)
        # Steady-state HBM residency per chip: arguments + non-aliased
        # outputs (aliased outputs are donated in-place updates).  The CPU
        # lowering's temp size additionally contains bf16->f32 float-
        # normalization phantoms that do not exist on TPU (see
        # EXPERIMENTS.md §Methodology), so it is reported but not gating.
        args = getattr(mem, "argument_size_in_bytes", 0)
        outb = getattr(mem, "output_size_in_bytes", 0)
        alias = getattr(mem, "alias_size_in_bytes", 0)
        out["hbm_residency_gib"] = round((args + outb - alias) / 2 ** 30, 2)
    return out


def run_cell(arch: str, shape_name: str, mesh, *, save: bool = True,
             verbose: bool = True, **opts) -> Dict[str, Any]:
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, mesh, **opts)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    result = {**meta, "lower_s": round(t_lower, 1),
              "compile_s": round(t_compile, 1),
              **analyze_compiled(lowered, compiled, meta["chips"])}
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} ({meta['mesh']}): "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s "
              f"bound={result['bound']} "
              f"t=(C {result['t_compute']:.3e}, M {result['t_memory']:.3e}, "
              f"X {result['t_collective']:.3e})s")
        mem = compiled.memory_analysis()
        if mem is not None:
            print(f"         mem: temp={getattr(mem, 'temp_size_in_bytes', 0)/2**30:.2f} GiB "
                  f"args={getattr(mem, 'argument_size_in_bytes', 0)/2**30:.2f} GiB "
                  f"out={getattr(mem, 'output_size_in_bytes', 0)/2**30:.2f} GiB")
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        tag = f"{meta['mesh']}_{arch}_{shape_name}"
        if opts.get("scheme") and configs.full(arch).attn_kind == "mla":
            tag += f"_{opts['scheme']}"
        if opts.get("policy"):
            tag += f"_{opts['policy']}"
        with open(os.path.join(ARTIFACT_DIR, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--scheme", default="rc",
                    help="MLA execution scheme (naive|seq|rc|ru)")
    ap.add_argument("--impl", default="chunked")
    ap.add_argument("--include-skipped", action="store_true")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    archs = [args.arch] if args.arch else configs.ARCHS
    failures = []
    for arch in archs:
        skips = configs.skip_shapes(arch)
        shapes = [args.shape] if args.shape else list(SHAPES)
        for sh in shapes:
            if sh in skips and not args.include_skipped:
                print(f"[dryrun] {arch} x {sh}: SKIP ({skips[sh]})")
                continue
            try:
                run_cell(arch, sh, mesh, scheme=args.scheme, impl=args.impl)
            except Exception as e:  # noqa: BLE001 — report, continue
                failures.append((arch, sh, repr(e)))
                print(f"[dryrun] {arch} x {sh}: FAIL {e}")
                traceback.print_exc()
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:")
        for a, s, e in failures:
            print(f"  {a} x {s}: {e}")
        raise SystemExit(1)
    print("\n[dryrun] ALL CELLS PASSED")


if __name__ == "__main__":
    main()
