"""Readings that a cell's check limit is set from; not part of a run.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...]

For each seed, in one process on the chip: the cell's traffic for a short
window at the cell's own load, then the check with its control: the widest
gap of the served tokens (the program's reading) and, at the same
positions, the widest gap of the tokens the float8 reference puts first
(the control's reading).  One JSON line per seed.  The limit in
``workloads/<cell>.json`` lies between the largest program reading and
the smallest control reading (PERF.md gives both).
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from chipbench import harness
    from chipbench.run import CACHE_DIR, log
    from repro.hwmodel.platforms import device_platform

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"calibrate: JAX's first device is {dev.platform}; nothing run")
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = harness.CompileCounter()
    for seed in args.seeds:
        t = time.perf_counter()
        rec = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=t, counter=counter,
                               platform=device_platform(dev), control=True,
                               log=log)
        v = rec["check"]
        print(json.dumps({
            "seed": seed, "max_logit_gap": v["numbers"]["max_logit_gap"][0],
            "control_max_logit_gap": v["control_max_logit_gap"],
            "sampled_tokens": v["sampled_tokens"],
            "sampled_requests": v["sampled_requests"],
            "stream_mismatch": v["stream_mismatch"],
            "attempted": v["attempted"], "failed": v["failed"],
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
