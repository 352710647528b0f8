"""Chip benchmark of the MLA serving system: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs from the root of a checkout on a machine with the chips the cell asks
for; it exits non-zero, printing no result, where JAX finds no TPU or too
few.  One process holds the chip: it builds the cell (``harness.py``),
measures for ``--seconds`` with the load client in a process of its own,
checks the served tokens against the plain reference, and prints one JSON
object as the last line of standard output:

    {"correct", "attempted", "failed", "metrics": {name: {"value",
     "unit"}}, "device": {"platform", "kind", "count",
     "memory_peak_bytes"[, "busy_s", "window_s"]}[, "breakdown"],
     "check": {number: {"value", "limit"}}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (``metrics/<name>.py``), both as
``BENCHMARK.json`` assigns them.  The numbers the check compared, each
beside its limit, are also the last lines of standard error.

JAX's persistent compilation cache lives in ``chipbench/.jax_cache`` of
the checkout, so only a cell's first run there compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")


def log(msg) -> None:
    print(msg, file=sys.stderr, flush=True)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(bench: dict, cell: str, group: str) -> list:
    return [m for m in bench[group]
            if cell in m.get("workloads", [cell])]


def end_to_end(rec: dict) -> dict:
    """Host-clock metrics of the window from the client's record: TTFT
    from when a request was due to its first token, the gaps between
    consecutive tokens of each completion, and the tokens that arrived
    inside the window over its length."""
    from chipbench.stats import p95
    res = rec["result"]
    t0, t_end = res["t0"], res["t_end"]
    ttft, itl, streamed = [], [], 0
    for r in res["records"]:
        firsts = [c["times"][0] for c in r["choices"] if c["times"]]
        if firsts:
            ttft.append((min(firsts) - r["due"]) * 1e3)
        for c in r["choices"]:
            times = c["times"]
            itl += [(b - a) * 1e3 for a, b in zip(times, times[1:])]
            streamed += sum(1 for t in times if t0 <= t < t_end)
    out = {"setup_s": rec["setup_s"]}
    if ttft:
        out["ttft_p95_ms"] = p95(ttft)
    if itl:
        out["itl_p95_ms"] = p95(itl)
    out["output_tokens_per_s"] = streamed / rec["seconds"]
    return out


def read_metric(name: str, run: dict):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def result_line(rec: dict, trace: bool, bench: dict) -> dict:
    """The last line of a run, from ``harness.run_cell``'s record."""
    import jax
    name = rec["cell"]["name"]
    dev = rec["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": rec["peak_bytes"]}
    metrics = {}
    if trace:
        for m in metrics_of(bench, name, "per_layer"):
            v = read_metric(m["name"], rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(rec)
        for m in metrics_of(bench, name, "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    v = rec["check"]
    line = {"correct": bool(v["correct"]), "attempted": v["attempted"],
            "failed": v["failed"], "metrics": metrics, "device": device}
    t = rec["trace"]
    if trace and t:
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    line["check"] = {k: {"value": val, "limit": lim}
                     for k, (val, lim) in v["numbers"].items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from chipbench import harness
    from repro.hwmodel.platforms import device_platform

    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"chipbench: needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s); nothing was run")
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = harness.CompileCounter()
    rec = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START,
                           counter=counter,
                           platform=device_platform(devs[0]), log=log)
    line = result_line(rec, bool(args.trace), benchmark())
    print(json.dumps(line), flush=True)
    for k, c in line["check"].items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
