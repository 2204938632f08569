"""Run one benchmark cell once, on the chip this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the cell needs is found by name from ``BENCHMARK.json`` (see
``harness/spec.py``). The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each number compared with
the plain reference beside its limit. The same numbers end standard error.
A run on anything but a TPU, or on fewer chips than the cell asks for, exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The compile cache and traces stay inside the checkout, at fixed paths.
CACHE_DIR = os.path.join(ROOT, ".chipbench", "jax_cache")
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(chips: int) -> dict:
    """The chips as JAX reports them; exits where they are not TPUs or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: JAX reports {devs[0].platform!r}, not a TPU; no result")
    if len(devs) < chips:
        sys.exit(f"run.py: {len(devs)} chips, the cell asks for {chips}; no result")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def loop(cell):
    """The loop that drives the cell's kind of traffic: ``harness/<kind>.py``."""
    return importlib.import_module(f"harness.{cell.traffic['kind']}")


def judge(checks: dict) -> bool:
    """``correct``: every number compared, {name: (value, limit)}, within its limit."""
    return bool(checks) and all(math.isfinite(v) and v <= lim for v, lim in checks.values())


def execute(cell, seed: int, seconds: float, trace: bool, peaks: dict):
    """Drive the cell; returns (result dict, Run)."""
    from harness.core import Run
    from harness.reference import Dims

    run = Run(cell=cell, dims=Dims.of(cell.config), peaks=peaks, trace_on=trace)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run.data["trace_dir"] = TRACE_DIR
    res = loop(cell).drive(run, seed, seconds)
    if trace:
        from harness import trace as T
        run.trace = T.reduce(T.extract(TRACE_DIR))
    return res, run


def assemble(cell, res: dict, run, device: dict, trace: bool) -> dict:
    from harness.spec import reader

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v = res["e2e"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    checks = {k: {"value": v, "limit": lim} for k, (v, lim) in res["checks"].items()}
    out = {"correct": judge(res["checks"]), "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None):
    args = parse(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro  # noqa: F401  (the system under test; without it there is no run)
    from harness import spec

    cell = spec.find_cell(args.workload)
    setup_jax()
    device = device_info(cell.chips)
    peaks = spec.peaks(device["kind"])
    res, run = execute(cell, args.seed, args.seconds, bool(args.trace), peaks)
    out = assemble(cell, res, run, device, bool(args.trace))
    print(json.dumps({k: v for k, v in run.counters.items()}), file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
