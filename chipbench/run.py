"""Run one cell once: ``python3 -m chipbench.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

One process: loads the cell's configuration and traffic files, builds
the system, warms only that cell's shapes, checks correctness, measures
for ``--seconds`` and prints one JSON object as the last line of its
standard output. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the tail of the window and reports its per-layer
metrics. There is no CPU result line: without a TPU of
``chipbench/peaks.json``, or with fewer chips than the cell asks for,
the exit code is non-zero and nothing is printed.
"""
from .setup_marks import MARKS, T0 as T_START, mark  # first: the clock

import argparse
import json
import os
import shutil
import sys

from . import manifest, trace as tracing, xplane

TRACE_DIR = os.path.join(manifest.ROOT, ".chipbench_trace")


def device_record() -> dict:
    import jax
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             peaks: dict, *, plane_filter: str = "TPU",
             line_filter=None, log=sys.stderr) -> dict:
    """Build, drive and reduce one cell; returns the result object
    without its ``device`` key. Used by ``main`` on the chip and by the
    selftest (tiny configurations, CPU plane) alike."""
    builder = manifest.module("builders", cell["config"]["kind"])
    driver = manifest.module("drivers", cell["traffic"]["kind"])
    logdir = os.path.join(TRACE_DIR, cell["workload"]["name"])
    tracer = tracing.Tracer(logdir, cell["traffic"]["trace_seconds"]) \
        if trace else tracing.NoTracer()
    del MARKS[:]
    mark("imports")
    system = builder.build(cell["config"], seed)
    out = driver.run(system, cell["traffic"], seed, seconds, tracer)
    setup_s = out["t_window"] - T_START
    obs = dict(out["obs"], config=cell["config"],
               traffic=cell["traffic"], peaks=peaks, trace=None)
    print(f"chipbench: setup_s {setup_s:.3f}; marks "
          f"{json.dumps(MARKS)}; notes {json.dumps(out['notes'])}",
          file=log)
    for name, ok in out["checks"].items():
        if not ok:
            print(f"chipbench: CHECK FAILED: {name}", file=log)
    result = {"correct": all(out["checks"].values()),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if not trace:
        values = dict(out["end_to_end"], setup_s=setup_s)
        missing = [m["name"] for m in cell["end_to_end"]
                   if m["name"] not in values]
        if missing:
            raise SystemExit(f"chipbench: the driver reported no "
                             f"{missing}")
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]}
        return result
    try:
        obs["trace"] = tracing.reduce(
            xplane.latest_xplane(logdir), tracer.window_s, tracer.steps,
            plane_filter=plane_filter, line_filter=line_filter,
            chips=cell["workload"]["chips"])
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    if obs["trace"] is None:
        raise SystemExit("chipbench: the trace holds no device plane: "
                         "no operation ran on the device")
    metrics = {}
    for spec in cell["per_layer"]:
        value = manifest.module("readers", spec["reader"]).read(
            spec.get("args", {}), obs)
        if value is not None:
            metrics[spec["name"]] = {"value": value,
                                     "unit": spec["unit"]}
    result["metrics"] = metrics
    result["busy_s"] = obs["trace"]["busy_s"]
    result["window_s"] = obs["trace"]["window_s"]
    result["breakdown"] = tracing.breakdown(obs["trace"])
    # beside the contract's keys (the driver ignores it): device self
    # time a step by kernel family, the STEP_BUDGET of PR 23
    result["step_budget_ms"] = obs["trace"]["buckets_ms_per_step"]
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = manifest.cell(manifest.load(), args.workload)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devs = jax.devices()
    peaks = manifest.load_json(
        os.path.join(manifest.HERE, "peaks.json"))["devices"]
    if devs[0].platform != "tpu" or devs[0].device_kind not in peaks:
        raise SystemExit(
            f"chipbench: needs a TPU of chipbench/peaks.json "
            f"({sorted(peaks)}); jax sees {devs[0].platform!r} "
            f"{devs[0].device_kind!r}")
    if len(devs) < cell["workload"]["chips"]:
        raise SystemExit(
            f"chipbench: {args.workload} needs "
            f"{cell['workload']['chips']} chip(s), jax sees {len(devs)}")
    print(f"chipbench: {args.workload} seed {args.seed} on "
          f"{len(devs)} x {devs[0].device_kind}; compile cache "
          f"{cache_dir}", file=sys.stderr)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks[devs[0].device_kind])
    device = device_record()
    for key in ("busy_s", "window_s"):
        if key in result:
            device[key] = result.pop(key)
    result["device"] = device
    compiles = result["metrics"].get("compiles_in_window")
    if compiles and compiles["value"] > 0:
        print(f"chipbench: WARNING: {compiles['value']} program(s) "
              f"compiled inside the measured window")
    sys.stdout.flush()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
