"""Run one cell once: ``python3 -m chipbench.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

One process: loads the cell's configuration and traffic files, builds
the system, warms only that cell's shapes, measures for ``--seconds``,
holds what the timed path produced against the plain reference and
prints one JSON object as the last line of its standard output.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the tail of the window and reports its per-layer metrics. There
is no CPU result line: without a TPU of ``chipbench/peaks.json``, or
with fewer chips than the cell asks for, the exit code is non-zero and
nothing is printed.
"""
from .setup_marks import MARKS, T0 as T_START, mark  # first: the clock

import argparse
import json
import os
import shutil
import sys
import time

from . import manifest, trace as tracing, xplane

TRACE_DIR = os.path.join(manifest.ROOT, ".chipbench_trace")
CACHE_DIR = os.path.join(manifest.ROOT, ".jax_cache")


def memory_peak_bytes() -> int:
    """The peak so far on the fullest device (0 where the backend keeps
    no statistics, as the CPU's)."""
    import jax
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()))


def within(c: dict) -> bool:
    """One compared number against its limit (a NaN is outside)."""
    return bool(c["value"] <= c["limit"])


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             peaks: dict, *, plane_filter: str = "TPU",
             line_filter=None, log=sys.stderr,
             control: bool = False) -> dict:
    """Build, drive and reduce one cell; returns the result object
    without its ``device`` key. Used by ``main`` on the chip and by the
    selftest (tiny configurations, CPU plane) alike. ``control`` is
    ``chipbench.control``'s: the reading that has to fail, beside the
    run's own."""
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
    marks = [[name, round(t, 3)] for name, t in MARKS]
    print(f"chipbench: setup_s {setup_s:.3f}; marks {json.dumps(marks)}; "
          f"notes {json.dumps(out['notes'])}", file=log)
    # the peak is read before the reference runs: a process's peak never
    # falls again, and the reference is no part of the system
    peak = memory_peak_bytes()
    t = time.perf_counter()
    compared = list(out["compared"])
    if "verify" in out:
        compared += out["verify"](control=control)
    print(f"chipbench: verified in {time.perf_counter() - t:.1f} s",
          file=log)
    result = {"correct": all(within(c) for c in compared),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]),
              "memory_peak_bytes": peak, "compared": compared}
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
    # beside the contract's keys (the driver ignores them): device self
    # time a step by kernel family, the STEP_BUDGET of PR 23, and the
    # device's whole programs, [seconds, calls] in the traced window
    result["step_budget_ms"] = obs["trace"]["buckets_ms_per_step"]
    result["device_modules"] = obs["trace"]["modules"]
    return result


def main(argv=None, control: bool = False) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = manifest.cell(manifest.load(), args.workload)

    # the compile cache lives inside the checkout, at a fixed path, so
    # that two checkouts share nothing and only a checkout's first run
    # of a cell compiles; the program takes the directory it is given. A
    # size cap from the environment is not taken over: one serving cell's
    # programs are 280 MB, and under the chip tool's 192 MiB cap (LRU)
    # every run compiled all of them again
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
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
                      peaks[devs[0].device_kind], control=control)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": result.pop("memory_peak_bytes")}
    for key in ("busy_s", "window_s"):
        if key in result:
            device[key] = result.pop(key)
    result["device"] = device
    compiles = result["metrics"].get("compiles_in_window")
    if compiles and compiles["value"] > 0:
        print(f"chipbench: WARNING: {compiles['value']} program(s) "
              f"compiled inside the measured window", file=sys.stderr)
    # every number compared beside its limit: the last lines of standard
    # error, and the last key of the result line
    compared = result.pop("compared")
    for c in compared:
        print(f"chipbench: compared {c['name']} = {c['value']!r} "
              f"limit {c['limit']!r} "
              f"{'ok' if within(c) else 'OUTSIDE'}"
              + (f" ({c['over']})" if "over" in c else ""),
              file=sys.stderr)
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"]}
                          for c in compared}
    sys.stderr.flush()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
