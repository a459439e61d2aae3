"""Chaos soak: seeded fault-schedule episodes until the budget runs
out, every episode audited against the end-to-end conservation
invariants (resilience/chaos.py, docs/RESILIENCE.md).

Episodes rotate across the serving engine (Poisson arrivals,
deadlines, cancels, decode/prefill faults, recover(), drain-under-
fire), the resilient training loop (step crashes, torn checkpoint
writes, flaky stores/watchdog beats, process relaunches), the
front-door/replica-kill stack, and the CROSS-PROCESS cluster (worker
subprocesses behind RPC replicas; cooperative kills, real SIGKILLs,
socket partitions, supervisor respawns — skipped back to serving when
the native TCPStore extension is unavailable). Each seed fully
determines its episode: a red seed printed here reproduces with

    python -c "from paddle_tpu.resilience import chaos; \\
               print(chaos.run_serving_episode(SEED).violations)"

Budget (env, so the run_all roster stays declarative; flags override):
  PTPU_CHAOS_EPISODES   max episodes           (default 20)
  PTPU_CHAOS_SECONDS    wall budget, 0 = none  (default 0)
  PTPU_CHAOS_SEED0      base seed              (default 0)

Output: one run_all-schema JSON metric line, then ``CHAOS_SOAK {json}``
with the full tally (episodes, red seeds + violations, faults fired
per point, recoveries/relaunches). Exits non-zero on any red episode.
"""
import _path  # noqa: F401  (repo-root import shim)

import argparse
import json
import os
import shutil
import sys
import tempfile
import time


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int,
                    default=int(os.environ.get("PTPU_CHAOS_EPISODES",
                                               20)))
    ap.add_argument("--seconds", type=float,
                    default=float(os.environ.get("PTPU_CHAOS_SECONDS",
                                                 0)))
    ap.add_argument("--seed-base", type=int,
                    default=int(os.environ.get("PTPU_CHAOS_SEED0", 0)))
    opts = ap.parse_args()

    from paddle_tpu.resilience import chaos
    try:
        from paddle_tpu.distributed.store import get_lib
        have_cluster = get_lib() is not None
    except Exception:
        have_cluster = False
    workdir = tempfile.mkdtemp(prefix="ptpu_chaos_")
    t0 = time.time()
    results = []
    fired = {}
    seed = opts.seed_base
    try:
        while len(results) < opts.episodes:
            if opts.seconds and time.time() - t0 > opts.seconds:
                break
            kind = ("serving", "training", "frontdoor",
                    "cluster")[seed % 4]
            if kind == "cluster" and not have_cluster:
                kind = "serving"   # no native store -> no workers
            r = chaos.run_episode(seed, kind, workdir=workdir)
            results.append(r)
            for p, n in r.fired.items():
                fired[p] = fired.get(p, 0) + n
            if not r.ok:
                print(f"RED seed={r.seed} kind={r.kind}",
                      file=sys.stderr)
                for v in r.violations:
                    print("  - " + v, file=sys.stderr)
            seed += 1
    finally:
        # one checkpoint tree per training episode lives under the
        # workdir — a long soak must not leak it into /tmp
        shutil.rmtree(workdir, ignore_errors=True)
        chaos._shutdown_cluster()   # reap the warm worker pool

    wall = time.time() - t0
    red = [r for r in results if not r.ok]
    n_serving = sum(1 for r in results if r.kind == "serving")
    n_front = sum(1 for r in results if r.kind == "frontdoor")
    n_cluster = sum(1 for r in results if r.kind == "cluster")
    summary = {
        "episodes": len(results),
        "green": len(results) - len(red),
        "serving_episodes": n_serving,
        "frontdoor_episodes": n_front,
        "cluster_episodes": n_cluster,
        "training_episodes":
            len(results) - n_serving - n_front - n_cluster,
        "seed_range": [opts.seed_base, seed - 1],
        "red_seeds": [{"seed": r.seed, "kind": r.kind,
                       "violations": r.violations} for r in red],
        "recoveries": sum(int(r.stats.get("recoveries", 0))
                          for r in results),
        "relaunches": sum(int(r.stats.get("relaunches", 0))
                          for r in results),
        "respawns": sum(int(r.stats.get("respawns", 0))
                        for r in results),
        "faults_fired": fired,
        "wall_s": round(wall, 2),
    }
    print(json.dumps({
        "metric": (
            f"chaos soak: {summary['green']}/{summary['episodes']} "
            f"episodes green (seeds {opts.seed_base}..{seed - 1}, "
            f"{n_serving} serving + {n_front} front-door/replica-kill"
            f" + {n_cluster} cluster + "
            f"{summary['training_episodes']} training, "
            f"{sum(fired.values())} faults fired over "
            f"{len(fired)} points, {summary['recoveries']} "
            f"recoveries, {summary['relaunches']} relaunches; every "
            f"episode audited for request conservation, token "
            f"identity, loss continuity, checkpoint monotonicity, "
            f"leaks; baseline=episode count)"),
        "value": float(summary["green"]),
        "unit": "episodes",
        "vs_baseline": float(summary["episodes"])}))
    print("CHAOS_SOAK " + json.dumps(summary))
    if red:
        raise SystemExit(
            f"{len(red)} red episode(s); reproduce via the seeds in "
            f"the CHAOS_SOAK line")


if __name__ == "__main__":
    main()
