"""Run every BASELINE config benchmark; one JSON line each
(BASELINE.md: 'performance baselines must be produced by our own
measurement harness'). Each script is standalone: a failure doesn't
stop the rest, and the run then exits non-zero.

``--prom-out DIR`` additionally makes each instrumented script write
its observability registry as Prometheus text exposition to
``DIR/<script>.prom`` (via the PTPU_PROM_OUT env var) — the metrics
snapshot that belongs next to the BENCH json."""
import _path  # noqa: F401  (repo-root import shim)

import argparse
import json
import os
import subprocess
import sys

# entries may carry script args (split on whitespace)
SCRIPTS = ["bench_resnet50.py", "bench_bert_dp.py", "bench_gpt_hybrid.py",
           "bench_ernie_zero3.py", "bench_ppyoloe_infer.py",
           "bench_llama_decode.py", "bench_serving_engine.py",
           # paged-KV concurrency under a shared byte budget
           "bench_serving_engine.py --prefix-share",
           # self-speculative decoding on the repetitive-suffix trace
           "bench_serving_engine.py --speculative",
           # draft-model speculation + sampled acceptance + tuner on
           # the low-self-similarity trace (ISSUE-19 acceptance)
           "bench_serving_engine.py --spec-v2",
           # KV tiering: host-RAM page tier + persistent prefix store
           # under device-page pressure (tier-labelled hit rates,
           # restart warm-start)
           "bench_serving_engine.py --kv-tiering",
           # watchtower incident detection: zero incidents on the
           # clean replay, a correctly-attributed stall incident on
           # the injected-outage replay
           "bench_serving_engine.py --watchtower",
           # chunked prefill: bounded decode stalls under mixed
           # long-prompt / short-decode traffic (token identity +
           # the tail-latency SLO artifact)
           "bench_serving_engine.py --chunked-prefill",
           # front-door closed-loop SLO (replica killed mid-run,
           # exactly-once ledger at the boundary)
           "bench_serving_engine.py --frontdoor",
           # control plane: priority brownout on an overload burst —
           # shed vs unshed per-tier p99 TTFT, zero LOST either way
           "bench_serving_engine.py --control-plane",
           # tensor-parallel + disaggregated serving on the emulated
           # mesh (token identity + compile-once per mesh shape)
           "bench_serving_engine.py --tensor-parallel",
           # cross-process cluster SLO (worker process SIGKILLED
           # mid-run, supervisor respawn, exactly-once ledger;
           # self-skips without the native TCPStore extension)
           "bench_serving_engine.py --cluster",
           # cross-host serving fabric: authenticated RPC + shared
           # weight store + wire KV handoff through a SIGKILL and a
           # partition (self-skips without the TCPStore extension)
           "bench_serving_engine.py --multihost",
           # budget via PTPU_CHAOS_EPISODES / PTPU_CHAOS_SECONDS
           "chaos_soak.py"]


def lint_preflight(repo: str) -> bool:
    """Run ptpu-lint over the package before any benchmark burns
    minutes of compute: a fresh invariant violation (leaked page
    acquisition, unguarded shared state, orphan fault point) is
    exactly the kind of bug a long soak then rediscovers the hard
    way. Emits the finding counts as a JSON benchmark line plus the
    Prometheus-style ``ptpu_lint_findings_total`` gauges."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.ptpu_lint", "paddle_tpu",
         "--json", "--metrics"],
        capture_output=True, text=True, timeout=600, cwd=repo)
    body = r.stdout.split("ptpu_lint_findings_total")[0]
    try:
        payload = json.loads(body)
        n_new = len(payload["findings"])
        n_base = payload["baselined"]
    except (ValueError, KeyError):
        n_new, n_base = -1, -1
    print(json.dumps({"metric": "ptpu_lint_new_findings",
                      "value": n_new, "unit": "findings",
                      "vs_baseline": None}))
    print(f'ptpu_lint_findings_total{{status="new"}} {n_new}')
    print(f'ptpu_lint_findings_total{{status="baselined"}} {n_base}')
    if r.returncode != 0:
        sys.stderr.write("ptpu_lint pre-flight failed "
                         f"(rc={r.returncode}):\n" + body[-2000:]
                         + r.stderr[-1000:] + "\n")
    return r.returncode == 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--prom-out", default=None, metavar="DIR",
                    help="write each script's Prometheus metrics "
                         "snapshot to DIR/<script>.prom")
    ap.add_argument("--skip-lint", action="store_true",
                    help="skip the ptpu-lint pre-flight")
    opts = ap.parse_args()
    if opts.prom_out:
        os.makedirs(opts.prom_out, exist_ok=True)
    here = os.path.dirname(os.path.abspath(__file__))
    if not opts.skip_lint:
        lint_preflight(os.path.dirname(here))
    failed = []
    for s in SCRIPTS:
        # Each script resolves the repo root via benchmarks/_path.py.
        # On CPU the multi-chip configs need the virtual 8-device mesh.
        env = dict(os.environ)
        if env.get("JAX_PLATFORMS") == "cpu":
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f]
            flags.append("--xla_force_host_platform_device_count=8")
            env["XLA_FLAGS"] = " ".join(flags)
        argv = s.split()
        if opts.prom_out:
            env["PTPU_PROM_OUT"] = os.path.join(
                opts.prom_out,
                s.replace(".py", "").replace(" --", "_").replace("-", "_")
                + ".prom")
        r = subprocess.run(
            [sys.executable, os.path.join(here, argv[0])] + argv[1:],
            capture_output=True, text=True, timeout=1800, env=env)
        for line in r.stdout.splitlines():
            if line.startswith("{"):
                print(line)
        if r.returncode != 0:
            failed.append(s)
            print(f'{{"metric": "{s} FAILED", "value": null, '
                  f'"unit": "", "vs_baseline": null}}')
            sys.stderr.write(r.stderr[-2000:] + "\n")
    if failed:
        sys.exit(f"run_all: {len(failed)} script(s) failed: {failed}")


if __name__ == "__main__":
    main()
