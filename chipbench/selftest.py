"""``python3 -m chipbench.selftest``: the harness checked on the CPU.

Proves paths, arithmetic and files, never the chip: it prints counts and
no result line. Tiny widths live here, not behind a flag of ``run.py``:
every cell of ``BENCHMARK.json`` runs its own builder kind, driver kind
and traffic file with the sizes below swapped in.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import glob  # noqa: E402
import time  # noqa: E402

from . import (manifest, run, serving_loop, step_budget,  # noqa: E402
               trace, traffic_gen, xplane)

FIXTURE = os.path.join(manifest.HERE, "fixtures", "mini_step.xplane.pb")

# the shapes bench.build_flagship() builds on the CPU backend
TINY_CONFIG = {
    "gpt_trainer": {
        "kind": "gpt_trainer", "recipe": "bench.build_flagship",
        "model": {"vocab_size": 1024, "hidden_size": 128,
                  "num_layers": 2, "num_heads": 4, "head_dim": 32,
                  "ffn_mult": 4, "max_seq_len": 128},
        "batch": 4, "chips": 1, "mesh": {}},
    "llama_engine": {
        "kind": "llama_engine", "dtype": "float32",
        "model": {"vocab_size": 512, "hidden_size": 64,
                  "intermediate_size": 128, "num_hidden_layers": 2,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "head_dim": 16, "max_position_embeddings": 512,
                  "rms_norm_eps": 1e-5, "rope_theta": 1e6,
                  "tie_word_embeddings": False},
        "chips": 1, "mesh": {},
        "engine": {"max_slots": 4, "max_len": 128,
                   "kv_layout": "paged"}},
}
TINY_TRAFFIC = {
    "train_stream": {"trace_seconds": 0.3},
    "closed_loop": {"prompt_tokens": {"log_uniform": [8, 64]},
                    "output_tokens": {"log_uniform": [4, 24]},
                    "clients": 8, "block": 8, "ramp_seconds": 0.3,
                    "check_prompt_tokens": [9, 40],
                    "check_new_tokens": 8, "trace_seconds": 0.3},
    "open_loop": {"prompt_tokens": {"log_uniform": [8, 64]},
                  "output_tokens": {"log_uniform": [4, 24]},
                  "block": 8, "ramp_seconds": 0.3, "rate_rps": 20.0,
                  "initial_inflight": 2, "drain_seconds": 2,
                  "check_prompt_tokens": [9, 40],
                  "check_new_tokens": 8, "trace_seconds": 0.3},
}
PEAKS = {"bf16_flops": 197e12}


class Count:
    def __init__(self):
        self.n = 0

    def ok(self, cond, what):
        if not cond:
            raise SystemExit(f"chipbench.selftest: FAILED: {what}")
        self.n += 1


def near(a, b, tol=1e-9):
    return abs(a - b) <= tol * max(1.0, abs(b))


def check_manifest(c: Count) -> None:
    bench = manifest.load()
    faults = manifest.check(bench)
    c.ok(not faults, f"manifest: {faults}")
    files = glob.glob(os.path.join(manifest.HERE, "layer_metrics",
                                   "*.json"))
    listed = {m["name"] for m in bench["per_layer"]}
    for f in files:
        spec = manifest.load_json(f)
        c.ok(spec["name"] + ".json" == os.path.basename(f),
             f"{f} holds another metric's name")
        manifest.module("readers", spec["reader"])
        if spec["name"] not in listed:
            print(f"  note: {spec['name']} has a file and no entry "
                  f"(its cell is not proved yet)")


def check_trace_readers(c: Count) -> dict:
    abs_lines = dict(next(iter(xplane.planes_abs(FIXTURE)))[1])
    budget = step_budget.budget_from_times(
        xplane.self_times(abs_lines["XLA Ops"]), steps=2)
    for k, want in step_budget.FIXTURE_EXPECT.items():
        c.ok(near(budget["buckets"][k], want), f"bucket {k}")
    tr = trace.reduce(FIXTURE, window_s=0.025, steps=2)
    c.ok(near(tr["busy_s"], 0.022), f"busy union {tr['busy_s']}")
    c.ok(tr["devices"] == 1 and tr["steps"] == 2, "trace shape")
    c.ok(near(tr["ops_s"]["while.1"], 0.001), "envelope self time")
    c.ok(len(trace.breakdown(tr)["device_ops"]) == 10, "breakdown")
    c.ok(abs_lines["XLA Ops"][4][1:] == (10_000_000_000,
                                         15_000_000_000), "planes_abs")
    c.ok(trace.reduce(FIXTURE, 1.0, 1, plane_filter="GPU") is None,
         "no device plane gives nothing")
    return tr


def check_readers(c: Count, tr: dict) -> None:
    """Every reader against the recorded fixture and a made-up set of
    observations whose answers are known; and on nothing, nothing."""
    cfg = {"model": {"max_seq_len": 1024, "head_dim": 128,
                     "num_heads": 16, "num_layers": 24}, "batch": 6}
    obs = {"trace": tr, "peaks": PEAKS, "config": cfg,
           "host": {"step_ms": 320.0, "n_params": 1e9,
                    "tokens_per_s_per_chip": 19700.0},
           "counters": {"compiles_in_window": 0},
           "samples": {"lat": [0.001 * i for i in range(101)]},
           "registry": {"fam": {"sum": 3.0, "count": 60}}}
    empty = {"trace": None, "peaks": PEAKS, "config": cfg, "host": {},
             "counters": {}, "samples": {}, "registry": {}}
    flash_ms = step_budget.FIXTURE_EXPECT["flash"]
    cases = {
        "trace_bucket": ({"bucket": "quantize"}, 1.25),
        "trace_idle": ({}, 100 * (1 - 0.022 / 0.025)),
        "trace_busy_per_step": ({}, 11.0),
        "flops_over_bucket": (
            {"flops": "flash_attention_train", "bucket": "flash",
             "peak": "bf16_flops"},
            100 * (7 * 1024 * 1024 * 128 * 16 * 24 * 6 / 197e12)
            / (flash_ms / 1e3)),
        "registry_mean": ({"family": "fam", "scale": 1000.0}, 50.0),
        "sample_quantile": ({"samples": "lat", "q": 0.9,
                             "scale": 1000.0}, 90.0),
        "host_clock": ({"key": "step_ms"}, 320.0),
        "counter": ({"key": "compiles_in_window"}, 0),
        "model_flops_utilization": ({"peak": "bf16_flops"}, 60.0),
    }
    have = {os.path.basename(f)[:-3] for f in glob.glob(
        os.path.join(manifest.HERE, "readers", "*.py"))} - {"__init__"}
    c.ok(have == set(cases), f"readers without a case: "
         f"{have ^ set(cases)}")
    for name, (args, want) in cases.items():
        reader = manifest.module("readers", name)
        got = reader.read(args, obs)
        c.ok(got is not None and near(got, want, 1e-6),
             f"reader {name}: {got} != {want}")
        c.ok(reader.read(args, empty) is None,
             f"reader {name} on nothing")


def check_traffic(c: Count) -> None:
    t = manifest.load_json(os.path.join(manifest.HERE, "traffic",
                                        "chat-open.json"))
    a, b = (traffic_gen.RequestMix(t, 1000, s) for s in (1, 2**31 + 5))
    def block(m):
        g = m.requests()
        return [(len(p), n) for p, n in (next(g) for _ in range(m.block))]
    c.ok(sorted(block(a)) == sorted(block(b)) and block(a) != block(b),
         "every seed: the same sizes in another order")
    def gaps(m):
        g, out, last = m.arrivals(0.0), [], 0.0
        for _ in range(m.block):
            x = next(g)
            out.append(round(x - last, 9))
            last = x
        return out, last
    (ga, ea), (gb, eb) = gaps(a), gaps(b)
    c.ok(sorted(ga) == sorted(gb) and ga != gb
         and near(ea, a.block / t["rate_rps"], 1e-6),
         "every seed: the same gaps in another order, a block spans "
         "block / rate")
    s = traffic_gen.BatchStream(64, 2, 16, {
        "zipf_exponent": 1.1, "vocab_permutation_seed": 1}, 7)
    ids, labels = s.next()
    s.close()
    c.ok(ids.shape == (2, 16) and (ids[:, 1:] == labels[:, :-1]).all(),
         "labels are the ids shifted")


class FakeFront:
    """A server on a fake clock: a pump takes ``step`` seconds and gives
    every request in flight one token; request number ``refuse`` is
    refused at the door."""

    def __init__(self, now, step, refuse):
        self.now, self.step, self.refuse = now, step, refuse
        self.live, self.seen = [], 0

    def submit(self, prompt, want, stream=None):
        from paddle_tpu.serving import QueueFull
        self.seen += 1
        if self.seen - 1 == self.refuse:
            raise QueueFull(1, 1)
        self.live.append([stream, want, []])

    def has_work(self):
        return bool(self.live)

    def pump(self):
        self.now[0] += self.step
        for item in list(self.live):
            stream, want, out = item
            out.append(1)
            stream.write({"event": "token"})
            if len(out) == want:
                stream.write({"event": "done", "finish_reason": "length",
                              "output_ids": out})
                self.live.remove(item)


def check_open_loop(c: Count) -> None:
    """Due times, lateness and a refused request, on a fake clock."""
    now = [0.0]
    clock = lambda: now[0]

    def sleep(dt):
        now[0] += max(dt, 1e-6)

    class Sys:
        front = FakeFront(now, step=0.1, refuse=2)

    class Mix:
        def requests(self):
            import numpy as np
            while True:
                yield np.ones(4, np.int64), 3
    # due at 0.05, 0.1, 0.15 (refused), 1.0
    arrivals = iter([0.05, 0.1, 0.15, 1.0, 99.0])
    loop = serving_loop.Loop(Sys, Mix(), trace.NoTracer(), clock, sleep)
    src = serving_loop.OpenSource(arrivals)
    loop.run(src, until=2.0)
    r = loop.recs
    c.ok([x.due for x in r] == [0.05, 0.1, 0.15, 1.0], "due times")
    # the first is sent on time; the pump then holds the thread until
    # 0.15, so the second (due 0.1) is 0.05 late and the third on time
    c.ok(near(r[0].sent, 0.05) and near(r[1].sent - r[1].due, 0.05)
         and near(r[3].sent, 1.0), "lateness")
    c.ok(r[2].ok is False and not r[2].times, "refusal recorded")
    out = serving_loop.reduce_window(r, 0.0, 2.0, None, {"ok": True},
                                     None, None, 0, 0, 0)
    # first tokens: 0.15 - 0.05, 0.25 - 0.1, refused = the window's
    # length, 1.1 - 1.0
    import numpy as np
    want = float(np.percentile([0.1, 0.15, 2.0, 0.1], 90)) * 1e3
    c.ok(near(out["end_to_end"]["ttft_p90_ms"], want, 1e-6),
         "a refused request counts as the window's length")
    c.ok(out["failed"] == 1 and out["attempted"] == 4
         and not out["checks"]["every_ended_request_complete"],
         "a refused request is a failure")
    c.ok(near(out["end_to_end"]["itl_p95_ms"], 100.0, 1e-6), "gaps")
    late = sorted(out["obs"]["samples"]["gen_late_s"])
    c.ok(near(late[-1], 0.05) and near(late[0], 0.0), "gen_late")


def check_explicit_recipe(c: Count) -> None:
    """The trainer built from a file's own arguments on the file's mesh
    (what a four-chip cell uses), and the plain reference against it: in
    float32 without kernels the two agree to rounding."""
    import jax
    from .builders import gpt_trainer
    config = dict(TINY_CONFIG["gpt_trainer"], recipe="explicit",
                  dtype="float32",
                  trainer={"microbatches": 1, "remat": False})
    system = gpt_trainer.build(config, 2**31 + 7)
    stream = traffic_gen.BatchStream(system.vocab, system.batch,
                                     system.seq, {
        "zipf_exponent": 1.1, "vocab_permutation_seed": 1}, 3)
    ids, labels = stream.next()
    stream.close()
    ref = system.reference_loss(ids, labels)
    got = float(jax.device_get(system.step(ids, labels)))
    c.ok(near(got, ref, 1e-5), f"explicit trainer {got} vs reference "
         f"{ref}")


UNPROVED = os.path.join(manifest.HERE, "unproved", "manifest.json")


def check_cells(c: Count) -> None:
    """Every cell's control flow, both result kinds, at a tiny size:
    the cells of ``BENCHMARK.json`` and, while it exists, of
    ``unproved/manifest.json`` (cells whose files are kept and whose
    proof on the chip is owed: PERF.md, Open questions)."""
    bench = manifest.load()
    if os.path.exists(UNPROVED):
        later = manifest.load(UNPROVED)
        faults = manifest.check(later)
        c.ok(not faults, f"unproved manifest: {faults}")
        bench = later       # it lists the proved cells too
    for w in bench["workloads"]:
        cell = manifest.cell(bench, w["name"])
        cell["config"] = TINY_CONFIG[cell["config"]["kind"]]
        cell["traffic"] = dict(cell["traffic"],
                               **TINY_TRAFFIC[cell["traffic"]["kind"]])
        for traced in (False, True):
            t = time.perf_counter()
            with open(os.devnull, "w") as quiet:
                r = run.run_cell(cell, 2**31 + 7, 1.0, traced, PEAKS,
                                 plane_filter="CPU",
                                 line_filter="CpuClient", log=quiet)
            want = cell["per_layer"] if traced else cell["end_to_end"]
            missing = [m["name"] for m in want
                       if m["name"] not in r["metrics"]]
            # the CPU trace has no kernels: their buckets read nothing
            missing = [m for m in missing if not (
                traced and m.startswith("flash_roofline"))]
            c.ok(r["correct"] and r["failed"] == 0 and r["attempted"] > 0
                 and not missing,
                 f"{w['name']} trace={int(traced)}: correct="
                 f"{r['correct']} failed={r['failed']} missing={missing}")
            c.ok(all(v["value"] == v["value"] and v["value"] >= 0
                     for v in r["metrics"].values()),
                 f"{w['name']}: a metric is negative or not a number")
            print(f"  {w['name']} trace={int(traced)}: "
                  f"{len(r['metrics'])} metrics, "
                  f"{time.perf_counter() - t:.1f} s")


def main() -> None:
    t0 = time.perf_counter()
    c = Count()
    for fn in (check_manifest, check_traffic, check_open_loop,
               check_explicit_recipe):
        fn(c)
    tr = check_trace_readers(c)
    check_readers(c, tr)
    n_fast = c.n
    check_cells(c)
    print(f"chipbench.selftest: {c.n} checks passed ({n_fast} without "
          f"a model, {c.n - n_fast} over tiny cells) in "
          f"{time.perf_counter() - t0:.0f} s on the CPU; no chip, no "
          f"result line")


if __name__ == "__main__":
    main()
