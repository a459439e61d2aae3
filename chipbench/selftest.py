"""``python3 -m chipbench.selftest``: the harness checked on the CPU.

Proves paths, arithmetic and files, never the chip: it prints counts and
no result line. Tiny widths are no flag of ``run.py``: every cell of
``BENCHMARK.json`` runs its own builder kind, driver kind and traffic
file with the ``TINY`` sizes of that builder's and that driver's module
swapped in. Every reader under ``readers/`` and every count under
``counts/`` brings its own ``SELFTEST_CASE``, so a later PR adds a kind,
a reader or a count as a file and edits nothing here.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import glob  # noqa: E402
import time  # noqa: E402

from . import (manifest, reference, run, serving_loop,  # noqa: E402
               step_budget, trace, traffic_gen, xplane)

FIXTURE = os.path.join(manifest.HERE, "fixtures", "mini_step.xplane.pb")


class _Tiny(dict):
    """``TINY`` of ``builders/<kind>.py`` or ``drivers/<kind>.py``, by
    kind (a mapping, as ``tests/test_program_spans.py`` reads it)."""

    def __init__(self, group: str):
        super().__init__()
        self.group = group

    def __missing__(self, kind: str) -> dict:
        return manifest.module(self.group, kind).TINY


TINY_CONFIG = _Tiny("builders")
TINY_TRAFFIC = _Tiny("drivers")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 7


def tiny_cell(bench: dict, name: str) -> dict:
    cell = manifest.cell(bench, name)
    cell["config"] = TINY_CONFIG[cell["config"]["kind"]]
    cell["traffic"] = dict(cell["traffic"],
                           **TINY_TRAFFIC[cell["traffic"]["kind"]])
    return cell


def run_tiny(cell: dict, traced: bool, seed: int = SEED) -> dict:
    with open(os.devnull, "w") as quiet:
        return run.run_cell(cell, seed, 1.0, traced, PEAKS,
                            plane_filter="CPU", line_filter="CpuClient",
                            log=quiet)


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
                  f"(PERF.md, Open questions)")


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


def modules_of(group: str) -> list:
    return sorted(os.path.basename(f)[:-3] for f in glob.glob(
        os.path.join(manifest.HERE, group, "*.py"))
        if not f.endswith("__init__.py"))


def check_readers(c: Count, tr: dict) -> None:
    """Every reader and every count against its own ``SELFTEST_CASE``
    (arguments, observations, answer), the observations laid over the
    recorded fixture's reduction; and on nothing, nothing."""
    common = {"trace": tr, "peaks": PEAKS, "config": {}, "host": {},
              "counters": {}, "samples": {}, "registry": {}}
    empty = dict(common, trace=None)
    for name in modules_of("readers"):
        reader = manifest.module("readers", name)
        c.ok(hasattr(reader, "SELFTEST_CASE"),
             f"readers/{name}.py brings no SELFTEST_CASE")
        args, extra, want = reader.SELFTEST_CASE
        got = reader.read(args, dict(common, **extra))
        c.ok(got == want if want is None
             else got is not None and near(got, want, 1e-6),
             f"reader {name}: {got} != {want}")
        c.ok(reader.read(args, empty) is None,
             f"reader {name} on nothing")
    for name in modules_of("counts"):
        counter = manifest.module("counts", name)
        config, obs, want = counter.SELFTEST_CASE
        got = counter.count(config, obs)
        c.ok(near(got, want, 1e-9), f"count {name}: {got} != {want}")


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
    out = serving_loop.reduce_window(r, 0.0, 2.0, None, None, None,
                                     0, 0, 0)
    # first tokens: 0.15 - 0.05, 0.25 - 0.1, refused = the window's
    # length, 1.1 - 1.0
    import numpy as np
    ttft = [0.1, 0.15, 2.0, 0.1]
    c.ok(near(out["obs"]["host"]["ttft_p90_ms"],
              float(np.percentile(ttft, 90)) * 1e3, 1e-6)
         and near(out["end_to_end"]["ttft_p75_ms"],
                  float(np.percentile(ttft, 75)) * 1e3, 1e-6),
         "a refused request counts as the window's length")
    c.ok(out["failed"] == 1 and out["attempted"] == 4
         and not all(run.within(x) for x in out["compared"]),
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


def check_warm_up(c: Count) -> None:
    """The warm-up reaches every program a window can: for a mix whose
    prompts start with the tokenizer's first id (every request a
    one-token prefix hit) the page copy and the extend program of every
    tail bucket and one prefill program; for a mix of uniform ids the
    prefill programs too. A window of either then compiles nothing."""
    import numpy as np
    from .builders import llama_engine
    traffic = dict(manifest.load_json(os.path.join(
        manifest.HERE, "traffic", "chat-saturated.json")),
        **TINY_TRAFFIC["closed_loop"])
    for bos in (traffic["bos_token_id"], None):
        system = llama_engine.build(llama_engine.TINY, SEED)
        eng = system.engine
        mix = traffic_gen.RequestMix(dict(traffic, bos_token_id=bos),
                                     system.vocab, SEED)
        reach = serving_loop.warm(system, mix, SEED)
        counts = eng.trace_counts
        c.ok(sorted(counts["extend"]) == reach["extend"]
             and sorted(counts["prefill"]) == reach["prefill"]
             and counts["copy"] == 1 and len(reach["extend"]) == 3
             and len(reach["prefill"]) == (1 if bos else 3),
             f"warm-up over {reach}: {counts}")
        before, hits = system.programs(), eng.cache.prefix_hit_tokens
        requests = mix.requests()
        sent = [next(requests)[0] for _ in range(2 * mix.block)]
        if bos is None:          # two prompts that share a first token
            sent[1][0] = sent[0][0] = sent[-1][0]
        for prompt in sent:
            system.front.submit(prompt, 3)
        system.front.run_until_idle()
        c.ok(system.programs() == before
             and eng.cache.prefix_hit_tokens - hits
             >= (len(sent) if bos else 1),
             f"a window after the warm-up compiled "
             f"{system.programs() - before} programs; prefix hits "
             f"{eng.cache.prefix_hit_tokens - hits} of {len(sent)}")
        system.free()


def check_correct_fails(c: Count) -> None:
    """``correct`` is a comparison that has been seen to fail: the
    control (the reference's own forward in int8, one precision below)
    reads past the limit at every position count a run compares, and a
    run whose tokens are altered where the engine produces them comes
    out not correct; the same run untouched is correct."""
    import numpy as np
    from .builders import llama_engine
    bench = manifest.load()
    name = next(w["name"] for w in bench["workloads"]
                if manifest.cell(bench, w["name"])["config"]["kind"]
                == "llama_engine")
    cell = tiny_cell(bench, name)
    r = run_tiny(cell, False)
    gap = next(x for x in r["compared"]
               if x["name"] == "served_logit_gap_std")
    c.ok(r["correct"] and gap["value"] < 0.01 and len(r["compared"]) == 4,
         f"the untouched run: {r['compared']}")
    with serving_loop.altered_tokens(every=5):
        r = run_tiny(cell, False)
    c.ok(not r["correct"] and r["failed"] == 0,
         f"altered tokens pass as correct: {r['compared']}")
    # the control, at a size a test run can hold: hidden 256 (at the
    # selftest's 64 a row has too few terms for int8's noise to add up)
    config = dict(llama_engine.TINY, model=dict(
        llama_engine.TINY["model"], hidden_size=256, head_dim=64,
        intermediate_size=512, num_hidden_layers=4))
    system = llama_engine.build(config, SEED)
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, system.vocab, 24)
    h = system.front.submit(prompt, 100)
    system.front.run_until_idle()
    outputs = list(h.req.output_ids)
    system.free()
    own = system.served_gaps(prompt, outputs)
    control = system.served_gaps(prompt, outputs, control=True)
    c.ok(own.max() < 0.01 < reference.LLAMA_LOGIT_TOL_STD < control.max()
         and own.mean() < 1e-4 < reference.LLAMA_LOGIT_MEAN_TOL_STD
         < control.mean(),
         f"control (widest {control.max()}, mean {control.mean()}) "
         f"against the program's own ({own.max()}, {own.mean()})")


def check_cells(c: Count) -> None:
    """Every cell's control flow, both result kinds, at a tiny size."""
    bench = manifest.load()
    for w in bench["workloads"]:
        cell = tiny_cell(bench, w["name"])
        for traced in (False, True):
            t = time.perf_counter()
            r = run_tiny(cell, traced)
            want = cell["per_layer"] if traced else cell["end_to_end"]
            missing = [m["name"] for m in want
                       if m["name"] not in r["metrics"]]
            # the CPU trace has no kernels and no line of programs:
            # their rooflines read nothing
            missing = [m for m in missing if not (
                traced and "_roofline" in m)]
            c.ok(r["correct"] and r["failed"] == 0 and r["attempted"] > 0
                 and not missing,
                 f"{w['name']} trace={int(traced)}: correct="
                 f"{r['correct']} failed={r['failed']} missing={missing}"
                 f" compared={r['compared']}")
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
    check_warm_up(c)
    check_correct_fails(c)
    check_cells(c)
    print(f"chipbench.selftest: {c.n} checks passed ({n_fast} without "
          f"a model, {c.n - n_fast} over tiny cells) in "
          f"{time.perf_counter() - t0:.0f} s on the CPU; no chip, no "
          f"result line")


if __name__ == "__main__":
    main()
