"""Step-budget decomposition: the machine-checked form of the rounds-1-5 notes
(git history before PR 23)
step waterfalls.

Buckets one profiled training step (xplane self-times on the device ops
line, via the in-tree parser ``benchmarks/xplane.py``) into a FIXED,
schema-stable set of buckets — matmul / flash / quantize / optimizer /
copy_slice / collective / fusion / rng / loop / other — and prints ONE
JSON line.  Every future claim about the non-matmul tail ("copy/slice is
72 ms", "quantize is 31 ms") is produced by this tool instead of being
hand-transcribed from chrome traces.

v2 adds the ``collectives`` record (ROADMAP item #3's multichip-overlap
tail): per-collective-kind totals (all-reduce / all-gather / reduce-
scatter / all-to-all / collective-permute) plus the EXPOSED vs
OVERLAPPED split against the union of compute intervals — run it under
the 8-chip hybrid meshes and an async collective silently turning
synchronous becomes a schema-guarded ``exposed_ms`` regression, not a
profiler anecdote.

Usage:
  # decompose an existing trace directory (jax.profiler logdir)
  python benchmarks/step_budget.py --logdir DIR --steps 3

  # profile the flagship GPT step and decompose it (TPU)
  python benchmarks/step_budget.py --run gpt --steps 3

  # CI selftest: parse the checked-in miniature fixture, assert the
  # schema (bucket keys + values) — keeps the proto walk from rotting
  # on CPU-only CI
  python benchmarks/step_budget.py --selftest

Library use (bench.py prints this next to its tokens/s line):
  from step_budget import capture, format_line
  budget = capture(step_fn, steps=3)      # None if no device plane
  print(format_line(budget))
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Dict, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _path  # noqa: F401, E402  (repo-root import shim)
import xplane  # noqa: E402

SCHEMA = "ptpu_step_budget_v2"

# The stable bucket-key set. Adding a key is a schema bump; the
# selftest and tests/test_step_budget.py pin this exact set.
# v2 keeps the buckets of v1 and ADDS the top-level `collectives`
# record (per-kind totals + exposed-vs-overlapped split) — the
# multichip-overlap artifact ROADMAP item #3 asks for.
BUCKET_KEYS = ("matmul", "flash", "quantize", "optimizer", "copy_slice",
               "collective", "fusion", "rng", "loop", "other")

# Buckets whose device time counts as COMPUTE COVER for the collective
# overlap split: a collective interval inside their union is hidden
# behind useful work, the remainder is EXPOSED wall time. copy/loop/
# rng/other are deliberately excluded — a while-envelope spans the
# whole step and would declare every collective "overlapped".
COMPUTE_COVER_BUCKETS = ("matmul", "flash", "fusion", "quantize",
                         "optimizer")

# Classification by the HLO lhs SYMBOL only (xplane.op_symbol) — the
# event name embeds the whole instruction text including operand lists,
# which is full of red herrings. First match wins, so the specific
# custom-call families (flash/quantize/optimizer) come before the
# generic ones. The substring tables live in xplane.py (shared with
# its human-readable bucketize) so the two classifiers cannot drift.
_CLASSES = (
    ("flash", xplane.FLASH_KEYS),
    ("quantize", xplane.QUANTIZE_KEYS),
    ("optimizer", xplane.OPTIMIZER_KEYS),
    ("matmul", xplane.MATMUL_KEYS),
    ("copy_slice", xplane.COPY_KEYS),
    ("collective", xplane.COLLECTIVE_KEYS),
    ("rng", xplane.RNG_KEYS),
    ("loop", xplane.LOOP_KEYS),
    ("fusion", ("fusion",)),
)


def classify(op_name: str) -> str:
    """Bucket key for one op event name."""
    sym = xplane.op_symbol(op_name).lower()
    for bucket, keys in _CLASSES:
        if any(k in sym for k in keys):
            return bucket
    return "other"


def empty_collectives() -> dict:
    """The zero collectives record (CPU smoke, single-chip steps)."""
    return {"by_kind": {}, "total_ms": 0.0, "exposed_ms": 0.0,
            "overlapped_ms": 0.0, "overlap_frac": 0.0}


def collective_detail(events, steps: int = 1) -> dict:
    """The multichip-overlap artifact: decompose one line's RAW event
    intervals ``[(op_name, start_ps, end_ps)]`` into per-collective-
    kind totals and the EXPOSED vs OVERLAPPED split — the part of
    every collective's span covered by the union of compute intervals
    (COMPUTE_COVER_BUCKETS) is hidden behind useful work; the rest is
    serial communication wall time. An overlap REGRESSION (async
    collectives silently turning synchronous) shows up as exposed_ms
    growing at constant total_ms — schema-guarded instead of being a
    profiler anecdote."""
    coll = []
    cover = []
    by_kind = defaultdict(float)
    n = max(steps, 1)
    for name, s, e in events:
        b = classify(name)
        if b == "collective":
            sym = xplane.op_symbol(name).lower()
            kind = next((k for k in xplane.COLLECTIVE_KEYS
                         if k in sym), "collective")
            coll.append((s, e, kind))
        elif b in COMPUTE_COVER_BUCKETS:
            cover.append((s, e))
    merged = []
    for s, e in sorted(cover):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    total_ps = overlapped_ps = 0
    for s, e, kind in coll:
        total_ps += e - s
        by_kind[kind] += (e - s) / 1e9 / n
        for cs, ce in merged:
            if ce <= s:
                continue
            if cs >= e:
                break
            overlapped_ps += min(e, ce) - max(s, cs)
    ms = lambda ps: round(ps / 1e9 / n, 3)
    return {
        "by_kind": {k: round(v, 3) for k, v in sorted(by_kind.items())},
        "total_ms": ms(total_ps),
        "exposed_ms": ms(total_ps - overlapped_ps),
        "overlapped_ms": ms(overlapped_ps),
        "overlap_frac": (round(overlapped_ps / total_ps, 4)
                         if total_ps else 0.0),
    }


def budget_from_times(per_op: Dict[str, float], steps: int = 1,
                      line: str = "", plane: str = "",
                      collectives: Optional[dict] = None) -> dict:
    """Collapse {op_name: total_ms} into the schema-stable record.
    ``collectives`` carries the interval-level overlap record when the
    caller has one (budget_from_xplane does); else the zero record —
    the key is always present, schema-stable."""
    buckets = defaultdict(float)
    for name, ms in per_op.items():
        buckets[classify(name)] += ms / max(steps, 1)
    out = {k: round(buckets.get(k, 0.0), 3) for k in BUCKET_KEYS}
    return {
        "schema": SCHEMA,
        "steps": int(steps),
        "plane": plane,
        "line": line,
        "total_ms": round(sum(out.values()), 3),
        "buckets": out,
        "collectives": (collectives if collectives is not None
                        else empty_collectives()),
    }


def budget_from_xplane(path: str, steps: int = 1,
                       plane_filter: str = "TPU",
                       line_filter: Optional[str] = None
                       ) -> Optional[dict]:
    """Decompose one xplane.pb file; None if no matching plane. Uses
    SELF times (nested region envelopes keep only their non-child
    remainder), and picks the 'XLA Ops' line when present — the per-op
    device line — else the busiest line."""
    # ONE proto walk feeds both views — a multi-step flagship trace is
    # tens of MB and this runs per bench invocation
    pd = list(xplane.planes(path))
    per_line = xplane.op_self_times(path, plane_filter=plane_filter,
                                    line_filter=line_filter,
                                    planes_data=pd)
    if not per_line:
        return None
    line = "XLA Ops" if "XLA Ops" in per_line else \
        max(per_line, key=lambda k: len(per_line[k]))
    intervals = xplane.op_intervals(path, plane_filter=plane_filter,
                                    line_filter=line_filter,
                                    planes_data=pd)
    return budget_from_times(per_line[line], steps=steps, line=line,
                             plane=plane_filter,
                             collectives=collective_detail(
                                 intervals.get(line, []), steps=steps))


def budget_from_logdir(logdir: str, steps: int = 1,
                       plane_filter: str = "TPU",
                       line_filter: Optional[str] = None
                       ) -> Optional[dict]:
    return budget_from_xplane(xplane.latest_xplane(logdir),
                              steps=steps, plane_filter=plane_filter,
                              line_filter=line_filter)


def capture(step_fn, steps: int = 3, plane_filter: str = "TPU",
            logdir: Optional[str] = None,
            line_filter: Optional[str] = None) -> Optional[dict]:
    """Profile ``steps`` calls of ``step_fn`` under jax.profiler and
    decompose. Caller is responsible for warmup (compile OUTSIDE the
    trace window). Returns None when the trace has no matching device
    plane (e.g. CPU smoke runs with plane_filter='TPU'). A tempdir
    trace (no ``logdir`` given) is deleted after decoding — a 3-step
    flagship xplane is hundreds of MB, and bench.py runs this on every
    TPU invocation; pass an explicit ``logdir`` to keep the trace."""
    import shutil
    import tempfile

    import jax
    own_dir = logdir is None
    logdir = logdir or tempfile.mkdtemp(prefix="ptpu_budget_")
    try:
        jax.profiler.start_trace(logdir)
        try:
            out = None
            for _ in range(steps):
                out = step_fn()
            if out is not None:
                arr = getattr(out, "_data", None)
                if arr is None:
                    leaves = jax.tree.leaves(out)
                    arr = leaves[0] if leaves else None
                if arr is not None:
                    jax.device_get(arr)  # drain the dispatched pipeline
        finally:
            jax.profiler.stop_trace()
        try:
            return budget_from_logdir(logdir, steps=steps,
                                      plane_filter=plane_filter,
                                      line_filter=line_filter)
        except FileNotFoundError:
            return None
    finally:
        if own_dir:
            shutil.rmtree(logdir, ignore_errors=True)


def format_line(budget: dict) -> str:
    """The one-line artifact: 'STEP_BUDGET {json}' (sorted keys — byte
    stable for a given record)."""
    return "STEP_BUDGET " + json.dumps(budget, sort_keys=True)


# ---------------------------------------------------------------------------
# selftest fixture: a miniature synthetic trace with one representative
# op per bucket plus a nested while-region (exercises the self-time
# subtraction). Checked in at benchmarks/fixtures/mini_step.xplane.pb;
# regenerate with --write-fixture after an intentional schema change.
# ---------------------------------------------------------------------------

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "mini_step.xplane.pb")

# (op event name, offset_ps, duration_ps) — 1 ms == 1e9 ps
_FIXTURE_EVENTS = [
    ("%while.1 = ...", 0, 10_000_000_000),           # envelope: 10 ms
    ("%fusion.12 = bf16[6144,8192] fusion(...)", 0, 4_000_000_000),
    ("%dot.3 = bf16[6144,2048] dot(...)", 4_000_000_000,
     3_000_000_000),
    ("%copy.7 = bf16[24,6144,2048] copy(...)", 7_000_000_000,
     2_000_000_000),
    # outside the envelope:
    ("%fa_fwd.2 = custom-call(...)", 10_000_000_000, 5_000_000_000),
    ("%_sr_colq_pallas.4 = custom-call(...)", 15_000_000_000,
     2_500_000_000),
    ("%fused_adamw.9 = custom-call(...)", 17_500_000_000,
     1_500_000_000),
    ("%dynamic-update-slice.5 = ...", 19_000_000_000, 1_000_000_000),
    ("%convert.6 = f32[...] convert(...)", 20_000_000_000,
     500_000_000),
    ("%all-reduce.8 = ...", 20_500_000_000, 250_000_000),
    ("%rng-bit-generator.10 = ...", 20_750_000_000, 250_000_000),
    ("%transcendental.11 = ...", 21_000_000_000, 1_000_000_000),
]

# expected per-step buckets for the fixture at steps=2 (ms):
#   while envelope self = 10 - (4 + 3 + 2) = 1 ms
_FIXTURE_EXPECT = {
    "matmul": 1.5, "flash": 2.5, "quantize": 1.25, "optimizer": 0.75,
    "copy_slice": 1.75, "collective": 0.125, "fusion": 2.0,
    "rng": 0.125, "loop": 0.5, "other": 0.5,
}


def write_fixture(path: str = FIXTURE) -> str:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    return xplane.write_xspace(path, [
        ("/device:TPU:0 (fixture)",
         [("XLA Ops", _FIXTURE_EVENTS),
          # a non-ops line the decomposer must ignore
          ("Steps", [("train_step.0", 0, 22_000_000_000)])]),
        ("/host:CPU (fixture)", [("python", [("noise", 0, 10)])]),
    ])


def selftest() -> dict:
    """Parse the checked-in fixture and assert the stable schema."""
    budget = budget_from_xplane(FIXTURE, steps=2)
    assert budget is not None, f"no TPU plane parsed from {FIXTURE}"
    assert budget["schema"] == SCHEMA, budget["schema"]
    assert tuple(sorted(budget["buckets"])) == tuple(sorted(BUCKET_KEYS)), \
        sorted(budget["buckets"])
    assert budget["line"] == "XLA Ops", budget["line"]
    for k, want in _FIXTURE_EXPECT.items():
        got = budget["buckets"][k]
        assert abs(got - want) < 1e-6, (k, got, want)
    assert abs(budget["total_ms"] - sum(_FIXTURE_EXPECT.values())) \
        < 1e-6, budget["total_ms"]
    # v2 collectives record: the fixture's all-reduce sits outside
    # every compute interval — fully EXPOSED
    coll = budget["collectives"]
    assert coll["by_kind"] == {"all-reduce": 0.125}, coll
    assert abs(coll["total_ms"] - 0.125) < 1e-6, coll
    assert abs(coll["exposed_ms"] - 0.125) < 1e-6, coll
    assert coll["overlapped_ms"] == 0.0 and coll["overlap_frac"] == 0.0
    return budget


def mesh_collectives_smoke(steps: int = 3) -> Optional[dict]:
    """ROADMAP item-#3 tail that needs no real chips: run a distilled
    HYBRID-MESH (fsdp x model) training-shaped step on the live device
    set — the CPU-emulated 8-device mesh in CI (same
    ``--xla_force_host_platform_device_count=8`` emulation as the
    MULTICHIP artifacts), real chips on TPU — profile it, and
    decompose with the v2 ``collectives`` record. This exercises the
    exposed-vs-overlapped split against an ACTUAL multi-device
    execution's all-reduce/all-gather intervals instead of the
    synthetic fixture: the flow an on-chip bench run reuses.

    The step is Megatron-shaped in miniature: activations data-
    parallel over `fsdp`, both weights output/contraction-sharded over
    `model`, so the forward needs a model-axis all-reduce (the
    row-parallel psum) and the loss reduction crosses `fsdp`. On CPU
    the XLA thunk executor records per-device op events (all-reduce /
    dot / fusion) on its client lines, which the CPU plane filter +
    executor line filter pick up; on TPU the usual 'XLA Ops' line
    serves.  Returns None when no device plane matched."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = jax.device_count()
    if n < 4 or n % 2:
        return None
    mesh = Mesh(np.asarray(jax.devices()).reshape(n // 2, 2),
                ("fsdp", "model"))
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    rng = np.random.RandomState(0)
    x = jax.device_put(
        rng.randn(8 * (n // 2), 128).astype(np.float32),
        sh("fsdp", None))
    w1 = jax.device_put(rng.randn(128, 256).astype(np.float32),
                        sh(None, "model"))
    w2 = jax.device_put(rng.randn(256, 128).astype(np.float32),
                        sh("model", None))

    @jax.jit
    def step(x, w1, w2):
        h = jnp.maximum(x @ w1, 0.0)     # col-parallel over `model`
        y = h @ w2                       # row-parallel -> all-reduce
        return jnp.sum((y - x) ** 2)     # loss crosses `fsdp` too

    step(x, w1, w2).block_until_ready()  # compile outside the trace
    on_tpu = jax.default_backend() == "tpu"
    return capture(lambda: step(x, w1, w2), steps=steps,
                   plane_filter="TPU" if on_tpu else "CPU",
                   # the CPU client's per-device executor threads
                   # ("tf_XLAPjRtCpuClient/<id>" in jax 0.9 traces)
                   line_filter=None if on_tpu else "CpuClient")


def _run_gpt_step():
    """Return a zero-arg step closure over the COMMITTED bench recipe
    (bench.build_flagship — one definition, so this tool's STEP_BUDGET
    line decomposes exactly the configuration behind the BENCH
    headline, env knobs like PTPU_LAYER_UNROLL included)."""
    import bench  # repo root, via the _path shim
    trainer, ids, labels, _ = bench.build_flagship()

    def step():
        return trainer.train_step(ids, labels)
    return step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--logdir", help="existing jax.profiler logdir")
    ap.add_argument("--xplane", help="existing .xplane.pb file")
    ap.add_argument("--run", choices=["gpt", "mesh-smoke"],
                    help="profile this workload then decompose "
                         "(mesh-smoke: distilled hybrid-mesh step on "
                         "the live devices, collectives record)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--plane", default="TPU",
                    help="plane-name substring filter (default TPU)")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--write-fixture", action="store_true")
    ap.add_argument("--out", help="also write the JSON record here")
    args = ap.parse_args()
    if args.run:  # the only modes that compile
        from paddle_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()

    if args.write_fixture:
        print(write_fixture())
        return
    if args.selftest:
        budget = selftest()
        print(format_line(budget))
        print("selftest OK")
        return
    if args.run == "mesh-smoke":
        import jax
        if jax.device_count() < 4 or jax.device_count() % 2:
            print("# mesh-smoke needs >= 4 devices (an even count); "
                  "on CPU set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=8")
            return
        budget = mesh_collectives_smoke(steps=args.steps)
        if budget is None:
            print("# no device plane/executor line matched the "
                  "mesh-smoke trace — nothing to decompose")
            return
    elif args.run:
        import jax
        step = _run_gpt_step()
        for _ in range(2):  # compile outside the trace window
            out = step()
        jax.device_get(jax.tree.leaves(out)[0])
        budget = capture(step, steps=args.steps,
                         plane_filter=args.plane)
    elif args.xplane:
        budget = budget_from_xplane(args.xplane, steps=args.steps,
                                    plane_filter=args.plane)
    elif args.logdir:
        budget = budget_from_logdir(args.logdir, steps=args.steps,
                                    plane_filter=args.plane)
    else:
        ap.error("need one of --logdir/--xplane/--run/--selftest")
    if budget is None:
        print(f"# no plane matching {args.plane!r} in trace — nothing "
              f"to decompose (CPU run?)")
        return
    line = format_line(budget)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(budget, sort_keys=True) + "\n")


if __name__ == "__main__":
    main()
