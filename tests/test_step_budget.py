"""Step-budget tool (benchmarks/step_budget.py): the selftest fixture
parses with stable bucket keys on CPU-only CI, the xplane writer
round-trips through the parser, and the classifier buckets the op
families the rounds-1-5 notes (git history before PR 23) ledgers talk about
(tier-1 by design — the tool
must not silently rot between TPU rounds)."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(HERE, "benchmarks")
sys.path.insert(0, BENCH)

import step_budget  # noqa: E402
import xplane  # noqa: E402


def test_selftest_fixture_parses_with_stable_schema():
    budget = step_budget.selftest()
    assert budget["schema"] == "ptpu_step_budget_v2"
    assert set(budget["buckets"]) == set(step_budget.BUCKET_KEYS)
    # v2: the collectives record is always present, stable keys
    assert set(budget["collectives"]) == {
        "by_kind", "total_ms", "exposed_ms", "overlapped_ms",
        "overlap_frac"}


def test_mesh_collectives_record_on_emulated_hybrid_mesh():
    """ROADMAP item-#3 tail (ISSUE-9 satellite): the v2 `collectives`
    record measured against an ACTUAL hybrid-mesh (fsdp x model)
    execution on the emulated 8-device CPU mesh — not the synthetic
    fixture. The step's row-parallel matmul forces a model-axis
    all-reduce, so the record must carry real collective time with a
    coherent exposed-vs-overlapped split (exposed + overlapped ==
    total within rounding, frac in [0, 1])."""
    from conftest import require_devices
    require_devices(8)
    budget = step_budget.mesh_collectives_smoke(steps=2)
    assert budget is not None, "no device plane matched the trace"
    assert budget["schema"] == "ptpu_step_budget_v2"
    coll = budget["collectives"]
    assert coll["total_ms"] > 0, budget
    assert any("all-reduce" in k or "all-gather" in k
               or "reduce-scatter" in k for k in coll["by_kind"]), \
        coll
    assert abs(coll["exposed_ms"] + coll["overlapped_ms"]
               - coll["total_ms"]) <= 0.01, coll
    assert 0.0 <= coll["overlap_frac"] <= 1.0
    # the chosen line is a per-device executor line, and the bucket
    # view agrees with the interval view on collective presence
    assert budget["buckets"]["collective"] > 0, budget


def test_selftest_cli_entrypoint():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "step_budget.py"),
         "--selftest"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines()
             if l.startswith("STEP_BUDGET ")]
    assert lines, r.stdout
    rec = json.loads(lines[0][len("STEP_BUDGET "):])
    assert set(rec["buckets"]) == set(step_budget.BUCKET_KEYS)
    assert "selftest OK" in r.stdout


def test_writer_parser_roundtrip(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    xplane.write_xspace(path, [
        ("/device:TPU:0", [
            ("XLA Ops", [("%dot.1 = f32[2,2] dot(...)", 0, 2_000_000),
                         ("%copy.2 = ...", 2_000_000, 1_000_000)]),
        ]),
    ])
    per_line = xplane.op_self_times(path)
    assert "XLA Ops" in per_line
    ops = per_line["XLA Ops"]
    assert abs(sum(ops.values()) - 0.003) < 1e-9, ops  # ms
    # nesting: an envelope keeps only its non-child remainder
    path2 = str(tmp_path / "n.xplane.pb")
    xplane.write_xspace(path2, [
        ("/device:TPU:0", [
            ("XLA Ops", [("%while.1 = ...", 0, 10_000_000),
                         ("%dot.2 = ...", 1_000_000, 4_000_000)]),
        ]),
    ])
    ops2 = xplane.op_self_times(path2)["XLA Ops"]
    assert abs(ops2["%while.1 = ..."] - 0.006) < 1e-9, ops2
    assert abs(ops2["%dot.2 = ..."] - 0.004) < 1e-9, ops2


def test_classifier_buckets_known_op_families():
    c = step_budget.classify
    assert c("%fusion.339 = bf16[6144,8192] fusion(...)") == "fusion"
    assert c("%dot.5 = ...") == "matmul"
    assert c("%convolution.2 = ...") == "matmul"
    assert c("%dynamic-update-slice.7 = ...") == "copy_slice"
    assert c("%convert.12 = f32[...] convert(...)") == "copy_slice"
    assert c("%reduce-precision.3 = ...") == "copy_slice"
    assert c("%fa_fwd.1 = custom-call(...)") == "flash"
    assert c("%fa_bwd.4 = custom-call(...)") == "flash"
    assert c("%_sr_colq_pallas.9 = ...") == "quantize"
    assert c("%_rowq_call.2 = ...") == "quantize"
    assert c("%fused_adamw.3 = ...") == "optimizer"
    assert c("%all-reduce.1 = ...") == "collective"
    assert c("%rng-bit-generator.6 = ...") == "rng"
    assert c("%while.9 = ...") == "loop"
    assert c("%exponential.2 = ...") == "other"
    # classification keys off the lhs SYMBOL only: a dot in the operand
    # text must not hijack the bucket
    assert c("%fusion.1 = fusion(%dot.5, %copy.2)") == "fusion"


def test_budget_from_times_schema_and_per_step_division():
    per_op = {"%dot.1 = ...": 6.0, "%copy.2 = ...": 3.0}
    b = step_budget.budget_from_times(per_op, steps=3, line="XLA Ops",
                                      plane="TPU")
    assert b["schema"] == step_budget.SCHEMA
    assert set(b["buckets"]) == set(step_budget.BUCKET_KEYS)
    assert b["buckets"]["matmul"] == 2.0
    assert b["buckets"]["copy_slice"] == 1.0
    assert b["buckets"]["flash"] == 0.0  # absent families stay present
    assert b["total_ms"] == 3.0
    # no interval data -> the ZERO collectives record, key still there
    assert b["collectives"] == step_budget.empty_collectives()
    # the printed artifact is byte-stable for a given record
    assert step_budget.format_line(b) == step_budget.format_line(
        json.loads(json.dumps(b)))


# -- v2 collectives: the multichip-overlap artifact --------------------

def test_collective_detail_exposed_vs_overlapped_split():
    """An all-reduce half-hidden under a dot, an all-gather fully
    exposed: the split must attribute exactly the covered picoseconds
    to overlapped and the remainder to exposed, per step."""
    events = [
        ("%dot.1 = ...", 0, 4_000_000_000),            # compute 0-4ms
        # all-reduce 2-6 ms: 2 ms under the dot, 2 ms exposed
        ("%all-reduce.2 = ...", 2_000_000_000, 6_000_000_000),
        # all-gather 7-8 ms: nothing covers it
        ("%all-gather.3 = ...", 7_000_000_000, 8_000_000_000),
        # a while envelope spanning everything must NOT count as cover
        ("%while.4 = ...", 0, 10_000_000_000),
    ]
    c = step_budget.collective_detail(events, steps=1)
    assert c["by_kind"] == {"all-reduce": 4.0, "all-gather": 1.0}
    assert c["total_ms"] == 5.0
    assert c["overlapped_ms"] == 2.0
    assert c["exposed_ms"] == 3.0
    assert c["overlap_frac"] == 0.4
    # per-step division
    c2 = step_budget.collective_detail(events, steps=2)
    assert c2["total_ms"] == 2.5 and c2["overlapped_ms"] == 1.0
    assert c2["overlap_frac"] == 0.4          # fraction is step-free


def test_collective_detail_merges_fragmented_compute_cover():
    """Abutting/overlapping compute intervals merge before the
    intersection — double-covered time must not count twice."""
    events = [
        ("%fusion.1 = ...", 0, 3_000_000_000),
        ("%dot.2 = ...", 2_000_000_000, 5_000_000_000),  # overlaps
        ("%reduce-scatter.3 = ...", 1_000_000_000, 6_000_000_000),
    ]
    c = step_budget.collective_detail(events)
    assert c["by_kind"] == {"reduce-scatter": 5.0}
    assert c["overlapped_ms"] == 4.0          # covered 1-5 ms, once
    assert c["exposed_ms"] == 1.0


def test_collectives_flow_through_budget_from_xplane(tmp_path):
    path = str(tmp_path / "c.xplane.pb")
    xplane.write_xspace(path, [
        ("/device:TPU:0", [
            ("XLA Ops", [
                ("%dot.1 = ...", 0, 4_000_000),
                ("%all-reduce.2 = ...", 3_000_000, 2_000_000),
            ]),
        ]),
    ])
    b = step_budget.budget_from_xplane(path, steps=1)
    assert b["schema"] == "ptpu_step_budget_v2"
    c = b["collectives"]
    assert c["by_kind"] == {"all-reduce": 0.002}
    assert c["overlapped_ms"] == 0.001        # 3-4 ms... (us scale)
    assert c["exposed_ms"] == 0.001
    assert c["overlap_frac"] == 0.5
    # raw-interval reader round-trips the writer
    iv = xplane.op_intervals(path)["XLA Ops"]
    assert ("%all-reduce.2 = ...", 3_000_000, 5_000_000) in iv


def test_budget_none_when_no_matching_plane(tmp_path):
    path = str(tmp_path / "cpu.xplane.pb")
    xplane.write_xspace(path, [("/host:CPU", [("python", [
        ("noise", 0, 10)])])])
    assert step_budget.budget_from_xplane(path) is None


def test_fixture_is_committed_and_regenerable(tmp_path):
    """The checked-in fixture must byte-match what --write-fixture
    produces: a drifted writer (or a hand-edited fixture) fails here
    instead of silently changing what the selftest asserts."""
    assert os.path.exists(step_budget.FIXTURE), step_budget.FIXTURE
    fresh = str(tmp_path / "fresh.xplane.pb")
    xplane.write_xspace(fresh, [
        ("/device:TPU:0 (fixture)",
         [("XLA Ops", step_budget._FIXTURE_EVENTS),
          ("Steps", [("train_step.0", 0, 22_000_000_000)])]),
        ("/host:CPU (fixture)", [("python", [("noise", 0, 10)])]),
    ])
    with open(step_budget.FIXTURE, "rb") as a, open(fresh, "rb") as b:
        assert a.read() == b.read()
