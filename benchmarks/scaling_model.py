"""8->256 chip scaling model from compiled-HLO collective traffic.

BASELINE.json names "8->256 chip scaling eff" as a first-class metric;
one real chip cannot measure it. This tool produces the next-best
artifact, the reference's cost-model analog
(python/paddle/distributed/auto_parallel/static/cost/): it

1. compiles the REAL training programs (BERT-base DP DistModel via
   DistModel.lower(); GPT hybrid via GPTSpmdTrainer.build_step().lower)
   on virtual CPU meshes of 8/16/32 devices,
2. counts every collective's bytes and group size straight from the
   optimized HLO (`collectives_from_hlo`) — the same numbers a test
   re-derives so the model cannot rot,
3. folds the counts into a v5e ICI roofline and emits predicted
   weak-scaling curves at 8/32/64/256 chips (benchmarks/SCALING.md).

Cost model (assumptions stated, all overridable):
- v5e: 2D ICI torus, one pod = 256 chips (8->256 never touches DCN).
  Per-link one-direction bandwidth 45 GB/s; a ring over a torus axis
  streams both directions => 90 GB/s per chip per mesh axis
  (jax-ml.github.io/scaling-book, v5e table).
- ring costs per chip: all-reduce 2(g-1)/g * B; all-gather and
  reduce-scatter (g-1)/g * B (B = full payload bytes); all-to-all
  (g-1)/g^2 * B; collective-permute B.
- compute time from the measured single-chip step (the rounds-1-5 notes (git
history before PR 23)), held
  constant per chip (weak scaling: per-chip batch fixed).
- two efficiency curves: exposed (zero overlap, worst case) and
  overlapped (collectives hide under compute up to 100%, cost =
  max(compute, comm) — the DP gradient bucket pipelining the
  reference's EagerReducer implements sits between the two).
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List

import _path  # noqa: F401

# -- v5e constants (see module docstring) --------------------------------
ICI_AXIS_BYTES_PER_S = 90e9        # bidirectional ring, per chip
POD_CHIPS = 256

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4,
                "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "s64": 8, "u64": 8, "c64": 8, "c128": 16}

# XLA's combiner pass merges gradient all-reduces into ONE op with a
# TUPLE shape: `%ar = (f32[128,512], f32[512], ...) all-reduce(...)` —
# the shape list between '= ' and the op mnemonic must be summed, not
# first-matched.
# NOTE: long tuples embed `/*index=5*/` comments, so the shape blob
# must be matched lazily with `.*?` up to the op mnemonic, not `[^=]*`.
_COLL_RE = re.compile(
    r"=\s+(\(?[a-z0-9]+\[[0-9,]*\].*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,]+)\}")
_GROUPS_ITOA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


@dataclass
class Collective:
    kind: str
    bytes: int          # payload (full buffer) bytes
    group: int          # participants per group

    def chip_bytes(self) -> float:
        """Bytes each chip moves over its axis links (ring model)."""
        g, b = self.group, self.bytes
        if g <= 1:
            return 0.0
        if self.kind == "all-reduce":
            return 2.0 * (g - 1) / g * b
        if self.kind in ("all-gather", "reduce-scatter"):
            return (g - 1) / g * b
        if self.kind == "all-to-all":
            return (g - 1) / (g * g) * b
        return float(b)  # collective-permute


def collectives_from_hlo(hlo: str) -> List[Collective]:
    """Every collective op in an optimized-HLO dump, with payload bytes
    and group size. `-done` ops are skipped (their `-start` carries the
    shape); fusions never contain collectives in XLA."""
    out = []
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        shapes, kind = m.group(1), m.group(2)
        total = 0
        for dtype, dims in _SHAPE_RE.findall(shapes):
            if dtype not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dtype]
        if not total:
            continue
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gm2 = _GROUPS_ITOA_RE.search(line)
            if gm2:  # iota form [num_groups, group_size]
                g = int(gm2.group(2))
        out.append(Collective(kind, total, g))
    return out


def traffic_summary(colls: List[Collective]) -> Dict[str, float]:
    by_kind: Dict[str, float] = defaultdict(float)
    for c in colls:
        by_kind[c.kind] += c.chip_bytes()
    by_kind["total"] = sum(by_kind.values())
    return dict(by_kind)


def comm_seconds(colls: List[Collective],
                 axis_bw: float = ICI_AXIS_BYTES_PER_S) -> float:
    """Serial ring-model time for all collectives of one step."""
    return sum(c.chip_bytes() for c in colls) / axis_bw


def efficiency(t_compute: float, t_comm: float):
    """(exposed, overlapped) weak-scaling efficiency."""
    return (t_compute / (t_compute + t_comm),
            t_compute / max(t_compute, t_comm))


# -- program builders (virtual CPU mesh) ---------------------------------

def _force_cpu(n: int):
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}")
    import jax
    jax.config.update("jax_platforms", "cpu")


def bert_dp_hlo(n_devices: int, bs_per_dev: int = 2, seq: int = 128,
                cfg_kw: Dict = None) -> str:
    """Optimized HLO of the BERT-base DP train step (DistModel path —
    the same program bench_bert_dp.py times)."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    cfg_kw = cfg_kw or dict(vocab_size=1024, hidden_size=128,
                            num_hidden_layers=2, num_attention_heads=4,
                            intermediate_size=512,
                            max_position_embeddings=seq)
    mesh = dist.ProcessMesh(list(range(n_devices)), dim_names=["dp"])
    dist.set_mesh(mesh)
    try:
        paddle.seed(0)
        cfg = BertConfig(**cfg_kw)
        model = BertForPretraining(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        def loss_fn(*args):
            pred, mlm_labels = args[0], args[-1]
            return paddle.nn.functional.cross_entropy(
                pred.reshape([-1, cfg.vocab_size]),
                mlm_labels.reshape([-1]))

        dm = dist.to_static(model, loss=loss_fn, optimizer=opt)
        B = bs_per_dev * n_devices
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (B, seq)).astype("int64"))
        labels = paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (B, seq)).astype("int64"))
        return dm.lower(ids, labels).compile().as_text()
    finally:
        dist.set_mesh(None)


def gpt_hybrid_hlo(n_devices: int, mesh_shape: Dict[str, int],
                   bs_per_data: int = 2, seq: int = 64,
                   cfg_kw: Dict = None) -> str:
    """Optimized HLO of the GPT hybrid (tp x dp x fsdp [x pipe]) step."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.gpt import (GPTConfig, GPTSpmdTrainer,
                                       build_mesh)

    cfg_kw = cfg_kw or dict(vocab_size=512, hidden_size=64,
                            num_layers=4, num_heads=4, max_seq_len=seq,
                            dtype=jnp.float32)
    cfg = GPTConfig(**cfg_kw)
    mesh = build_mesh(n_devices=n_devices, **mesh_shape)
    trainer = GPTSpmdTrainer(cfg, mesh, microbatches=1)
    B = bs_per_data * mesh.shape["data"]
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, seq)).astype(np.int32)
    labels = np.roll(ids, -1, 1)
    import jax
    fn = trainer.build_step()
    with jax.set_mesh(mesh):
        lowered = fn.lower(trainer.params, trainer.opt_state, ids,
                           labels)
        return lowered.compile().as_text()


# -- the report ----------------------------------------------------------

def grad_allreduce_bytes(model_param_bytes: float, g: int) -> float:
    return 2.0 * (g - 1) / g * model_param_bytes


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/SCALING.md")
    ap.add_argument("--devices", type=int, nargs="+",
                    default=[8, 16, 32])
    args = ap.parse_args()
    _force_cpu(max(args.devices))
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    lines = []
    results = {}

    # ---- BERT-DP: count at several world sizes, fit, extrapolate ----
    bert_counts = {}
    for n in args.devices:
        colls = collectives_from_hlo(bert_dp_hlo(n))
        bert_counts[n] = traffic_summary(colls)
    # DP law: per-chip allreduce bytes = 2(g-1)/g * G. Fit G from the
    # largest compiled world, then check the smaller ones against it.
    n_fit = max(bert_counts)
    G = bert_counts[n_fit]["total"] / (2 * (n_fit - 1) / n_fit)
    fit_err = {}
    for n, t in bert_counts.items():
        pred = grad_allreduce_bytes(G, n)
        fit_err[n] = abs(pred - t["total"]) / max(t["total"], 1)
    results["bert_dp"] = {"counts": bert_counts, "G_bytes": G,
                          "fit_rel_err": fit_err}

    # Weak-scaling prediction at REAL scale: BERT-base params ~110M
    # plus one extra V*D ride for the tied MLM-decoder gradient (the
    # compiled HLO all-reduces the lookup and decoder contributions
    # separately — tests/test_scaling_model.py pins this), grads bf16
    # on the wire at the measured per-chip step time.
    bert_param_bytes = (110e6 + 30522 * 768) * 2
    t_comp = (32 * 128) / 57593.0      # measured: bs32/seq128 per chip
    curve = {}
    for n in (8, 32, 64, 256):
        t_comm = grad_allreduce_bytes(bert_param_bytes, n) \
            / ICI_AXIS_BYTES_PER_S
        exposed, overlapped = efficiency(t_comp, t_comm)
        curve[n] = {"t_compute_ms": round(t_comp * 1e3, 2),
                    "t_comm_ms": round(t_comm * 1e3, 3),
                    "eff_exposed": round(exposed, 4),
                    "eff_overlapped": round(overlapped, 4)}
    results["bert_dp"]["curve"] = curve

    # ---- GPT hybrid: tp inside, dp/fsdp across ----
    hybrid_counts = {}
    shapes = {8: dict(model=2, data=2, fsdp=2, pipe=1, sep=1),
              16: dict(model=2, data=4, fsdp=2, pipe=1, sep=1),
              32: dict(model=2, data=8, fsdp=2, pipe=1, sep=1)}
    for n in args.devices:
        if n not in shapes:
            continue
        colls = collectives_from_hlo(gpt_hybrid_hlo(n, shapes[n]))
        by_kind = traffic_summary(colls)
        hybrid_counts[n] = by_kind
    results["gpt_hybrid"] = {"counts": hybrid_counts,
                             "shapes": {k: v for k, v in shapes.items()
                                        if k in hybrid_counts}}

    # Real-scale projection for the flagship recipe at v5e-256:
    # tp=8 (inside a torus row), fsdp=32 over the rest; per-chip
    # traffic per step from analytic per-axis laws validated above.
    # GPT-1.3B: params 1.31e9 * 2B (bf16); activations per layer
    # [B=6,S=1024,D=2048] bf16 = 25.2 MB.
    P_bytes = 1.31e9 * 2
    act_bytes = 6 * 1024 * 2048 * 2
    L = 24
    t_comp = 0.348                     # measured single-chip step
    curve = {}
    for n in (8, 32, 64, 256):
        tp = min(8, n // 4)
        fsdp = n // tp
        # tp: 2 allreduce (fwd) + 2 allreduce (bwd) per layer on
        # activations (Megatron f/g ops)
        tp_bytes = L * 4 * 2 * (tp - 1) / tp * act_bytes / tp
        # fsdp: allgather params fwd+bwd, reduce-scatter grads
        fsdp_bytes = 3 * (fsdp - 1) / fsdp * (P_bytes / 1)
        t_comm = (tp_bytes + fsdp_bytes) / ICI_AXIS_BYTES_PER_S
        exposed, overlapped = efficiency(t_comp, t_comm)
        curve[n] = {"mesh": f"tp={tp} fsdp={fsdp}",
                    "t_compute_ms": round(t_comp * 1e3, 1),
                    "t_comm_ms": round(t_comm * 1e3, 2),
                    "eff_exposed": round(exposed, 4),
                    "eff_overlapped": round(overlapped, 4)}
    results["gpt_hybrid"]["curve"] = curve

    md = _render(results)
    with open(args.out, "w") as f:
        f.write(md)
    print(json.dumps({"out": args.out,
                      "bert_fit_rel_err": fit_err,
                      "bert_eff_256_overlapped":
                          results["bert_dp"]["curve"][256][
                              "eff_overlapped"],
                      "gpt_eff_256_overlapped":
                          results["gpt_hybrid"]["curve"][256][
                              "eff_overlapped"]}))
    return results


def _render(r) -> str:
    out = ["# Predicted 8->256 chip weak-scaling (v5e ICI model)", "",
           "Produced by `python benchmarks/scaling_model.py` — byte",
           "counts come from the OPTIMIZED HLO of the real compiled",
           "programs on virtual CPU meshes (tests/test_scaling_model.py",
           "re-derives them so this file cannot rot); the ICI constants",
           "and ring formulas are in scaling_model.py's docstring.", ""]
    b = r["bert_dp"]
    out += ["## BERT-base pure DP (BASELINE configs[1])", "",
            f"Fitted gradient payload G = {b['G_bytes']:.3e} B from "
            f"compiled HLO; per-world fit error: " +
            ", ".join(f"{n}: {e:.1%}" for n, e in
                      sorted(b["fit_rel_err"].items())), "",
            "| chips | t_comp ms | t_comm ms | eff (exposed) | "
            "eff (overlapped) |", "|---|---|---|---|---|"]
    for n, c in sorted(b["curve"].items()):
        out.append(f"| {n} | {c['t_compute_ms']} | {c['t_comm_ms']} | "
                   f"{c['eff_exposed']:.3f} | "
                   f"{c['eff_overlapped']:.3f} |")
    g = r["gpt_hybrid"]
    out += ["", "## GPT-1.3B hybrid tp x fsdp (BASELINE configs[2])", "",
            "Compiled-HLO per-chip traffic at small worlds "
            "(bytes/step, ring model):", ""]
    for n, t in sorted(g["counts"].items()):
        out.append(f"- {n} devices {g['shapes'][n]}: " +
                   ", ".join(f"{k} {v:.2e}" for k, v in
                             sorted(t.items())))
    out += ["", "| chips | mesh | t_comp ms | t_comm ms | "
            "eff (exposed) | eff (overlapped) |", "|---|---|---|---|---|---|"]
    for n, c in sorted(g["curve"].items()):
        out.append(f"| {n} | {c['mesh']} | {c['t_compute_ms']} | "
                   f"{c['t_comm_ms']} | {c['eff_exposed']:.3f} | "
                   f"{c['eff_overlapped']:.3f} |")
    out += ["", "Assumptions: 90 GB/s bidirectional ring bandwidth per",
            "chip per mesh axis (v5e 2D torus, 45 GB/s/link/direction);",
            "one v5e pod = 256 chips so no DCN hop appears in 8->256;",
            "per-chip batch fixed (weak scaling); compute times are the",
            "MEASURED single-chip steps from the rounds-1-5 notes (git",
            "history before PR 23).",
            "Exposed = zero overlap (worst case); overlapped = perfect",
            "compute/comm overlap (max(comp, comm)). The reference's",
            "bucketed EagerReducer and our jit schedules land between",
            "the two bounds.", ""]
    return "\n".join(out)


if __name__ == "__main__":
    main()
