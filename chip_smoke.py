"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` (one TPU chip) drives the two main paths once,
through the entry points a user calls, at full width:

- Trainer: ``bench.build_flagship()`` (GPT-3 1.3B widths, batch 6 x seq
  1024, the committed recipe), five steps; the loss is finite and falls,
  and the compiled step holds the flash, quantize and AdamW kernels.
- Trace: three traced steps through ``benchmarks/step_budget.capture``.
- Server: ``LlamaForCausalLM`` at the TinyLlama-1.1B widths in a paged
  ``ServingEngine`` behind ``ReplicaRouter`` and ``FrontDoor``; eight
  requests complete, greedy tokens equal ``model.generate()``, one
  decode program.

``python chip_smoke.py --chips 4`` (one host with four chips) runs only
the sharded paths and what they are compared with: the trainer on a
fsdp=2 x model=2 mesh against the one-device mesh, and the engine with a
``model`` axis of 4 against the single-chip engine.

``--rehearse`` runs the same control flow at a tiny size on the CPU
backend (with ``--chips 4``: on four virtual CPU devices). It proves
paths and arguments, never the chip: it prints no result line.

This parent process never imports JAX: a chip belongs to one process, so
each phase runs as a child, one at a time. The last line of a passing
chip run is ``{"ok": true, "device": {...}}``; without a TPU, or if any
phase or check fails, the exit code is non-zero and no such line is
printed.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# GPT-3 1.3B widths (bench.build_flagship) for the sharded pair. Depth is
# cut from 24: without moment8 / bf16-SR masters (single-device paths,
# off so both meshes run the same math) the one-chip side holds f32
# masters + f32 moments, which at 24 layers is 15.8 GB of state alone.
MESH_TRAINER_LAYERS = 8
# |loss_4chip - loss_1chip| / loss_1chip per step. The two programs do
# the same math in a different order: bf16 matmuls whose contraction is
# split over the `model` axis are summed by an all-reduce in another
# order than one chip's MXU accumulates them. A wrong sharding rule
# (a dropped all-reduce, a doubled shard) shows as percents, not 1e-3.
MESH_TRAINER_RTOL = 2e-3

PROMPT_LENS = (24, 40, 64, 96, 136, 184, 240, 300)
NEW_TOKENS = 32


def _check(ok, what):
    """A failed check fails the phase: no except turns it into a note."""
    if not ok:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")


def _device(rehearse, want_count):
    """Phase 'Device': the first device is a TPU (the CPU only under
    --rehearse) and the host holds the chips this run was asked for."""
    import jax
    import jaxlib
    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices())}
    print(f"device: {json.dumps(dev)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__}", flush=True)
    if d.platform != "tpu" and not rehearse:
        raise SystemExit(
            f"chip_smoke: no TPU found (jax.devices()[0].platform is "
            f"{d.platform!r}); run on the chip, or pass --rehearse for "
            f"the CPU rehearsal")
    _check(dev["count"] == want_count,
           f"this run needs {want_count} device(s), jax sees "
           f"{dev['count']}")
    return dev


def _cache():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    d = enable_compile_cache()
    print(f"compile cache: {d} "
          f"({len(os.listdir(d)) if os.path.isdir(d) else 0} entries "
          f"at start)", flush=True)


def _kernel_counts(compiled_text):
    """Pallas kernels in a compiled step, by family: one
    ``tpu_custom_call`` line each, classified by the same name tables
    the trace reader buckets with (benchmarks/xplane.py)."""
    import xplane
    fams = {"flash": xplane.FLASH_KEYS, "quantize": xplane.QUANTIZE_KEYS,
            "adamw": xplane.OPTIMIZER_KEYS}
    counts = dict.fromkeys(fams, 0)
    counts["other"] = 0
    for line in compiled_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        # the instruction name and op_name, not the kernel's own bytes
        head = line.split("backend_config=")[0]
        for fam, keys in fams.items():
            if any(k in head for k in keys):
                counts[fam] += 1
                break
        else:
            counts["other"] += 1
    return counts


def _memory():
    """memory_stats() of every device, in GiB (zeros on the CPU)."""
    import jax
    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({"id": d.id,
                    "in_use_gib": round(
                        st.get("bytes_in_use", 0) / 2**30, 3),
                    "peak_gib": round(
                        st.get("peak_bytes_in_use", 0) / 2**30, 3)})
    return out


# -- one chip -------------------------------------------------------------

def phase_trainer(rehearse, dev):
    """Trainer + Trace, in the process that holds the chip."""
    import jax
    import numpy as np
    sys.path.insert(0, os.path.join(HERE, "benchmarks"))
    import bench
    t0 = time.perf_counter()
    trainer, ids, labels, info = bench.build_flagship()
    print(f"trainer: built GPT-{info['size']} "
          f"({trainer.n_params() / 1e9:.3f}B params, batch "
          f"{info['batch']} x seq {info['seq']}, layer_unroll="
          f"{trainer.layer_unroll!r}) in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    losses, secs = [], []
    for _ in range(5):      # two warm-up steps and three more
        t0 = time.perf_counter()
        losses.append(float(jax.device_get(
            trainer.train_step(ids, labels))))
        secs.append(round(time.perf_counter() - t0, 3))
    print(f"trainer: losses {losses}")
    print(f"trainer: step seconds {secs} (the first holds the compile)")
    _check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall over five steps: {losses}")

    # what the chip runs: the step's own executable, compiled from the
    # trainer's jitted function at the live arguments (a persistent-
    # cache hit of the compile the first step just made)
    t0 = time.perf_counter()
    with jax.set_mesh(trainer.mesh):
        compiled = trainer.build_step().lower(
            trainer.params, trainer.opt_state, ids, labels).compile()
    kernels = _kernel_counts(compiled.as_text())
    print(f"trainer: kernels in the compiled step {kernels} "
          f"(re-lowered in {time.perf_counter() - t0:.1f}s); memory "
          f"{compiled.memory_analysis()}")
    if dev["platform"] == "tpu":
        for fam in ("flash", "quantize", "adamw"):
            _check(kernels[fam] > 0,
                   f"no {fam} kernel in the compiled step — the path "
                   f"fell to its XLA reference: {kernels}")
    print(f"trainer: device memory {_memory()}")
    print("PASS trainer", flush=True)

    from step_budget import capture, format_line
    on_tpu = dev["platform"] == "tpu"
    budget = capture(lambda: trainer.train_step(ids, labels), steps=3,
                     plane_filter="TPU" if on_tpu else "CPU",
                     line_filter=None if on_tpu else "CpuClient")
    _check(budget is not None, "no device plane found in the trace")
    print(format_line(budget))
    filled = {k: v for k, v in budget["buckets"].items() if v > 0}
    _check(filled and budget["total_ms"] > 0,
           f"trace buckets are empty: {budget}")
    if on_tpu:   # (the int8 matmuls run inside `fusion` events)
        for fam in ("flash", "quantize", "optimizer", "fusion"):
            _check(fam in filled, f"trace has no {fam} time: {filled}")
    print("PASS trace", flush=True)


def _server_model(rehearse):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    if rehearse:
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=512)
    else:       # TinyLlama-1.1B, published widths
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=22,
                          num_attention_heads=32, num_key_value_heads=4,
                          max_position_embeddings=2048)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    model.to(dtype="bfloat16")
    return model, cfg


def _serve(model, cfg, **engine_kw):
    """The 8 requests through FrontDoor -> ReplicaRouter -> a paged
    ServingEngine; returns (engine, prompts, generated tokens)."""
    import numpy as np
    from paddle_tpu.observability import MetricRegistry
    from paddle_tpu.serving import FrontDoor, ReplicaRouter, ServingEngine
    eng = ServingEngine(model, max_slots=16, max_len=512, **engine_kw)
    front = FrontDoor(ReplicaRouter([eng], registry=MetricRegistry()),
                      registry=MetricRegistry())
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)
               for n in PROMPT_LENS]
    t0 = time.perf_counter()
    handles = [front.submit(p, NEW_TOKENS) for p in prompts]
    front.run_until_idle()
    wall = time.perf_counter() - t0
    for h in handles:
        _check(h.req.finish_reason == "length"
               and len(h.req.output_ids) == NEW_TOKENS,
               f"request {h.rid} did not complete: finish_reason="
               f"{h.req.finish_reason!r}, {len(h.req.output_ids)} tokens")
    print(f"server: {len(handles)} requests x {NEW_TOKENS} tokens in "
          f"{wall:.1f}s (compiles included); programs "
          f"{eng.trace_counts}", flush=True)
    _check(eng.trace_counts["decode"] == 1,
           f"expected one decode program, traced "
           f"{eng.trace_counts['decode']}")
    return eng, prompts, [list(h.req.output_ids) for h in handles]


def phase_server(rehearse, dev):
    import paddle_tpu as paddle
    t0 = time.perf_counter()
    model, cfg = _server_model(rehearse)
    n = sum(int(p.size) for p in model.parameters())
    print(f"server: built Llama {n / 1e9:.3f}B bf16 in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    _, prompts, outs = _serve(model, cfg)
    for i in (0, 5):    # the identity the README claims
        p = prompts[i]
        ref = model.generate(paddle.to_tensor(p[None]),
                             max_new_tokens=NEW_TOKENS
                             ).numpy()[0, len(p):].tolist()
        _check(outs[i] == ref,
               f"request {i} (prompt {len(p)}): engine tokens {outs[i]} "
               f"!= model.generate() {ref}")
    print(f"server: greedy tokens of requests 0 and 5 equal "
          f"model.generate(); device memory {_memory()}")
    print("PASS server", flush=True)


# -- four chips -----------------------------------------------------------

def phase_mesh_trainer(rehearse, dev):
    """GPTSpmdTrainer on fsdp=2 x model=2 against the same seed and
    batch on a one-device mesh (the first of the four chips), under one
    recipe whose math is the same on both meshes."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh
    if rehearse:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128, dtype=jnp.float32)
        batch = 4
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048,
                        num_layers=MESH_TRAINER_LAYERS, num_heads=16,
                        max_seq_len=1024, dtype=jnp.bfloat16)
        batch = 6
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (batch, cfg.max_seq_len)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)

    def run(mesh):
        # the Pallas quantize and AdamW paths are single-device by
        # design: off on both sides, f32 masters (no stochastic
        # rounding, whose random bits depend on the sharding)
        tr = GPTSpmdTrainer(cfg, mesh, microbatches=1, seed=0,
                            remat="save_main", ce_chunks=1,
                            quant8=False, fused_optimizer=False,
                            moment8=False)
        t0 = time.perf_counter()
        losses = [float(jax.device_get(tr.train_step(ids, labels)))
                  for _ in range(3)]
        spread = {k: len(v.sharding.device_set)
                  for k, v in (("wte", tr.params["wte"]),
                               ("wqkv", tr.params["blocks"]["wqkv"]))}
        mem = _memory()
        print(f"mesh-trainer: mesh {dict(mesh.shape)} losses {losses} "
              f"in {time.perf_counter() - t0:.1f}s; devices per param "
              f"{spread}; memory {mem}", flush=True)
        del tr
        gc.collect()
        return losses, spread, mem

    l4, spread4, mem4 = run(build_mesh(n_devices=4, fsdp=2, model=2))
    _check(all(n == 4 for n in spread4.values()),
           f"sharded params do not span four devices: {spread4}")
    _check(all(m["peak_gib"] > 0 for m in mem4) or rehearse,
           f"a device held nothing during the sharded steps: {mem4}")
    l1, spread1, _ = run(build_mesh(n_devices=1))
    _check(all(n == 1 for n in spread1.values()),
           f"the one-device side spans more than one device: {spread1}")
    _check(all(np.isfinite(l4 + l1)), f"loss not finite: {l4} {l1}")
    rel = [abs(a - b) / abs(b) for a, b in zip(l4, l1)]
    print(f"mesh-trainer: relative loss difference per step {rel} "
          f"(tolerance {MESH_TRAINER_RTOL})")
    _check(max(rel) <= MESH_TRAINER_RTOL,
           f"sharded trainer off the one-chip run: {l4} vs {l1}")
    print("PASS mesh-trainer", flush=True)


def phase_mesh_server(rehearse, dev):
    """ServingEngine(mesh=...) with a `model` axis of 4 against the
    single-chip engine on the same 8 requests: identical greedy tokens."""
    import numpy as np
    from paddle_tpu.distributed import ProcessMesh
    model, cfg = _server_model(rehearse)
    eng4, _, out4 = _serve(
        model, cfg, mesh=ProcessMesh(np.arange(4), ["model"]))
    pools = {len(k.sharding.device_set) for k in eng4.cache.ks}
    params = {name: len(a.sharding.device_set)
              for name, a in eng4._params.items()}
    mem4 = _memory()
    print(f"mesh-server: KV pools span {sorted(pools)} devices; params "
          f"spanning four: {sum(n == 4 for n in params.values())} of "
          f"{len(params)}; memory {mem4}", flush=True)
    _check(pools == {4}, f"KV pools do not span four devices: {pools}")
    _check(all(n == 4 for n in params.values()),
           f"params not placed on the four-device mesh: "
           f"{ {k: n for k, n in params.items() if n != 4} }")
    _check(all(m["peak_gib"] > 0 for m in mem4) or rehearse,
           f"a device held nothing while serving: {mem4}")
    del eng4
    _, _, out1 = _serve(model, cfg)
    diff = [i for i, (a, b) in enumerate(zip(out4, out1)) if a != b]
    _check(not diff,
           f"tensor-parallel tokens differ from the single-chip engine "
           f"on requests {diff}: {[(out4[i], out1[i]) for i in diff]}")
    print("mesh-server: greedy tokens identical on all 8 requests")
    print("PASS mesh-server", flush=True)


PHASES = {"trainer": phase_trainer, "server": phase_server,
          "mesh-trainer": phase_mesh_trainer,
          "mesh-server": phase_mesh_server}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend; no result line")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)     # a child of this script
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.phase:      # a child: the one process that touches JAX
        dev = _device(args.rehearse, args.chips)
        _cache()
        PHASES[args.phase](args.rehearse, dev)
        with open(args.out, "w") as f:
            json.dump(dev, f)
        return

    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count=4")
    names = ("trainer", "server") if args.chips == 1 \
        else ("mesh-trainer", "mesh-server")
    t_all = time.perf_counter()
    dev = None
    with tempfile.TemporaryDirectory() as tmp:
        for name in names:
            out = os.path.join(tmp, name + ".json")
            t0 = time.perf_counter()
            rc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--phase", name, "--out", out,
                 "--chips", str(args.chips)]
                + (["--rehearse"] if args.rehearse else []),
                env=env, cwd=HERE).returncode
            print(f"phase {name}: exit {rc} after "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
            if rc != 0:
                sys.exit(f"chip_smoke: phase {name} failed (exit {rc})")
            with open(out) as f:
                dev = json.load(f)
    print(f"all phases passed in {time.perf_counter() - t_all:.1f}s")
    if args.rehearse:
        print("REHEARSAL passed on the CPU backend: paths and arguments "
              "only, not a chip result")
        return
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
