"""Builder ``gpt_trainer``: ``GPTSpmdTrainer`` at the configuration's shapes.

``"recipe": "bench.build_flagship"`` takes the trainer the program's
own ``bench.build_flagship()`` builds — remat, int8, unroll and moments
stay the program's to change and to be judged on — and then CHECKS that
its widths, depth, batch, sequence and mesh equal the configuration
file: the shapes are the yardstick's. ``"recipe": "explicit"`` builds
the trainer from the file's ``trainer`` arguments on the file's mesh
axes (a four-chip cell needs only ``chips`` and ``mesh``).
"""
from __future__ import annotations

import sys

from .. import manifest, reference, seeding
from ..setup_marks import mark

# the selftest's sizes: the shapes bench.build_flagship() builds on the
# CPU backend
TINY = {"kind": "gpt_trainer", "recipe": "bench.build_flagship",
        "model": {"vocab_size": 1024, "hidden_size": 128, "num_layers": 2,
                  "num_heads": 4, "head_dim": 32, "ffn_mult": 4,
                  "max_seq_len": 128},
        "batch": 4, "chips": 1, "mesh": {}}

SHAPE_KEYS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
              "max_seq_len", "ffn_mult")


class GPTSystem:
    def __init__(self, trainer, batch: int, chips: int):
        self.trainer = trainer
        self.batch = int(batch)
        self.seq = int(trainer.cfg.max_seq_len)
        self.vocab = int(trainer.cfg.vocab_size)
        self.chips = int(chips)
        self.n_params = int(trainer.n_params())

    def step(self, ids, labels):
        """Dispatch one train step; the loss stays on the device."""
        return self.trainer.train_step(ids, labels)

    def reference_loss(self, ids, labels) -> float:
        return reference.gpt_loss(self.trainer.params, ids, labels,
                                  self.trainer.cfg.num_heads)

    def programs(self) -> int:
        """Step programs traced so far (1 after warm-up)."""
        return int(self.trainer.build_step()._cache_size())


def _dtype(name):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def build(config: dict, seed: int) -> GPTSystem:
    import jax
    from paddle_tpu.models.gpt import (GPTConfig, GPTSpmdTrainer,
                                       build_mesh)
    want = config["model"]
    chips = int(config.get("chips", 1))
    axes = {k: int(v) for k, v in config.get("mesh", {}).items()}
    if config["recipe"] == "bench.build_flagship":
        if manifest.ROOT not in sys.path:
            sys.path.insert(0, manifest.ROOT)
        import bench
        trainer, _, _, info = bench.build_flagship()
        batch = info["batch"]
    elif config["recipe"] == "explicit":
        cfg = GPTConfig(dtype=_dtype(config["dtype"]),
                        **{k: want[k] for k in SHAPE_KEYS})
        kw = dict(config["trainer"])
        for k in ("moment_dtype", "master_dtype"):
            if k in kw:
                kw[k] = _dtype(kw[k])
        trainer = GPTSpmdTrainer(
            cfg, build_mesh(n_devices=chips, **axes), **kw)
        batch = config["batch"]
    else:
        raise SystemExit(f"chipbench: unknown recipe "
                         f"{config['recipe']!r}")
    jax.block_until_ready(trainer.params)
    mark("trainer_built")
    got = {k: getattr(trainer.cfg, k) for k in SHAPE_KEYS}
    got["head_dim"] = trainer.cfg.head_dim
    got["batch"] = batch
    expect = dict({k: want[k] for k in SHAPE_KEYS},
                  head_dim=want["head_dim"], batch=config["batch"])
    if got != expect:
        raise SystemExit(
            f"chipbench: the trainer's shapes {got} are not the "
            f"configuration's {expect}")
    mesh = {k: v for k, v in trainer.mesh.shape.items() if v != 1}
    if mesh != {k: v for k, v in axes.items() if v != 1} \
            or trainer.mesh.devices.size != chips:
        raise SystemExit(
            f"chipbench: the trainer's mesh {dict(trainer.mesh.shape)} "
            f"is not the configuration's {axes} on {chips} chip(s)")
    with jax.set_mesh(trainer.mesh):
        trainer.params = seeding.redraw(
            trainer.params, seed,
            out_shardings=jax.tree.map(lambda a: a.sharding,
                                       trainer.params))
    jax.block_until_ready(trainer.params)
    mark("weights_from_seed")
    return GPTSystem(trainer, batch, chips)
