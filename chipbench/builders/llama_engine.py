"""Builder ``llama_engine``: a Llama-style model in the paged serving
engine behind the router and the front door, unchanged.

The module is the program's (assembled a layer at a time in the serving
dtype: see ``_assemble``), then every matrix is redrawn on the device from
``--seed`` in one jitted call (``seeding.redraw``). Engine arguments and
the mesh come from the configuration file: ``"mesh": {"model": 4}`` with
``"chips": 4`` is a tensor-parallel engine, nothing else changes.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .. import reference, seeding
from ..setup_marks import mark

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings")


class LlamaSystem:
    def __init__(self, model, cfg, engine, front, chips: int):
        self.model, self.cfg = model, cfg
        self.engine, self.front = engine, front
        self.vocab = int(cfg.vocab_size)
        self.chips = int(chips)

    def programs(self) -> int:
        """Programs the engine has traced so far, of every kind."""
        return sum(sum(v.values()) if isinstance(v, dict) else int(v)
                   for v in self.engine.trace_counts.values())

    def logit_deficits(self, prompt, outputs) -> list:
        """For each generated token: how far the plain reference's logit
        of the token the engine chose lies under the reference's largest
        logit at that position, in standard deviations of that
        position's logits (0 = the reference agrees)."""
        ids = np.concatenate([np.asarray(prompt, np.int64),
                              np.asarray(outputs, np.int64)])
        c, n = self.cfg, len(outputs)
        params, _ = self.model.raw_state()
        # logits at position p predict token p + 1: the rows that
        # predicted the n outputs are the n before the last
        logits = reference.llama_logits(
            params, ids[:-1], layers=c.num_hidden_layers,
            heads=c.num_attention_heads, kv_heads=c.kv_heads,
            eps=c.rms_norm_eps, theta=c.rope_theta, last=n)
        chosen = logits[np.arange(n), np.asarray(outputs)]
        return list((logits.max(axis=1) - chosen) / logits.std(axis=1))


def _assemble(cfg, dtype: str):
    """The program's ``LlamaForCausalLM`` at ``cfg``, in ``dtype``.

    ``nn.Layer`` builds every parameter in float32 on the device,
    whatever the default dtype, and is cast afterwards: at Mistral-7B
    widths a half-depth model is 15 GB in float32 and cannot be built on
    a 16 GB chip (PERF.md, Open questions). So the module is assembled
    from the program's own classes a layer at a time, each cast as it is
    made, and never holds more than one layer in float32."""
    from paddle_tpu.models.llama import (LlamaDecoderLayer,
                                         LlamaForCausalLM)
    model = LlamaForCausalLM(
        dataclasses.replace(cfg, num_hidden_layers=1))
    model.to(dtype=dtype)
    layers = model.llama.layers
    attn = layers[0].self_attn
    for _ in range(cfg.num_hidden_layers - 1):
        layer = LlamaDecoderLayer(cfg, False, (attn._cos, attn._sin))
        layer.to(dtype=dtype)
        layers.append(layer)
    model.config = model.llama.config = cfg
    return model


def build(config: dict, seed: int) -> LlamaSystem:
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.observability import MetricRegistry
    from paddle_tpu.serving import FrontDoor, ReplicaRouter, ServingEngine
    m = config["model"]
    cfg = LlamaConfig(**{k: m[k] for k in MODEL_KEYS})
    if cfg.head_dim != m["head_dim"]:
        raise SystemExit(
            f"chipbench: head size {cfg.head_dim} is not the "
            f"configuration's {m['head_dim']}")
    paddle.seed(0)
    model = _assemble(cfg, config["dtype"])
    model.eval()
    mark("model_built")
    params, _ = model.raw_state()
    drawn = seeding.redraw(params, seed)
    for name, p in model.named_parameters():
        p._data = drawn[name]
    import jax
    jax.block_until_ready(drawn)
    mark("weights_from_seed")
    chips = int(config.get("chips", 1))
    kw = dict(config["engine"])
    if chips > 1:
        from paddle_tpu.distributed import ProcessMesh
        (axis, size), = config["mesh"].items()
        if size != chips:
            raise SystemExit("chipbench: the mesh axis must span the "
                             "cell's chips")
        kw["mesh"] = ProcessMesh(np.arange(chips), [axis])
    engine = ServingEngine(model, registry=MetricRegistry(), **kw)
    front_registry = MetricRegistry()
    front = FrontDoor(ReplicaRouter([engine], registry=front_registry),
                      registry=front_registry)
    mark("engine_built")
    return LlamaSystem(model, cfg, engine, front, chips)
