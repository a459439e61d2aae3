"""Builder ``brumby_engine``: a Brumby-style model (every attention a
degree-2 power-retention layer) in the serving engine's recurrent-state
cache, behind the router and the front door, unchanged.

As ``llama_engine``: the module is the program's, assembled a layer at a
time in the serving dtype (``nn.Layer`` builds every parameter in
float32 first: the embedding and the head of a 151936-word vocabulary
are 3.1 GB each before the cast, a layer 1.3 GB), then every matrix is
redrawn on the device from ``--seed`` (``seeding.redraw``; the gate
projection too, at its initial spread, so gates centre on 0.5 and a
chip run's memory is a few tokens long: the op's CPU tests hold long
memory to the reference). The engine learns from the model that it
keeps a state and no K/V; its arguments come from the configuration
file.
"""
from __future__ import annotations

import dataclasses

from .. import reference_brumby, seeding
from ..setup_marks import mark
from .llama_engine import MODEL_KEYS, REFERENCE_PAD, LlamaSystem

# the selftest's sizes (CPU, float32: the engine and the plain reference
# then agree to rounding); five query heads a KV head, as published
TINY = {
    "kind": "brumby_engine", "dtype": "float32",
    "model": {"vocab_size": 512, "hidden_size": 80,
              "intermediate_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 10, "num_key_value_heads": 2,
              "head_dim": 8, "max_position_embeddings": 512,
              "rms_norm_eps": 1e-6, "rope_theta": 1e6,
              "tie_word_embeddings": False},
    "chips": 1, "mesh": {},
    "engine": {"max_slots": 4, "max_len": 128, "kv_layout": "state"}}


class BrumbySystem(LlamaSystem):
    """What ``serving_loop`` uses of a system, for this model: the
    layers' and the head's parameters a token passes through, the
    engine's programs, and the plain reference's reading of what was
    served."""

    def __init__(self, model, cfg, weights, engine, front, chips: int):
        self.model, self.cfg = model, cfg
        self.weights = weights
        self.engine, self.front = engine, front
        self.vocab = int(cfg.vocab_size)
        self.chips = int(chips)
        self.head_params = int(weights["lm_head.weight"].size)
        self.layer_params = sum(int(a.size) for a in weights.values()) \
            - int(weights["brumby.embed_tokens.weight"].size) \
            - self.head_params

    def served_gaps(self, prompt, outputs, control: bool = False):
        c = self.cfg
        return reference_brumby.served_gaps(
            self.weights, prompt, outputs, pad_to=REFERENCE_PAD,
            control=control, layers=c.num_hidden_layers,
            heads=c.num_attention_heads, kv_heads=c.kv_heads,
            eps=c.rms_norm_eps, theta=c.rope_theta)


def _assemble(cfg, dtype: str):
    """The program's ``BrumbyForCausalLM`` at ``cfg`` in ``dtype``, never
    holding more than the vocabulary's two matrices and one layer in
    float32 (``llama_engine._assemble`` says why)."""
    from paddle_tpu.models.brumby import (BrumbyDecoderLayer,
                                          BrumbyForCausalLM)
    model = BrumbyForCausalLM(
        dataclasses.replace(cfg, num_hidden_layers=1))
    model.to(dtype=dtype)
    layers = model.brumby.layers
    mixer = layers[0].retention
    for _ in range(cfg.num_hidden_layers - 1):
        layer = BrumbyDecoderLayer(cfg, (mixer._cos, mixer._sin))
        layer.to(dtype=dtype)
        layers.append(layer)
    model.config = model.brumby.config = cfg
    return model


def build(config: dict, seed: int) -> BrumbySystem:
    import jax
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
    if int(config.get("chips", 1)) != 1:
        raise SystemExit("chipbench: a state cache is one chip's yet")
    paddle.seed(0)
    model = _assemble(cfg, config["dtype"])
    model.eval()
    mark("model_built")
    params, _ = model.raw_state()
    drawn = seeding.redraw(params, seed)
    for name, p in model.named_parameters():
        p._data = drawn[name]
    jax.block_until_ready(drawn)
    mark("weights_from_seed")
    engine = ServingEngine(model, registry=MetricRegistry(),
                           **config["engine"])
    front_registry = MetricRegistry()
    front = FrontDoor(ReplicaRouter([engine], registry=front_registry),
                      registry=front_registry)
    mark("engine_built")
    return BrumbySystem(model, cfg, drawn, engine, front, 1)
