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


# the selftest's sizes (CPU, float32: the engine and the plain reference
# then agree to rounding)
TINY = {
    "kind": "llama_engine", "dtype": "float32",
    "model": {"vocab_size": 512, "hidden_size": 64,
              "intermediate_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "max_position_embeddings": 512,
              "rms_norm_eps": 1e-5, "rope_theta": 1e6,
              "tie_word_embeddings": False},
    "chips": 1, "mesh": {},
    "engine": {"max_slots": 4, "max_len": 128, "kv_layout": "paged",
               "page_size": 16}}

# prompt + outputs of a request are padded to a multiple of this for the
# reference, so that one run compiles one or two programs for it
REFERENCE_PAD = 512


class LlamaSystem:
    def __init__(self, model, cfg, weights, engine, front, chips: int):
        self.model, self.cfg = model, cfg
        self.weights = weights      # name -> array, drawn by seeding.redraw
        self.engine, self.front = engine, front
        self.vocab = int(cfg.vocab_size)
        self.chips = int(chips)
        # parameters a token's hidden state is multiplied by in the
        # layers, and in the head (the embedding is a row read)
        self.head_params = int(weights["lm_head.weight"].size)
        self.layer_params = sum(int(a.size) for a in weights.values()) \
            - int(weights["llama.embed_tokens.weight"].size) \
            - self.head_params

    def programs(self) -> int:
        """Programs the engine has traced so far, of every kind."""
        return sum(sum(v.values()) if isinstance(v, dict) else int(v)
                   for v in self.engine.trace_counts.values())

    def free(self) -> None:
        """Drop the engine with its cache pool and the front door; the
        benchmark's weights stay for the reference."""
        import gc
        self.engine = self.front = None
        gc.collect()

    def served_gaps(self, prompt, outputs, control: bool = False):
        """``reference.llama_served_gaps`` at this system's sizes."""
        c = self.cfg
        return reference.llama_served_gaps(
            self.weights, prompt, outputs, pad_to=REFERENCE_PAD,
            control=control, layers=c.num_hidden_layers,
            heads=c.num_attention_heads, kv_heads=c.kv_heads,
            eps=c.rms_norm_eps, theta=c.rope_theta)


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
    return LlamaSystem(model, cfg, drawn, engine, front, chips)
