"""Builder ``solar_engine``: the Solar-Open2 share (3 KDA layers to 1
gated GQA layer, a sparse FFN in every layer with the experts this chip
holds) in the serving engine's cache manager, K/V pages and state rows
side by side, behind the router and the front door, unchanged.

As ``llama_engine``: the module is the program's, assembled a layer at a
time in the serving dtype (``nn.Layer`` builds every parameter in
float32 first: a layer's 40 experts are 2.5 GB before the cast), then
every matrix is redrawn on the device from ``--seed``
(``seeding.redraw``: the routers, the experts' stacks and the
convolutions' taps too, each at its initial spread; the vectors
``A_log``, ``dt_bias`` and the norm gains stay). The engine learns from
the model what each layer keeps; its arguments come from the
configuration file.
"""
from __future__ import annotations

import dataclasses

from .. import reference_solar, seeding
from ..setup_marks import mark
from .llama_engine import REFERENCE_PAD, LlamaSystem

# the selftest's sizes (CPU, float32: the engine and the plain reference
# then agree to rounding); one period, 5 of 20 experts held, 4 a token
TINY = {
    "kind": "solar_engine", "dtype": "float32",
    "model": {"vocab_size": 512, "hidden_size": 64,
              "num_hidden_layers": 4, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "moe_intermediate_size": 32, "n_routed_experts": 5,
              "experts_published": 20, "first_expert": 5,
              "n_shared_experts": 1, "num_experts_per_tok": 4,
              "norm_topk_prob": True, "routed_scaling_factor": 1,
              "rms_norm_eps": 1e-5, "max_position_embeddings": 512,
              "gqa_layers": [0, 4], "kda_allow_neg_eigval": True,
              "tie_word_embeddings": False, "kda_rank": 8,
              "linear_attn_config": {"short_conv_kernel_size": 4,
                                     "head_dim": 16, "num_heads": 4}},
    "chips": 1, "mesh": {},
    "engine": {"max_slots": 4, "max_len": 128, "page_size": 16,
               "prefix_sharing": False}}


class SolarSystem(LlamaSystem):
    """What ``serving_loop`` uses of a system, for this model.
    ``layer_params`` is what a token passes through here: the mixers,
    the routers, the shared experts and, of the routed experts, ``8 x
    held / published`` of one a layer (one of a token's 8 is held on
    average), so that ``mfu_bf16_pct.serve`` counts work done here."""

    def __init__(self, model, cfg, weights, engine, front):
        self.model, self.cfg = model, cfg
        self.weights = weights
        self.engine, self.front = engine, front
        self.vocab = int(cfg.vocab_size)
        self.chips = 1
        self.head_params = int(weights["lm_head.weight"].size)
        routed = sum(int(a.size) for name, a in weights.items()
                     if ".mlp.experts_" in name)
        a_token = cfg.num_experts_per_tok / cfg.router_width
        self.layer_params = int(
            sum(int(a.size) for a in weights.values())
            - int(weights["solar.embed_tokens.weight"].size)
            - self.head_params - routed + routed * a_token)

    def served_gaps(self, prompt, outputs, control: bool = False):
        c = self.cfg
        return reference_solar.served_gaps(
            self.weights, prompt, outputs, pad_to=REFERENCE_PAD,
            control=control, layers=c.num_hidden_layers,
            gqa_layers=c.gqa_layers, heads=c.num_attention_heads,
            kv_heads=c.num_key_value_heads,
            linear_heads=c.linear_num_heads,
            top_k=c.num_experts_per_tok, first_expert=c.first_expert,
            eps=c.rms_norm_eps, neg_eigval=c.kda_allow_neg_eigval)


def _assemble(cfg, dtype: str):
    """The program's ``SolarOpen2ForCausalLM`` at ``cfg`` in ``dtype``,
    never holding more than the vocabulary's two matrices and one layer
    in float32 (``llama_engine._assemble`` says why)."""
    from paddle_tpu.models.solar import (SolarDecoderLayer,
                                         SolarOpen2ForCausalLM)
    model = SolarOpen2ForCausalLM(
        dataclasses.replace(cfg, num_hidden_layers=1))
    model.to(dtype=dtype)
    for i in range(1, cfg.num_hidden_layers):
        layer = SolarDecoderLayer(cfg, cfg.is_gqa(i))
        layer.to(dtype=dtype)
        model.solar.layers.append(layer)
    model.config = model.solar.config = cfg
    return model


def build(config: dict, seed: int) -> SolarSystem:
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.solar import SolarOpen2Config
    from paddle_tpu.observability import MetricRegistry
    from paddle_tpu.serving import FrontDoor, ReplicaRouter, ServingEngine
    cfg = SolarOpen2Config.from_dict(config["model"])
    if cfg.routed_scaling_factor != 1 or not cfg.is_gqa(0):
        raise SystemExit("chipbench: the plain reference has "
                         "routed_scaling_factor 1 and layer 0 GQA")
    if int(config.get("chips", 1)) != 1:
        raise SystemExit("chipbench: state rows are one chip's yet")
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
    return SolarSystem(model, cfg, drawn, engine, front)
