"""Model builder for configurations whose ``family`` is ``afmoe``: a decoder
with gated grouped-query attention over sliding windows and full contexts
mixed, sandwich norms and a dropless expert layer
(``paddle_tpu.models.afmoe``), served through ``ServingEngine`` as one
chip's share of an expert-parallel group. The configuration's file keeps
the source's own key names (the HuggingFace ``config.json`` of Arcee's
Trinity models); what is run differently from the source is under the keys
``reduced`` lists. ``layers_run`` names the published layers that are run:
each keeps its published kind (``layer_types``) and is dense where the
source's is (below ``num_dense_layers``).

A family module offers: ``setup_parallel``, ``build_model``,
``reference_weights``, ``make_train_step``, ``make_engine``,
``train_flops_per_token``, ``reference`` (the plain reference module).
Serving only: the training entries refuse.
"""
import math

import paddle_tpu as paddle
from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM

from ..reference import afmoe_ref as reference  # noqa: F401 (family API)

# the source's keys that fix a size or an equation of what is run
_MODEL_KEYS = {
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "sliding_window", "layer_types", "num_dense_layers", "intermediate_size",
    "moe_intermediate_size", "num_experts", "num_experts_per_tok",
    "num_shared_experts", "route_norm", "route_scale", "rms_norm_eps",
    "rope_theta", "mup_enabled", "vocab_size"}
# the source's keys that are checked and otherwise only recorded: what
# they say is the one thing this family builds
_FIXED = {"hidden_act": "silu", "n_group": 1, "topk_group": 1,
          "num_expert_groups": 1, "num_limited_groups": 1,
          "score_func": "sigmoid", "rope_scaling": None,
          "tie_word_embeddings": False}
_RECORDED = {"global_attn_every_n_layers", "load_balance_coeff",
             "max_position_embeddings", "model_type", "num_hidden_layers",
             "use_grouped_mm"}

CONFIG_KEYS = {
    "": _MODEL_KEYS | set(_FIXED) | _RECORDED | {
        "name", "family", "num_layers", "layers_run", "layer_types_run",
        "experts_held", "max_seq_len", "engine",
        "source", "published", "reduced", "reduced_why", "assumed",
        "deployment", "parameters"},
    "engine": {"page_size", "num_pages", "window_pages", "max_slots",
               "prefill_chunk", "prefix_cache", "max_queue", "token_pads",
               "emit_logits", "why"},
}


def setup_parallel(cfg):
    return None


def layers_run(cfg):
    """-> (kind of each layer that is run, how many of them are dense):
    the published layers ``layers_run`` names, in order, dense ones first
    as in the source."""
    run = list(cfg["layers_run"])
    if len(run) != cfg["num_layers"] or run != sorted(set(run)) \
            or run[-1] >= len(cfg["layer_types"]):
        raise ValueError(f"{cfg['name']}: layers_run {run} does not name "
                         f"{cfg['num_layers']} of the source's "
                         f"{len(cfg['layer_types'])} layers, in order")
    kinds = [cfg["layer_types"][i] for i in run]
    if kinds != cfg["layer_types_run"]:
        raise ValueError(f"{cfg['name']}: layer_types_run says "
                         f"{cfg['layer_types_run']}; the source's kinds of "
                         f"layers {run} are {kinds}")
    return kinds, sum(i < cfg["num_dense_layers"] for i in run)


def model_config(cfg, dtype="bfloat16", **kw):
    for key, want in _FIXED.items():
        if cfg[key] != want:
            raise ValueError(f"{cfg['name']}: {key} = {cfg[key]!r}; this "
                             f"family builds {want!r} only")
    kinds, dense = layers_run(cfg)
    return AfmoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], layer_types=kinds,
        num_dense_layers=dense, intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
        experts_held=cfg["experts_held"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], mup_enabled=cfg["mup_enabled"],
        max_seq_len=cfg["max_seq_len"], dtype=dtype, **kw)


def build_model(cfg, seed, hcg=None):
    """Random weights from the seed, each leaf drawn on the device and
    held in bfloat16 from the start: 4.3 B parameters never stand in
    float32."""
    paddle.seed(int(seed) % (2 ** 31))
    model = AfmoeForCausalLM(model_config(cfg))
    model.eval()
    return model


def reference_weights(model):
    """The model's arrays under the names ``afmoe_ref`` wants. No copy:
    the reference reads the very arrays the program computes with."""
    def a(p):
        return p._data

    c = model.config
    layers = []
    for b in model.layers:
        at, mlp = b.attn, b.mlp
        if b.is_moe:
            ffn = {"gate_w": a(mlp.gate_weight), "gate_b": a(mlp.gate_bias),
                   "w13": a(mlp.w13), "w2": a(mlp.w2)}
            if mlp.shared_width:
                ffn.update(shared_w13=a(mlp.shared_w13),
                           shared_w2=a(mlp.shared_w2))
        else:
            ffn = {"w13": a(mlp.w13), "w2": a(mlp.w2)}
        layers.append({
            "input_norm": a(b.input_norm.weight),
            "post_attention_norm": a(b.post_attention_norm.weight),
            "pre_mlp_norm": a(b.pre_mlp_norm.weight),
            "post_mlp_norm": a(b.post_mlp_norm.weight),
            "qkvg": a(at.qkvg_proj), "q_norm": a(at.q_norm),
            "k_norm": a(at.k_norm), "o": a(at.o_proj), "ffn": ffn})
    return {
        "cfg": {"num_heads": c.num_heads, "num_kv_heads": c.num_kv_heads,
                "head_dim": c.head_dim,
                "windows": tuple(c.window(i) for i in range(c.num_layers)),
                "num_experts_per_tok": c.num_experts_per_tok,
                "route_norm": c.route_norm, "route_scale": c.route_scale,
                "experts_held": c.experts_held or (0, c.num_experts),
                "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
                "embed_scale": math.sqrt(c.hidden_size)
                if c.mup_enabled else 1.0},
        "embed": a(model.embed), "norm": a(model.norm.weight),
        "lm_head": a(model.lm_head), "layers": layers}


def make_train_step(model, cfg, hcg=None):
    raise NotImplementedError(
        "the afmoe family is benchmarked on the serving path only: at 16 "
        "bytes a parameter its smallest honest cut fits no chip")


def train_flops_per_token(cfg):
    raise NotImplementedError("no training cell runs the afmoe family")


def make_engine(model, cfg):
    """The engine as a deployment builds it: ragged path, attention
    backend left to the start-up gate, the full layers' pages and the
    window layers' as two groups of their own sizes."""
    from paddle_tpu.serving import ServingEngine
    e = cfg["engine"]
    model.eval()
    pages = {spec.group: int(e["num_pages"] if spec.window is None
                             else e["window_pages"])
             for spec in model.cache_spec()}
    return ServingEngine(model, page_size=int(e["page_size"]),
                         num_pages=pages,
                         max_slots=int(e["max_slots"]),
                         prefill_chunk=int(e["prefill_chunk"]),
                         prefix_cache=bool(e.get("prefix_cache", True)),
                         max_queue=int(e.get("max_queue", 256)),
                         token_pads=e.get("token_pads"),
                         emit_logits=bool(e.get("emit_logits", False)))
