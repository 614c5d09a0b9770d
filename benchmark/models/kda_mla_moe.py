"""Model builder for configurations whose ``family`` is ``kda_mla_moe``: a
decoder that mixes delta-rule linear-attention layers (a recurrent state a
request) with latent-attention layers (pages) over a group-limited expert
layer (``paddle_tpu.models.kda_mla_moe``), served through ``ServingEngine``
as one chip's share of an expert-parallel group. The configuration's file
keeps the source's own key names (the HuggingFace ``config.json`` of
inclusionAI's Ling-3.0 models); what is run differently from the source is
under the keys ``reduced`` lists. ``layers_run`` names the published layers
that are run: each keeps its published kind (layer ``l`` is latent
attention where ``(l + 1) % layer_group_size == 0``, else KDA) and is dense
where the source's is (below ``first_k_dense_replace``).

A family module offers: ``setup_parallel``, ``build_model``,
``reference_weights``, ``make_train_step``, ``make_engine``,
``train_flops_per_token``, ``reference`` (the plain reference module).
Serving only: the training entries refuse.
"""
import paddle_tpu as paddle
from paddle_tpu.models import KDAMLAMoEConfig, KDAMLAMoEForCausalLM

from ..reference import kda_mla_moe_ref as reference  # noqa: F401
from .mla_moe import setup_parallel  # noqa: F401

# the source's keys that fix a size or an equation of what is run
_MODEL_KEYS = {
    "hidden_size", "num_attention_heads", "head_dim", "layer_group_size",
    "short_conv_kernel_size", "kda_lower_bound", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "num_experts",
    "num_experts_per_tok", "moe_shared_expert_intermediate_size", "n_group",
    "topk_group", "first_k_dense_replace", "routed_scaling_factor",
    "rms_norm_eps", "rope_theta", "vocab_size"}
# the source's keys that are checked and otherwise only recorded: what
# they say is the one thing this family builds
_FIXED = {"q_lora_rank": None, "score_function": "sigmoid",
          "norm_topk_prob": True,
          "moe_router_enable_expert_bias": True, "use_qk_norm": True,
          "linear_silu": True, "kda_safe_gate": True, "no_kda_lora": True,
          "use_kda_lora": False, "group_norm_size": 1,
          "num_kv_heads_for_linear_attn": 0, "use_mla_nope": False,
          "use_nGPT": False, "value_norm": False, "up_proj_norm": False,
          "scale_router_input": False,
          "gated_attention_proj_granularity_type": "head_wise"}
_RECORDED = {"max_position_embeddings", "num_hidden_layers",
             "num_key_value_heads", "partial_rotary_factor", "rotary_dim",
             "mtp_use_kda", "expert_swiglu_limit_list",
             "share_expert_swiglu_limit_list", "image_patch_token",
             "video_patch_token", "image_start_token", "video_start_token"}

CONFIG_KEYS = {
    "": _MODEL_KEYS | set(_FIXED) | _RECORDED | {
        "name", "family", "num_layers", "layers_run", "layer_types_run",
        "experts_held", "max_seq_len", "engine",
        "source", "published", "reduced", "reduced_why", "assumed",
        "deployment", "parameters"},
    "engine": {"page_size", "num_pages", "max_slots", "prefill_chunk",
               "prefill_token_budget", "prefix_cache", "max_queue",
               "token_pads", "emit_logits", "why"},
}


def layers_run(cfg):
    """-> (kind of each layer that is run, how many of them are dense):
    the published layers ``layers_run`` names, in order, dense ones first
    as in the source. None of them may clamp its experts' activations
    (``*_swiglu_limit_list``): the clamp is not built."""
    run = list(cfg["layers_run"])
    if len(run) != cfg["num_layers"] or run != sorted(set(run)) \
            or run[-1] >= cfg["num_hidden_layers"]:
        raise ValueError(f"{cfg['name']}: layers_run {run} does not name "
                         f"{cfg['num_layers']} of the source's "
                         f"{cfg['num_hidden_layers']} layers, in order")
    kinds = ["mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"
             for i in run]
    if kinds != cfg["layer_types_run"]:
        raise ValueError(f"{cfg['name']}: layer_types_run says "
                         f"{cfg['layer_types_run']}; the source's kinds of "
                         f"layers {run} are {kinds}")
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        clamped = [i for i in run if cfg[key][i]]
        if clamped:
            raise ValueError(f"{cfg['name']}: {key} is not 0 in layers "
                             f"{clamped}; this family builds no clamp")
    return kinds, sum(i < cfg["first_k_dense_replace"] for i in run)


def model_config(cfg, dtype="bfloat16", **kw):
    for key, want in _FIXED.items():
        if cfg[key] != want:
            raise ValueError(f"{cfg['name']}: {key} = {cfg[key]!r}; this "
                             f"family builds {want!r} only")
    if cfg["rotary_dim"] != cfg["qk_rope_head_dim"]:
        raise ValueError(f"{cfg['name']}: rotary_dim {cfg['rotary_dim']} "
                         "is not the decoupled rotary key's width")
    kinds, dense = layers_run(cfg)
    shared = cfg["moe_shared_expert_intermediate_size"]
    if shared % cfg["moe_intermediate_size"]:
        raise ValueError(f"{cfg['name']}: the shared expert's width "
                         f"{shared} is no multiple of an expert's")
    return KDAMLAMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], layer_kinds=kinds,
        conv_taps=cfg["short_conv_kernel_size"],
        kda_lower_bound=cfg["kda_lower_bound"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=shared // cfg["moe_intermediate_size"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        num_dense_layers=dense,
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=True, experts_held=cfg["experts_held"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], max_seq_len=cfg["max_seq_len"],
        dtype=dtype,
        # whole lane tiles a row: the page DMA of the latent kernel
        cache_row_align=128, **kw)


def build_model(cfg, seed, hcg=None):
    """Random weights from the seed, each leaf drawn on the device and
    held in bfloat16 from the start."""
    paddle.seed(int(seed) % (2 ** 31))
    model = KDAMLAMoEForCausalLM(model_config(cfg))
    model.eval()
    return model


def reference_weights(model):
    """The model's arrays under the names ``kda_mla_moe_ref`` wants. No
    copy: the reference reads the very arrays the program computes with."""
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
        if b.kind == "kda":
            mix = {"qkv": a(at.qkv_proj), "conv_w": a(at.conv_weight),
                   "f": a(at.f_proj), "dt_bias": a(at.dt_bias),
                   "a_log": a(at.a_log), "b": a(at.b_proj),
                   "g": a(at.g_proj), "o_norm": a(at.o_norm)}
        else:
            mix = {"q": a(at.q_proj), "kv_a": a(at.kv_a_proj),
                   "kv_a_norm": a(at.kv_a_norm), "kv_b": a(at.kv_b_proj),
                   "gate": a(at.gate_proj)}
        layers.append(dict(mix, kind=b.kind,
                           input_norm=a(b.input_norm.weight),
                           post_norm=a(b.post_norm.weight),
                           o=a(at.o_proj), ffn=ffn))
    return {
        "cfg": {"num_heads": c.num_heads, "head_dim": c.head_dim,
                "conv_taps": c.conv_taps,
                "kda_lower_bound": c.kda_lower_bound,
                "kv_lora_rank": c.kv_lora_rank,
                "qk_nope_head_dim": c.qk_nope_head_dim,
                "qk_rope_head_dim": c.qk_rope_head_dim,
                "v_head_dim": c.v_head_dim,
                "num_experts_per_tok": c.num_experts_per_tok,
                "n_group": c.n_group, "topk_group": c.topk_group,
                "routed_scaling_factor": c.routed_scaling_factor,
                "norm_topk_prob": c.norm_topk_prob,
                "experts_held": c.experts_held
                or (0, c.n_routed_experts),
                "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta},
        "embed": a(model.embed), "norm": a(model.norm.weight),
        "lm_head": a(model.lm_head), "layers": layers}


def make_engine(model, cfg):
    """The engine as a deployment builds it: ragged path, both kernels
    left to their start-up gates. ``prefill_token_budget`` is the prompt
    tokens a round may carry beside its decode rows (whole chunks of
    ``prefill_chunk``, one a prefilling request)."""
    from paddle_tpu.serving import ServingEngine
    e = cfg["engine"]
    model.eval()
    return ServingEngine(model, page_size=int(e["page_size"]),
                         num_pages=int(e["num_pages"]),
                         max_slots=int(e["max_slots"]),
                         prefill_chunk=int(e["prefill_chunk"]),
                         prefill_token_budget=int(e["prefill_token_budget"]),
                         prefix_cache=bool(e["prefix_cache"]),
                         max_queue=int(e["max_queue"]),
                         token_pads=e["token_pads"],
                         emit_logits=bool(e["emit_logits"]))


def make_train_step(model, cfg, hcg=None):
    raise NotImplementedError(
        "the kda_mla_moe family is benchmarked on the serving path only: "
        "the recurrence has no backward here, and at 16 bytes a parameter "
        "its smallest honest cut fits no chip")


def train_flops_per_token(cfg):
    raise NotImplementedError("no training cell runs the kda_mla_moe family")
