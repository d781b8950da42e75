"""The system under test, built the way a user builds it: the program's
``Lfm2MoeForCausalLM`` at the configuration's widths, abstract
(``meta_init``), cast to bf16 and given the benchmark's own weights from
the seed. The one place the benchmark touches this model's code.
"""

from __future__ import annotations


def build_model(ctx, layers: int):
    import jax

    import paddle_tpu as pt
    from paddle_tpu.core import meta
    from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM

    weights = ctx.part("weights")
    w = ctx.widths()
    if layers != len(w["layer_types"]):
        raise ValueError("num_hidden_layers is not layer_types' length")
    cfg = Lfm2MoeConfig(
        vocab_size=w["vocab_size"], hidden_size=w["hidden_size"],
        intermediate_size=w["intermediate_size"],
        moe_intermediate_size=w["moe_intermediate_size"],
        layer_types=tuple(w["layer_types"]),
        num_dense_layers=w["num_dense_layers"],
        num_attention_heads=w["num_attention_heads"],
        num_key_value_heads=w["num_key_value_heads"],
        conv_L_cache=w["conv_L_cache"], norm_eps=w["norm_eps"],
        rope_theta=w["rope_theta"], num_experts=w["router_num_experts"],
        held_experts=(w["held_experts_first"], w["num_experts"]),
        num_experts_per_tok=w["num_experts_per_tok"],
        routed_scaling_factor=w["routed_scaling_factor"],
        initializer_range=weights.STD, use_flash_attention=True)
    if cfg.head_dim != w["head_dim"]:
        raise ValueError("head_dim is not hidden_size / heads")
    with meta.meta_init():
        model = Lfm2MoeForCausalLM(cfg)
    model.to(pt.bfloat16)
    values = weights.make_all(w, ctx.seed, layers)
    params = dict(model.named_parameters())
    if set(params) != set(values):
        raise RuntimeError(f"parameter names differ: "
                           f"{set(params) ^ set(values)}")
    for name, p in params.items():
        if tuple(p.value.shape) != values[name].shape:
            raise RuntimeError(f"shape of {name} differs")
        p.value = values[name]
    jax.block_until_ready(values)
    pub = ctx.config["published"]
    ctx.say(f"model: hidden {cfg.hidden_size}, layers "
            f"{'/'.join(t[:4] for t in cfg.layer_types)} (published "
            f"{pub['num_hidden_layers']}), {cfg.num_dense_layers} dense of "
            f"{cfg.intermediate_size}; experts {cfg.held_experts[1]} held of "
            f"{cfg.num_experts}, top-{cfg.num_experts_per_tok}, width "
            f"{cfg.moe_intermediate_size}; attention "
            f"{cfg.num_attention_heads}/{cfg.num_key_value_heads} of "
            f"{cfg.head_dim}, q/k norm, RoPE {cfg.rope_theta:g}; taps "
            f"{cfg.conv_L_cache}; vocab {cfg.vocab_size} (published "
            f"{pub['vocab_size']}), tied, bf16; "
            f"{weights.n_params(w, layers) / 1e6:.1f}M parameters")
    return model
