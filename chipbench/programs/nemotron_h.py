"""The system under test, built the way a user builds it: the program's
``NemotronHForCausalLM`` at the configuration's widths, abstract
(``meta_init``), cast to bf16 and given the benchmark's own weights from
the seed. The one place the benchmark touches this model's code.
"""

from __future__ import annotations


def build_model(ctx, layers: int):
    import jax

    import paddle_tpu as pt
    from paddle_tpu.core import meta
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM

    weights = ctx.part("weights")
    w = ctx.widths()
    if layers != len(w["hybrid_override_pattern"]):
        raise ValueError("num_hidden_layers is not the pattern's length")
    cfg = NemotronHConfig(
        vocab_size=w["vocab_size"], hidden_size=w["hidden_size"],
        hybrid_override_pattern=w["hybrid_override_pattern"],
        num_attention_heads=w["num_attention_heads"],
        num_key_value_heads=w["num_key_value_heads"],
        head_dim=w["head_dim"], mamba_num_heads=w["mamba_num_heads"],
        mamba_head_dim=w["mamba_head_dim"],
        ssm_state_size=w["ssm_state_size"], n_groups=w["n_groups"],
        conv_kernel=w["conv_kernel"], chunk_size=w["chunk_size"],
        time_step_min=w["time_step_min"], time_step_max=w["time_step_max"],
        time_step_floor=w["time_step_floor"],
        n_routed_experts=w["router_num_experts"],
        held_experts=(w["held_experts_first"], w["n_routed_experts"]),
        num_experts_per_tok=w["num_experts_per_tok"],
        moe_intermediate_size=w["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=w[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=w["routed_scaling_factor"],
        mlp_hidden_act=w["mlp_hidden_act"],
        layer_norm_epsilon=w["layer_norm_epsilon"],
        initializer_range=weights.STD,
        use_flash_attention=True)
    with meta.meta_init():
        model = NemotronHForCausalLM(cfg)
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
    ctx.say(f"model: hidden {cfg.hidden_size}, blocks "
            f"{cfg.hybrid_override_pattern} (published "
            f"{pub['num_hidden_layers']}), Mamba-2 {cfg.mamba_num_heads} "
            f"heads of {cfg.mamba_head_dim}, state {cfg.ssm_state_size}; "
            f"experts {cfg.held_experts[1]} held of "
            f"{cfg.n_routed_experts}, top-{cfg.num_experts_per_tok}, "
            f"width {cfg.moe_intermediate_size}, shared "
            f"{cfg.moe_shared_expert_intermediate_size}; attention "
            f"{cfg.num_attention_heads}/{cfg.num_key_value_heads} of "
            f"{cfg.head_dim}; vocab {cfg.vocab_size} (published "
            f"{pub['vocab_size']}), bf16; "
            f"{weights.n_params(w, layers) / 1e6:.1f}M parameters")
    return model
