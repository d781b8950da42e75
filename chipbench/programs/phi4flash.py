"""The system under test, built the way a user builds it: the program's
``Phi4FlashForCausalLM`` at the configuration's widths, abstract
(``meta_init``), cast to bf16 and given the benchmark's own weights from
the seed. The one place the benchmark touches this model's code.
"""

from __future__ import annotations


def build_model(ctx, layers: int):
    import jax

    import paddle_tpu as pt
    from paddle_tpu.core import meta
    from paddle_tpu.models import Phi4FlashConfig, Phi4FlashForCausalLM

    weights = ctx.part("weights")
    w = ctx.widths()
    if layers != len(w["published_layer_indices"]):
        raise ValueError("num_hidden_layers is not the number of "
                         "published_layer_indices")
    cfg = Phi4FlashConfig(
        vocab_size=w["vocab_size"], hidden_size=w["hidden_size"],
        intermediate_size=w["intermediate_size"],
        num_hidden_layers=w["published_num_hidden_layers"],
        num_attention_heads=w["num_attention_heads"],
        num_key_value_heads=w["num_key_value_heads"],
        mb_per_layer=w["mb_per_layer"],
        sliding_window=w["sliding_window"],
        layer_norm_eps=w["layer_norm_eps"],
        published_layer_indices=tuple(w["published_layer_indices"]),
        mamba_d_state=w["mamba_d_state"], mamba_d_conv=w["mamba_d_conv"],
        mamba_expand=w["mamba_expand"], mamba_dt_rank=w["mamba_dt_rank"],
        scan_chunk=w["scan_chunk"], time_step_min=w["time_step_min"],
        time_step_max=w["time_step_max"],
        time_step_floor=w["time_step_floor"],
        initializer_range=weights.STD, lambda_std=weights.LAMBDA_STD)
    if cfg.head_dim != w["head_dim"]:
        raise ValueError("head_dim is not hidden_size / heads")
    with meta.meta_init():
        model = Phi4FlashForCausalLM(cfg)
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
    kinds = [cfg.layer_kind(l) for l in cfg.published_layer_indices]
    ctx.say(f"model: hidden {cfg.hidden_size}, MLP "
            f"{cfg.intermediate_size}, layers "
            f"{list(cfg.published_layer_indices)} of "
            f"{cfg.num_hidden_layers} (published "
            f"{pub['num_hidden_layers']}): {kinds}; Mamba-1 d_inner "
            f"{cfg.d_inner}, state {cfg.mamba_d_state}, dt_rank "
            f"{cfg.mamba_dt_rank}, chunk {cfg.scan_chunk}; attention "
            f"{cfg.num_attention_heads}/{cfg.num_key_value_heads} of "
            f"{cfg.head_dim} in pairs, window {cfg.sliding_window}; vocab "
            f"{cfg.vocab_size} (published {pub['vocab_size']}), tied "
            f"head, bf16; {weights.n_params(w, layers) / 1e6:.1f}M "
            f"parameters")
    return model
