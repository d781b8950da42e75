"""The system under test, built the way a user builds it.

The one place the benchmark touches the program's model code: the
program's Llama classes at the configuration's widths, built abstract
(``meta_init``, as ``chip_smoke.py`` does: initialising in float32 and
casting would hold 4 bytes a parameter) and given the benchmark's own
weights from the seed.
"""

from __future__ import annotations


def build_model(ctx, layers: int):
    """The program's Llama code at the configuration's widths, built
    abstract (``meta_init``) and given the benchmark's own weights."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.core import meta
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    weights = ctx.part("weights")
    w = ctx.widths()
    cfg = LlamaConfig(
        vocab_size=w["vocab_size"], hidden_size=w["hidden_size"],
        intermediate_size=w["intermediate_size"], num_hidden_layers=layers,
        num_attention_heads=w["num_attention_heads"],
        num_key_value_heads=w["num_key_value_heads"],
        max_position_embeddings=w["max_position_embeddings"],
        rms_norm_eps=w["rms_norm_eps"], rope_theta=w["rope_theta"],
        tie_word_embeddings=w["tie_word_embeddings"],
        initializer_range=weights.STD, dtype="bfloat16",
        use_flash_attention=True)
    if cfg.head_dim != w["head_dim"]:
        raise ValueError("head_dim is not hidden_size / heads")
    with meta.meta_init():
        model = LlamaForCausalLM(cfg)
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
    ctx.say(f"model: hidden {cfg.hidden_size}, mlp "
            f"{cfg.intermediate_size}, {cfg.num_attention_heads} heads "
            f"over {cfg.num_key_value_heads} KV heads of {cfg.head_dim}, "
            f"vocab {cfg.vocab_size}, bf16; num_hidden_layers {layers} "
            f"(published {ctx.config['published']['num_hidden_layers']}); "
            f"{weights.n_params(w, layers) / 1e6:.1f}M parameters")
    return model
