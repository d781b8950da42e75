"""``NemotronHForCausalLM`` against the plain reference
(``chipbench/reference/nemotron_h.py``), whose Mamba-2 block is the
step-by-step recurrence where the program runs the chunked SSD.

Seeded weights from the benchmark's own generator, cast to float32, on
both sides; conftest pins matmul precision ``highest``. So the two
differ by the order of float32 sums only (chunked against stepwise,
grouped products against a masked loop), and the tolerances are a few
float32 roundings of sums over 64 to 128 tokens: 2e-5 relative on the
loss, 2e-4 of a leaf's largest entry on its gradient (the A_log and
dt_bias leaves sum the whole sequence through exp()). A routing choice
flipped by such a rounding would show as a gap a thousand times that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from chipbench.reference import nemotron_h as ref
from chipbench.weights import nemotron_h as weights
from paddle_tpu import distributed as dist, optimizer as opt
from paddle_tpu.core.functional import functional_call
from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
from paddle_tpu.trainer import TrainStep


def _widths(pattern):
    return {
        "vocab_size": 128, "hidden_size": 32,
        "hybrid_override_pattern": pattern,
        "num_hidden_layers": len(pattern), "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "mamba_num_heads": 4,
        "mamba_head_dim": 8, "ssm_state_size": 8, "n_groups": 2,
        "conv_kernel": 4, "chunk_size": 16, "time_step_min": 0.001,
        "time_step_max": 0.1, "time_step_floor": 1e-4,
        "router_num_experts": 8, "n_routed_experts": 2,
        "held_experts_first": 2, "num_experts_per_tok": 3,
        "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 24,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "mlp_hidden_act": "relu2", "layer_norm_epsilon": 1e-5}


def _model(w):
    pt.seed(0)
    return NemotronHForCausalLM(NemotronHConfig(
        vocab_size=w["vocab_size"], hidden_size=w["hidden_size"],
        hybrid_override_pattern=w["hybrid_override_pattern"],
        num_attention_heads=w["num_attention_heads"],
        num_key_value_heads=w["num_key_value_heads"],
        head_dim=w["head_dim"], mamba_num_heads=w["mamba_num_heads"],
        mamba_head_dim=w["mamba_head_dim"],
        ssm_state_size=w["ssm_state_size"], n_groups=w["n_groups"],
        chunk_size=w["chunk_size"],
        n_routed_experts=w["router_num_experts"],
        held_experts=(w["held_experts_first"], w["n_routed_experts"]),
        num_experts_per_tok=w["num_experts_per_tok"],
        moe_intermediate_size=w["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=w[
            "moe_shared_expert_intermediate_size"],
        use_flash_attention=False))


@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEMEM*EME"],
                         ids=["mamba2", "experts", "attention", "nine"])
def test_loss_and_gradients_match_the_reference(pattern):
    w = _widths(pattern)
    model = _model(w)
    # larger than 0.02: at toy widths the blocks must matter to the loss
    params = {n: 5.0 * v.astype(jnp.float32) if v.ndim > 1
              else v.astype(jnp.float32)
              for n, v in weights.make_all(w, 7, len(pattern)).items()}
    assert set(params) == {n for n, _ in model.named_parameters()}
    assert weights.layer_shapes(w) == {} and \
        weights.n_params(w, len(pattern)) == sum(
            v.size for v in params.values())
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0,
                             w["vocab_size"])

    prog = jax.jit(jax.value_and_grad(lambda p: functional_call(
        model, p, input_ids=ids, labels=ids)))
    plain = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, w, len(pattern))))
    got_l, got = prog(params)
    want_l, want = plain(params)
    np.testing.assert_allclose(got_l, want_l, rtol=2e-5)
    for n in params:
        np.testing.assert_allclose(
            got[n], want[n], rtol=0, err_msg=n,
            atol=2e-4 * float(jnp.abs(want[n]).max()) + 1e-9)


def test_trains_through_train_step_and_reports_routing_counts():
    """bf16 parameters, fp32 masters, through ``TrainStep.run`` as the
    benchmark drives it; the step's routing counts reach telemetry."""
    prev = pt.flags.flag("telemetry")
    pt.flags.set_flags({"FLAGS_telemetry": True})
    try:
        pt.seed(0)
        model = NemotronHForCausalLM(NemotronHConfig.tiny(
            hybrid_override_pattern="MEME*", use_flash_attention=False))
        model.to(pt.bfloat16)
        assert not any("correction_bias" in n
                       for n, _ in model.named_parameters())
        mesh = dist.build_mesh(devices=jax.devices()[:1])
        from paddle_tpu import observability as obs

        ts = TrainStep(
            model, opt.AdamW(3e-3, multi_precision=True,
                             grad_clip=opt.ClipGradByGlobalNorm(1.0)),
            mesh, telemetry=obs.TrainTelemetry(sample_every=2))
        ids = np.random.default_rng(0).integers(
            0, 256, (2, 64), dtype=np.int32)
        losses = [float(ts.run({"input_ids": ids, "labels": ids}))
                  for _ in range(6)]
        assert losses[-1] < losses[0]
        sample = ts.telemetry.last_sample
        # 2 expert blocks x 128 tokens x top-3
        assert sample["moe_rows_routed"] == 2 * 128 * 3
        assert 0 < sample["moe_rows_held"] < sample["moe_rows_routed"]
        assert sample["moe_rows_max"] * 8 >= sample["moe_rows_held"]
    finally:
        pt.flags.set_flags({"FLAGS_telemetry": prev})


def test_dense_model_step_returns_no_counters():
    """A model without expert layers compiles the step it always did:
    loss and gradient norm, no further output."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(use_flash_attention=False))
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    ts = TrainStep(model, opt.AdamW(1e-3), mesh, telemetry=True,
                   abstract=True)
    assert not ts._emit_counters
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    out = ts.lower({"input_ids": ids, "labels": ids}).out_info
    assert len(out) == 4  # params, state, loss, grad_norm


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_mamba2_core_keeps_what_recomputing_it_whole_would_give(dtype):
    """``_mamba2_core`` keeps what its backward pass reads. Its gradients,
    on all seven arguments at the benchmark's rehearsal widths, are those
    of the same function made again whole in the backward pass (a plain
    ``jax.checkpoint`` with no policy): a value kept is the value made
    again, so they agree to the last bit."""
    from chipbench.run import load_json
    from paddle_tpu.models.mamba import _mamba2_core

    w = load_json("configs", "nemotron-3-nano-30b-a3b-train.json")[
        "rehearsal"]
    nh, p, g, n = (w["mamba_num_heads"], w["mamba_head_dim"], w["n_groups"],
                   w["ssm_state_size"])
    d_in, conv_dim = nh * p, nh * p + 2 * g * n
    sizes = (nh, p, g, n, w["chunk_size"], w["layer_norm_epsilon"])
    keys = iter(jax.random.split(jax.random.PRNGKey(35), 8))

    def draw(shape, scale=1.0, dtype=jnp.float32):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    args = (draw((2, 128, d_in + conv_dim + nh), dtype=dtype),
            draw((conv_dim, w["conv_kernel"]), 0.5), draw((conv_dim,), 0.1),
            draw((nh,)), draw((nh,), 0.5), 1 + draw((nh,), 0.1),
            1 + draw((d_in,), 0.1))
    cot = draw((2, 128, d_in))

    def grad_of(core):
        return jax.grad(lambda *a: jnp.sum(
            core(*a, sizes).astype(jnp.float32) * cot), range(7))

    whole = jax.checkpoint(_mamba2_core.__wrapped__, static_argnums=(7,))
    kernels = [str(jax.make_jaxpr(grad_of(core))(*args)).count("pallas_call")
               for core in (_mamba2_core, whole)]
    assert kernels == [2, 3], kernels
    for got, want in zip(jax.jit(grad_of(_mamba2_core))(*args),
                         jax.jit(grad_of(whole))(*args)):
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
