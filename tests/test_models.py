"""Model-zoo tests: forward shapes, grads finite, training reduces loss,
and (for mamba) the associative-scan recurrence vs a sequential numpy
reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import distributed as dist, optimizer as opt
from paddle_tpu.core.functional import extract_params, functional_call
from paddle_tpu.models import (
    GPTConfig,
    GPTForCausalLM,
    MambaConfig,
    MambaForCausalLM,
    ViT,
    ViTConfig,
)
from paddle_tpu.trainer import TrainStep


def test_gpt_forward_and_train():
    pt.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny(hidden_dropout_prob=0.0,
                                          attention_probs_dropout_prob=0.0,
                                          use_flash_attention=False))
    ids = jnp.asarray(np.random.randint(0, 256, (2, 16)))
    logits = model(ids)
    assert logits.shape == (2, 16, 256)
    mesh = dist.build_mesh(dp=2, fsdp=2, tp=2)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = dist.HybridConfig(
        dp_degree=2, sharding_degree=2, mp_degree=2
    )
    strategy.sharding = True
    strategy.sharding_configs.stage = 3
    ts = TrainStep(model, opt.AdamW(3e-3, multi_precision=False), mesh,
                   strategy)
    ids8 = jnp.asarray(np.random.randint(0, 256, (8, 16)))
    batch = {"input_ids": ids8, "labels": ids8}
    losses = [float(ts.run(batch)) for _ in range(12)]
    assert losses[-1] < losses[0], losses


def test_vit_forward_and_grads():
    pt.seed(1)
    model = ViT(ViTConfig.tiny())
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 3, 32, 32)), jnp.float32
    )
    logits = model(x)
    assert logits.shape == (2, 10)
    labels = jnp.asarray([1, 2])
    params = extract_params(model)
    loss, grads = jax.value_and_grad(
        lambda p: functional_call(model, p, x, labels=labels)
    )(params)
    assert np.isfinite(float(loss))
    for n, g in grads.items():
        assert bool(jnp.all(jnp.isfinite(g))), n


def test_mamba_scan_matches_sequential():
    from paddle_tpu.models.mamba import selective_scan

    rng = np.random.default_rng(0)
    b, s, d, n = 2, 12, 4, 3
    u = rng.standard_normal((b, s, d)).astype(np.float32)
    delta = np.abs(rng.standard_normal((b, s, d))).astype(np.float32)
    A = -np.abs(rng.standard_normal((d, n))).astype(np.float32)
    B = rng.standard_normal((b, s, n)).astype(np.float32)
    C = rng.standard_normal((b, s, n)).astype(np.float32)
    D = rng.standard_normal((d,)).astype(np.float32)

    y = selective_scan(*map(jnp.asarray, (u, delta, A, B, C, D)))

    # sequential reference
    h = np.zeros((b, d, n), np.float32)
    ys = np.zeros((b, s, d), np.float32)
    for t in range(s):
        dA = np.exp(delta[:, t, :, None] * A[None])
        dBu = (delta[:, t] * u[:, t])[..., None] * B[:, t, None, :]
        h = dA * h + dBu
        ys[:, t] = np.einsum("bdn,bn->bd", h, C[:, t]) + u[:, t] * D
    np.testing.assert_allclose(np.asarray(y), ys, rtol=1e-4, atol=1e-4)


def test_mamba_lm_trains():
    pt.seed(3)
    model = MambaForCausalLM(MambaConfig.tiny())
    ids = jnp.asarray(np.random.randint(0, 256, (4, 16)))
    params = extract_params(model)
    o = opt.AdamW(5e-3, multi_precision=False)
    state = o.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(
            lambda p: functional_call(model, p, ids, labels=ids)
        )(params)
        params, state = o.update(grads, state, params)
        return params, state, loss

    losses = []
    for _ in range(15):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


# ---------------- every family through TrainStep ----------------

def _vit_job():
    from paddle_tpu.nn import functional as F

    cfg = ViTConfig.tiny()
    rng = np.random.default_rng(0)
    data = {
        "input": jnp.asarray(rng.standard_normal(
            (4, cfg.num_channels, cfg.image_size, cfg.image_size)),
            jnp.float32),
        "label": jnp.asarray(rng.integers(0, cfg.num_classes, (4,))),
    }
    return ViT(cfg), data, lambda logits, label: F.cross_entropy(
        logits, label).mean()


def _unet_job():
    from paddle_tpu.core.module import Layer
    from paddle_tpu.models import UNet2DConditionModel, UNetConfig

    cfg = UNetConfig.tiny(sample_size=8)
    unet = UNet2DConditionModel(cfg)

    class DenoisingMSE(Layer):
        """The model returns its own loss (TrainStep's self-loss
        path): the MSE of the predicted against the given noise."""

        def __init__(self):
            super().__init__()
            self.unet = unet

        def forward(self, sample, timestep, context, target):
            pred = self.unet(sample, timestep, context)
            return jnp.mean((pred.astype(jnp.float32)
                             - target.astype(jnp.float32)) ** 2)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (1, cfg.in_channels, cfg.sample_size, cfg.sample_size)),
        jnp.float32)
    data = {
        "sample": x,
        "timestep": jnp.asarray(rng.integers(0, 1000, (1,))),
        "context": jnp.asarray(rng.standard_normal(
            (1, 77, cfg.cross_attention_dim)), jnp.float32),
        "target": x,
    }
    return DenoisingMSE(), data, None


def _token_job(model, vocab_size, batch, seq):
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, vocab_size, (batch, seq)))
    return model, {"input_ids": ids, "labels": ids}, None


def _moe_job():
    from paddle_tpu.models import ErnieMoEConfig, ErnieMoEForCausalLM

    cfg = ErnieMoEConfig.tiny(hidden_dropout_prob=0.0,
                              attention_probs_dropout_prob=0.0)
    return _token_job(ErnieMoEForCausalLM(cfg), cfg.vocab_size, 2, 128)


def _mamba_job():
    cfg = MambaConfig.tiny(use_chunked_scan=True, scan_chunk=32)
    return _token_job(MambaForCausalLM(cfg), cfg.vocab_size, 2, 64)


@pytest.mark.parametrize("family", ["vit", "unet", "moe", "mamba"])
def test_family_trains_through_trainstep(family):
    """The families TrainStep meets nowhere else, each with its own
    batch kind (images and labels with a ``loss_fn=`` adapter; latents,
    timesteps and context under a self-loss wrapper; token ids): two
    ``TrainStep.run`` calls on a one-device mesh, both losses finite
    and the second not the first (the update reached the parameters)."""
    pt.seed(0)
    model, data, loss_fn = {"vit": _vit_job, "unet": _unet_job,
                            "moe": _moe_job, "mamba": _mamba_job}[family]()
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    ts = TrainStep(model, opt.AdamW(1e-4, multi_precision=True), mesh,
                   loss_fn=loss_fn)
    first, second = float(ts.run(data)), float(ts.run(data))
    assert np.isfinite(first) and np.isfinite(second)
    assert second != first
