"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described and not attached. Interpret-mode tests cannot see
what it refuses (a block not aligned to the (8, 128) tiling, too much
VMEM), so the Pallas kernels of the main path are compiled here at
llama2-7B widths. Nothing runs: a case passing says the kernel lowers,
never that it is right or fast.

This is the only test file that describes the chip. Only one process
may hold libtpu, so the topology is described inside a module-scoped
fixture (never at import, in a ``skipif`` or in ``parametrize``
arguments) and every case compiles in the test's own process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import (
    _backend,
    decode_attention,
    paged_attention,
    pallas_attention,
    quant_matmul,
    ssd,
)

BF16 = jnp.bfloat16
# llama2-7B attention widths; the engine's 8 decode slots
HEADS, D, SLOTS, MAX_POS = 32, 128, 8, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """compile(fn, specs) for the described chip, with the
    kernels' interpret switch forced off (here ``default_backend()`` is
    the CPU), the persistent compile cache off (an entry written for a
    described chip cannot be read back without one) and matmul precision
    at the product default (``conftest.py`` pins ``highest`` for the
    numerics tests; Mosaic refuses an fp32-precision bf16 matmul)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(_backend, "interpret", lambda: False)
    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_compilation_cache",
              "jax_default_matmul_precision")}
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    # the flash kernels' calls are jitted: no trace made with the other
    # setting of the interpret switch may serve this one, or outlive it
    jax.clear_caches()

    def compile_(fn, specs, sharding=one_chip):
        args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                for s, d in specs]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    jax.clear_caches()


def _flash(grad, b=2, s=2048, hq=HEADS, hk=HEADS):
    def fwd(q, k, v):
        return pallas_attention.mha(q, k, v, causal=True)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), (0, 1, 2)
        )(q, k, v)

    q = ((b, s, hq, D), BF16)
    kv = ((b, s, hk, D), BF16)
    return (fwd_bwd if grad else fwd), (q, kv, kv)


def _paged_specs(kvh, page, pool_dtype, new_token):
    """Operand specs of the paged decode kernels: max_len 1024 at 8
    slots, as ``chip_smoke.py`` serves."""
    max_pages = 1024 // page
    n_pages = SLOTS * max_pages + 1
    pool = ((kvh, n_pages, page, D), pool_dtype)
    specs = [((SLOTS, kvh, HEADS // kvh, D), BF16)]
    if new_token:
        specs += [((SLOTS, kvh, D), BF16)] * 2
    specs += [pool, pool, ((SLOTS, max_pages), jnp.int32),
              ((SLOTS,), jnp.int32)]
    return specs, n_pages


def _block_table_decode(kvh, page):
    specs, _ = _paged_specs(kvh, page, BF16, new_token=False)
    return paged_attention.paged_decode_attention, specs


def _fused_paged(kvh, page, pool_dtype):
    specs, n_pages = _paged_specs(kvh, page, pool_dtype, new_token=True)
    rope = ((MAX_POS, D // 2), jnp.float32)
    specs += [((SLOTS,), jnp.int32), rope, rope]
    if pool_dtype == jnp.int8:
        scale = ((kvh, n_pages, page, 1), jnp.float32)
        specs += [scale, scale]

        def fn(q, kn, vn, kp, vp, bt, lens, pos, cos, sin, ks, vs):
            return paged_attention.fused_paged_decode_attention(
                q, kn, vn, kp, vp, bt, lens, pos, cos, sin,
                k_scale=ks, v_scale=vs)

        return fn, specs
    return paged_attention.fused_paged_decode_attention, specs


def _fused_contiguous(kvh, max_len, cache_dtype):
    cache = ((SLOTS, max_len, kvh, D), cache_dtype)
    rope = ((MAX_POS, D // 2), jnp.float32)
    specs = [((SLOTS, kvh, HEADS // kvh, D), BF16),
             ((SLOTS, kvh, D), BF16), ((SLOTS, kvh, D), BF16),
             cache, cache, ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32),
             rope, rope]
    if cache_dtype == jnp.int8:
        scale = ((SLOTS, max_len, kvh), jnp.float32)
        specs += [scale, scale]

        def fn(q, kn, vn, ck, cv, lens, pos, cos, sin, ks, vs):
            return decode_attention.fused_contiguous_decode_attention(
                q, kn, vn, ck, cv, lens, pos, cos, sin,
                k_scale=ks, v_scale=vs)

        return fn, specs
    return decode_attention.fused_contiguous_decode_attention, specs


def _quant_matmul(m, weight_dtype):
    k, n = 4096, 11008  # llama2-7B gate/up projection
    rows = k // 2 if weight_dtype == "int4" else k

    def fn(x, w, s):
        return quant_matmul.weight_only_matmul_pallas(
            x, w, s, weight_dtype=weight_dtype)

    return fn, [((m, k), BF16), ((rows, n), jnp.int8),
                ((k // 128, n), jnp.float32)]


# every Pallas kernel of the train and serve main paths, and every
# decode kernel PT_FLAGS_fused_decode=auto can choose on a TPU
CASES = {
    "flash_fwd": lambda: _flash(False),
    "flash_fwd_bwd": lambda: _flash(True),
    "flash_fwd_bwd_gqa8_s8192": lambda: _flash(True, b=1, s=8192, hk=8),
    # nemotron3-nano-train-8k's attention: 32 query heads over 2
    "flash_fwd_bwd_gqa2_s8192": lambda: _flash(True, b=1, s=8192, hk=2),
    "block_table_decode_p64": lambda: _block_table_decode(32, 64),
    "block_table_decode_p16_gqa8": lambda: _block_table_decode(8, 16),
    "fused_paged_bf16_p64": lambda: _fused_paged(32, 64, BF16),
    "fused_paged_bf16_p16_gqa8": lambda: _fused_paged(8, 16, BF16),
    "fused_paged_bf16_p128": lambda: _fused_paged(32, 128, BF16),
    "fused_paged_int8_p64": lambda: _fused_paged(32, 64, jnp.int8),
    "fused_paged_int8_p32_gqa8": lambda: _fused_paged(8, 32, jnp.int8),
    "fused_contiguous_bf16": lambda: _fused_contiguous(32, 2048, BF16),
    "fused_contiguous_bf16_gqa8": lambda: _fused_contiguous(8, 1024, BF16),
    "fused_contiguous_int8": lambda: _fused_contiguous(32, 1024, jnp.int8),
    "wo_matmul_int8_m8": lambda: _quant_matmul(8, "int8"),
    "wo_matmul_int8_m256": lambda: _quant_matmul(256, "int8"),
    "wo_matmul_int4_m256": lambda: _quant_matmul(256, "int4"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, chip_compile):
    fn, specs = CASES[case]()
    compiled = chip_compile(fn, specs)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_is_one_forward_and_one_backward_call(chip_compile):
    """The benchmark cell's own attention (mistral7b-train-2k: b 2,
    s 2048, 32 heads over 8, d 128), forward + backward. The two flash
    roofline metrics multiply one call's required work by the number of
    kernel events the trace holds, so an attention call must stay ONE
    forward and ONE backward custom call."""
    import json
    import pathlib
    import re

    fn, specs = _flash(True, b=2, s=2048, hq=32, hk=8)
    text = chip_compile(fn, specs).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # ... under the names the two metrics' patterns look for: an event of
    # the trace is named by its instruction's text
    lines = [ln.strip() for ln in text.splitlines()]
    metrics = pathlib.Path(__file__).parent.parent / "chipbench" / "metrics"
    for name in ("flash_fwd_roofline.train", "flash_bwd_roofline.train"):
        rx = re.compile(json.loads(
            (metrics / f"{name}.json").read_text())["args"]["kernel"])
        assert sum(bool(rx.search(ln)) for ln in lines) == 1, name


def test_flash_compiles_under_four_chip_mesh(topo, chip_compile):
    """The ZeRO-3 train step's attention on the 2x2 host: a bare Pallas
    call there is refused ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"), so
    ``flash_attention`` runs it per shard under a mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed.sharding import mesh_context
    from paddle_tpu.kernels.flash_attention import flash_attention

    mesh = dist.build_mesh(fsdp=4, devices=list(topo.devices))
    qkv = ((4, 2048, HEADS, D), BF16)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: flash_attention(
            *a, causal=True).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    with mesh_context(mesh):
        compiled = chip_compile(
            fwd_bwd, (qkv, qkv, qkv),
            NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None)))
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba2_core_holds_the_two_ssd_kernels_and_no_chunk_tensor(
        chip_compile):
    """A Mamba-2 block of the cell ``nemotron3-nano-train-8k`` between
    its two projections (conv, SSD, gated norm: ``_mamba2_core``),
    forward and backward at the published widths and 1 x 8192 tokens:
    the SSD is two custom calls, the forward rule's and the backward's,
    and nothing float32 of ``[chunks, heads, Q, Q]``, the
    einsum form's decays and scores, is left among the program's
    arrays. The kernels ask for no more VMEM than the flash kernels'
    budget; the compiler refuses a kernel over its limit."""
    import json
    import math
    import pathlib
    import re

    from paddle_tpu.models.mamba import _mamba2_core

    root = pathlib.Path(__file__).parent.parent / "chipbench"
    w = json.loads((root / "configs" /
                    "nemotron-3-nano-30b-a3b-train.json").read_text())
    sizes = json.loads((root / "traffic" / "train-8k.json").read_text())
    b, s = sizes["batch"], sizes["sequence"]
    nh, p, g, n, chunk = (w["mamba_num_heads"], w["mamba_head_dim"],
                          w["n_groups"], w["ssm_state_size"],
                          w["chunk_size"])
    assert (nh, p, g, n, chunk) == (64, 64, 8, 128, 128)
    d_in, conv_dim = nh * p, nh * p + 2 * g * n

    def fwd_bwd(*a):
        return jax.grad(lambda *a: _mamba2_core(
            *a, (nh, p, g, n, chunk, 1e-5)).astype(jnp.float32).sum(),
            range(7))(*a)

    f32 = jnp.float32
    text = chip_compile(fwd_bwd, [
        ((b, s, 2 * d_in + 2 * g * n + nh), BF16),
        ((conv_dim, w["conv_kernel"]), f32), ((conv_dim,), f32),
        ((nh,), f32), ((nh,), f32), ((nh,), f32), ((d_in,), f32),
    ]).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    per_chunk = (s // chunk) * nh * chunk * chunk
    big = {m.group(0) for m in re.finditer(
        r"f32\[([\d,]+),%d,%d\]" % (chunk, chunk), text)
        if math.prod(map(int, m.group(1).split(","))) * chunk * chunk
        >= per_chunk}
    assert not big, sorted(big)
    # ... and both carry the scope the cell's SSD metric reads (a
    # backward rule keeps its caller's)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    assert len(calls) == 2 and all(
        re.search(r'op_name="[^"]*ssm_scan', ln) for ln in calls), calls
    assert ssd._VMEM_LIMIT_BYTES <= pallas_attention._TILE_VMEM_BYTES


def test_nemotron_nine_block_step_compiles_and_fits(topo, chip_compile):
    """The whole train step of the cell ``nemotron3-nano-train-8k`` as
    the benchmark builds it (nine blocks MEMEM*EME at the published
    widths, 8 of 128 experts held, an eighth of the vocabulary, 1 x 8192
    tokens, AdamW with fp32 masters), compiled for a described v5e: the
    chunked SSD, the held experts' products under their device-counted
    loops and flash attention at 32/2 heads all lower,
    and the program fits one chip beside its 9.3 GB of state. A Mamba-2
    block makes its forward once: two SSD kernel calls a block, and
    nothing that sums (a kernel, the conv's and the chunk's windows, the
    norm's group sums) among what the backward pass makes again. XLA
    itself makes nothing again either: where a step's live arrays pass
    what the compiler allows a program, it recomputes weight products
    to fit and names them ``.remat``."""
    import json
    import pathlib
    import re

    import paddle_tpu as pt
    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.core import meta
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
    from paddle_tpu.trainer import TrainStep

    root = pathlib.Path(__file__).parent.parent / "chipbench"
    w = json.loads((root / "configs" /
                    "nemotron-3-nano-30b-a3b-train.json").read_text())
    sizes = json.loads((root / "traffic" / "train-8k.json").read_text())
    cfg = NemotronHConfig(
        vocab_size=w["vocab_size"],
        hybrid_override_pattern=w["hybrid_override_pattern"],
        held_experts=(w["held_experts_first"], w["n_routed_experts"]),
        n_routed_experts=w["router_num_experts"])
    assert (cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.mamba_num_heads, cfg.ssm_state_size) == (
        w["hidden_size"], w["moe_intermediate_size"],
        w["mamba_num_heads"], w["ssm_state_size"])
    with meta.meta_init():
        model = NemotronHForCausalLM(cfg)
    model.to(pt.bfloat16)
    mesh = dist.build_mesh(devices=[topo.devices[0]])
    ts = TrainStep(
        model, opt.AdamW(1e-4, multi_precision=True,
                         grad_clip=opt.ClipGradByGlobalNorm(1.0)),
        mesh, abstract=True)
    ids = jax.ShapeDtypeStruct((sizes["batch"], sizes["sequence"]),
                               jnp.int32)
    compiled = ts.lower({"input_ids": ids, "labels": ids}).compile()
    text = compiled.as_text()
    # flash attention is there, and the held experts' loops are loops:
    # the two-matrix experts stay on the walk, no grouped kernel here
    assert "tpu_custom_call" in text and " while(" in text
    assert "experts_gate_up" not in text and "experts_d_" not in text
    lines = text.splitlines()
    calls = [ln for ln in lines if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    scans = [ln for ln in calls if re.search(r'op_name="[^"]*ssm_scan', ln)]
    # 4 M blocks x (forward, backward); flash forward and its two-call
    # backward at s = 8192
    assert (len(scans), len(calls)) == (8, 11), (len(scans), len(calls))
    again = [ln.split(" = ")[0].strip() for ln in lines
             if "rematted_computation" in ln and re.search(
                 r" (custom-call|reduce-window|reduce)\(", ln)]
    assert not again, again
    by_xla = sorted(set(re.findall(r"%([\w.\-]+\.remat[\d.]*) = ", text)))
    assert not by_xla, by_xla
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    # 15.75 GiB (16.91e9 B) is what a v5e chip gives a program; half a
    # gigabyte of it is left as margin. The step reads 15.76e9 B here
    assert total < 16.91e9 - 0.5e9, total


@pytest.mark.parametrize("name, m, h, top_k, gated, kernels", [
    ("nemotron", 2688, 1856, 6, False, 0), ("lfm2", 2048, 1792, 4, True, 6)])
def test_held_experts_take_the_grouped_kernels_only_when_gated_and_aligned(
        name, m, h, top_k, gated, kernels, chip_compile):
    """On the chip (here: the kernels' switch forced as there) the
    Nemotron cell's two-matrix experts of 1856 lower to the walk, with
    no kernel call, forward or backward; the LFM2 cell's lower to the
    six grouped calls."""
    import re

    from paddle_tpu.distributed import moe

    bf16 = jnp.bfloat16
    w = {"w1": ((8, m, h), bf16), "w2": ((8, h, m), bf16)}
    if gated:
        w["w3"] = w["w1"]
    act = jax.nn.silu if gated else moe.relu2

    def step(x, idx, gates, *w_):
        def loss(x, gates, w_):
            return jnp.sum(moe.held_experts_apply(
                x, idx, gates, dict(zip(sorted(w), w_)), act, 0)[0])
        return jax.value_and_grad(loss, (0, 1, 2))(x, gates, w_)

    specs = [((8192, m), bf16), ((8192, top_k), jnp.int32),
             ((8192, top_k), jnp.float32)] + [w[k] for k in sorted(w)]
    text = chip_compile(step, specs).as_text()
    assert len(re.findall(r"custom-call\(.*tpu_custom_call", text)) \
        == kernels, name
    assert ("experts_gate_up" in text) == bool(kernels)


def _calls_matched(calls, metric):
    """The kernel calls a metric's patterns match (``kernels_of`` names
    the metric files whose pattern is taken as it stands)."""
    import json
    import pathlib
    import re

    metrics = pathlib.Path(__file__).parent.parent / "chipbench" / "metrics"
    args = json.loads((metrics / f"{metric}.json").read_text())["args"]
    if "kernel" in args:
        return [ln for ln in calls if re.search(args["kernel"], ln)]
    return sum((_calls_matched(calls, m) for m in args["kernels_of"]), [])


def test_phi4flash_six_layer_step_compiles_and_fits(topo, chip_compile):
    """The whole train step of the cell ``phi4-mini-flash-train-8k`` as
    the benchmark builds it (published layers 0, 1, 16, 17, 18, 19 at the
    published widths, an eighth of the vocabulary with the head tied,
    1 x 8192 tokens, AdamW with fp32 masters), compiled for a described
    v5e: the selective scan's kernel pair lowers at d_inner 5120, state
    16 (it had never been compiled for the chip), flash attention lowers
    with a window, with scores' heads padded to the values' 128 and as
    cross-attention on another layer's keys and values, and the program
    fits one chip beside its 9.76 GB of state. A Mamba-1 layer runs its
    scan's forward kernel once (its output and states are kept), both
    kernels keep the scope ``s6_scan`` that the cell's metrics read and
    the names their roofline metrics match, and XLA itself makes nothing
    again (no instruction named ``.remat``)."""
    import json
    import pathlib
    import re

    import paddle_tpu as pt
    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.core import meta
    from paddle_tpu.models import Phi4FlashConfig, Phi4FlashForCausalLM
    from paddle_tpu.trainer import TrainStep

    root = pathlib.Path(__file__).parent.parent / "chipbench"
    w = json.loads((root / "configs" /
                    "phi-4-mini-flash-reasoning-train.json").read_text())
    sizes = json.loads((root / "traffic" / "train-8k.json").read_text())
    cfg = Phi4FlashConfig(
        vocab_size=w["vocab_size"],
        num_hidden_layers=w["published_num_hidden_layers"],
        published_layer_indices=tuple(w["published_layer_indices"]))
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.d_inner,
            cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.sliding_window,
            cfg.scan_chunk) == (
        w["hidden_size"], w["intermediate_size"],
        w["mamba_expand"] * w["hidden_size"], w["mamba_d_state"],
        w["mamba_dt_rank"], w["sliding_window"], w["scan_chunk"])
    with meta.meta_init():
        model = Phi4FlashForCausalLM(cfg)
    model.to(pt.bfloat16)
    mesh = dist.build_mesh(devices=[topo.devices[0]])
    ts = TrainStep(
        model, opt.AdamW(1e-4, multi_precision=True,
                         grad_clip=opt.ClipGradByGlobalNorm(1.0)),
        mesh, abstract=True)
    ids = jax.ShapeDtypeStruct((sizes["batch"], sizes["sequence"]),
                               jnp.int32)
    compiled = ts.lower({"input_ids": ids, "labels": ids}).compile()
    text = compiled.as_text()
    # a trace names an event by the instruction's text, its name first
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    scans = [ln for ln in calls if re.search(r'op_name="[^"]*s6_scan', ln)]
    # 2 Mamba-1 layers x (forward, backward); 3 attention layers x 2
    # maps x (forward, one fused backward call)
    assert (len(scans), len(calls)) == (4, 16), (len(scans), len(calls))
    fwd, bwd, flash = (_calls_matched(calls, m) for m in (
        "s6_scan_fwd_roofline.train", "s6_scan_bwd_roofline.train",
        "diff_attn_device_ms.train"))
    assert len(fwd) == len(bwd) == 2 and not set(fwd) & set(bwd)
    assert sorted(fwd + bwd) == sorted(scans)
    assert len(flash) == 12 and not set(flash) & set(scans)
    # the scan's kernels are handed u, B and C as the bf16 they are
    # (delta float32), and gather dB and dC over the blocks of channels
    # themselves: no partial a block is left for XLA to sum
    b, s, d, n = (sizes["batch"], sizes["sequence"], cfg.d_inner,
                  cfg.mamba_d_state)
    handed = (f"bf16[{b},{s},{d}]{{2,1,0}}, f32[{b},{s},{d}]{{2,1,0}}, "
              f"bf16[{b},{s},{n}]{{2,1,0}}, bf16[{b},{s},{n}]{{2,1,0}}, ")
    assert all("operand_layout_constraints={" + handed in ln for ln in scans)
    assert not re.search(rf"f32\[\d+,{b},{s},{n}\]", text)
    by_xla = sorted(set(re.findall(r"%([\w.\-]+\.remat[\d.]*) = ", text)))
    assert not by_xla, by_xla
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    assert m.argument_size_in_bytes >= w["bytes_reckoned"]["state_bytes"]
    # 15.75 GiB (16.91e9 B) is what a v5e chip gives a program; half a
    # gigabyte of it is left as margin. The step reads 15.50e9 B here
    assert total < 16.91e9 - 0.5e9, total


def test_lfm2_five_layer_step_compiles_and_fits(topo, chip_compile):
    """The whole train step of the cell ``lfm2-8b-a1b-train-8k`` as the
    benchmark builds it (published layers 1-5 at the published widths, 8
    of 32 SwiGLU experts held, a quarter of the vocabulary with the head
    tied, 1 x 8192 tokens, AdamW with fp32 masters), compiled for a
    described v5e: plain causal flash attention lowers at head size 64
    (padded to the 128 lanes), the gated experts' rows go through the
    grouped kernels over one sorted buffer (no loop over the experts or
    their blocks is left), whose calls keep the scope ``moe_experts``
    that the cell's metric reads in both passes, the short
    convolution's taps and gates carry ``sconv_mix`` forward and
    backward, and the program fits one chip beside its 7.11 GB of state
    with nothing made again by XLA (no instruction named ``.remat``)."""
    import json
    import pathlib
    import re

    import paddle_tpu as pt
    from paddle_tpu import distributed as dist, optimizer as opt
    from paddle_tpu.core import meta
    from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM
    from paddle_tpu.trainer import TrainStep

    root = pathlib.Path(__file__).parent.parent / "chipbench"
    w = json.loads((root / "configs" / "lfm2-8b-a1b-train.json").read_text())
    sizes = json.loads((root / "traffic" / "train-8k.json").read_text())
    cfg = Lfm2MoeConfig(
        vocab_size=w["vocab_size"], layer_types=tuple(w["layer_types"]),
        num_dense_layers=w["num_dense_layers"],
        num_experts=w["router_num_experts"],
        held_experts=(w["held_experts_first"], w["num_experts"]))
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.conv_L_cache,
            cfg.num_experts_per_tok, cfg.rope_theta) == (
        w["hidden_size"], w["intermediate_size"],
        w["moe_intermediate_size"], w["num_attention_heads"],
        w["num_key_value_heads"], w["head_dim"], w["conv_L_cache"],
        w["num_experts_per_tok"], w["rope_theta"])
    with meta.meta_init():
        model = Lfm2MoeForCausalLM(cfg)
    model.to(pt.bfloat16)
    mesh = dist.build_mesh(devices=[topo.devices[0]])
    ts = TrainStep(
        model, opt.AdamW(1e-4, multi_precision=True,
                         grad_clip=opt.ClipGradByGlobalNorm(1.0)),
        mesh, abstract=True)
    ids = jax.ShapeDtypeStruct((sizes["batch"], sizes["sequence"]),
                               jnp.int32)
    compiled = ts.lower({"input_ids": ids, "labels": ids}).compile()
    text = compiled.as_text()
    lines = text.splitlines()
    calls = [ln.strip() for ln in lines
             if "tpu_custom_call" in ln and "custom-call(" in ln]

    # one attention layer: flash forward and its two-call backward at
    # s = 8192, rep 4: the only calls the cell's attention metric counts
    flash = _calls_matched(calls, "qknorm_attn_device_ms.train")
    assert len(flash) == 3, flash
    # a sparse layer's held rows go through the grouped kernels: two
    # calls forward, four backward (the weight gradients as two), each
    # under the scope the experts' metric reads, in its own pass
    grouped = [ln for ln in calls if ln not in flash]
    kernels = {"experts_gate_up": "jvp", "experts_down": "jvp",
               "experts_d_hidden": "transpose", "experts_d_rows": "transpose",
               "experts_d_weights": "transpose"}
    sparse = len(cfg.layer_types) - cfg.num_dense_layers
    assert len(grouped) == 6 * sparse, len(grouped)
    for ln in grouped:
        op_name = re.search(r'op_name="([^"]*)"', ln).group(1)
        kernel, = [k for k in kernels
                   if re.search(r"[/(]%s[/)]" % k, op_name)]
        under = r"(.*[/(])?moe_experts[/)]"
        if kernels[kernel] == "transpose":
            assert re.search(r"transpose\(jvp\(" + under, op_name), op_name
        else:
            assert re.search(r"jvp\(" + under, op_name), op_name
            assert "transpose(" not in op_name, op_name
    assert sum(ln.count("experts_d_weights") > 0 for ln in grouped) \
        == 2 * sparse
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("moe_experts", "sconv_mix"):
        assert any(re.search(r"jvp\((.*[/(])?%s[/)]" % scope, n)
                   for n in names), scope
        assert any(re.search(r"transpose\(jvp\((.*[/(])?%s[/)]" % scope, n)
                   for n in names), scope
    # the per-expert scan and its block loops are gone: no loop carries
    # the stacked matrices or a float32 sum of a weight gradient (what
    # loops are left move the live rows into and out of the sorted
    # buffer, and sort)
    f, hid = cfg.moe_intermediate_size, cfg.hidden_size
    assert re.search(rf"bf16\[8,{hid},{f}\]", text)
    whiles = [ln for ln in lines if " while(" in ln]
    assert whiles
    for ln in whiles:
        assert not re.search(
            rf"bf16\[8,{hid},{f}\]|f32\[{hid},{f}\]|f32\[{f},{hid}\]", ln), ln
    by_xla = sorted(set(re.findall(r"%([\w.\-]+\.remat[\d.]*) = ", text)))
    assert not by_xla, by_xla
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    assert m.argument_size_in_bytes >= w["bytes_reckoned"]["state_bytes"]
    print("lfm2 memory_analysis:", m.argument_size_in_bytes,
          m.temp_size_in_bytes, m.output_size_in_bytes,
          m.alias_size_in_bytes, total)
    # 15.75 GiB (16.91e9 B) is what a v5e chip gives a program; the step
    # reads far under it here (the configuration's bytes_reckoned)
    assert total < 16.91e9 - 0.5e9, total


def test_selective_scan_pair_compiles_at_mamba130m_width(chip_compile):
    """The selective scan's kernel pair at ``MambaConfig``'s other
    user's width (Mamba-130m: d_inner 1536, state 16) over 2048 tokens,
    operands as the mixer hands them: the blocks of channels the kernel
    picks for itself (three of 512, seen in dA's gathered output) fit
    the chip's VMEM in both directions."""
    import re

    from paddle_tpu.kernels import selective_scan

    b, s, d, n = 1, 2048, 1536, 16
    f32 = jnp.float32

    def loss(*args):
        return jnp.sum(selective_scan.chunked_selective_scan(*args,
                                                             chunk=128))

    compiled = chip_compile(
        jax.grad(loss, argnums=tuple(range(6))),
        [((b, s, d), BF16), ((b, s, d), f32), ((d, n), f32),
         ((b, s, n), BF16), ((b, s, n), BF16), ((d,), f32)])
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert len(calls) == 2, len(calls)
    assert selective_scan._pick_d_block(d, n, 128) == 512
    assert sum(bool(re.search(rf"f32\[{b},3,{n},512\]", ln))
               for ln in calls) == 1


@pytest.mark.parametrize("page,dtype", [(64, BF16), (16, BF16),
                                        (32, jnp.int8), (128, jnp.int8)])
def test_auto_only_picks_compiled_decode_kernels(page, dtype, monkeypatch):
    """What ``auto`` says on a TPU for the tilings compiled above: the
    gate and the compile cases must not drift apart."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode_attention.fused_decode_active(D, page, dtype)
    assert not decode_attention.fused_decode_active(D, 8, dtype)
