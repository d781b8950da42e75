"""Tests of the benchmark's own yardstick (chipbench/), on the CPU.

The trace reduction on a hand-made event list, the required-operations
count against a hand count, the names and cross-references of
BENCHMARK.json and the files it points at, the plain reference against
the program's Llama code at tiny GQA widths, the control of "How
correct is decided" (the reference one precision down has to read
worse than the program does), and a whole run at the rehearsal size
with the timed path broken underneath, which has to come out not
correct. No topology is described and nothing is compiled for a chip.
"""

import json
import os
import re
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_union_of_intervals_and_idle_share():
    from chipbench.readers import device_idle_share, xplane

    # overlapping (0-10, 5-15), nested (20-40 holds 25-30), disjoint 50-60
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 40, "while"), (25, 30, "c"),
           (50, 60, "kernel")]
    assert xplane.union_ns((s, e) for s, e, _ in ops) == 15 + 20 + 10
    assert xplane.gaps_ns(((s, e) for s, e, _ in ops), 0, 100) == [
        (60, 100), (40, 50), (15, 20)]
    mods = [(0, 16, "jit_step(1)"), (20, 60, "jit_other(2)")]
    trace = xplane.Trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}},
        [(40, 50, "make_batch")], (0, 100))
    assert trace.busy_s() == pytest.approx(45e-9)
    assert device_idle_share.read(trace, {}, {}, {}) == pytest.approx(55.0)
    assert trace.module_busy_ns("jit_step") == (15, 1)
    assert trace.module_busy_ns("jit_", holds="kernel") == (30, 1)
    assert trace.module_busy_ns("jit_", lacks="kernel") == (15, 1)
    assert trace.op_ns("^kernel$") == (10, 1)
    gaps = trace.breakdown("nobody")["idle_gaps"]
    assert gaps[0] == ["nobody", pytest.approx(40e-9)]
    assert gaps[1] == ["make_batch", pytest.approx(10e-9)]


def test_required_flops_against_a_hand_count():
    from chipbench.opsbytes import dense_gqa_flops as f

    w = {"hidden_size": 8, "intermediate_size": 16, "head_dim": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "vocab_size": 32}
    # one layer: q 8x8, k and v 8x4 each, o 8x8, three MLP 8x16; head 8x32
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert f.matmul_params(w, 1) == per_layer + 256
    # 10 tokens, each seeing 5.5 keys: QK^T and PV, 2 flops a multiply-add
    attn = 2 * 2 * 10 * 5.5 * (4 * 2)
    assert f.forward(w, 1, 10, 5.5) == 2 * (per_layer + 256) * 10 + attn
    assert f.train_step(w, 1, 2, 5) == 3 * f.forward(w, 1, 10, 3.0)


def test_names_files_and_moves():
    from chipbench import run as harness

    bench = _bench()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {c["name"]: c for c in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
    assert "setup_s" in e2e and all(
        0 < m["bound"] <= 0.1 for m in e2e.values())
    for cell in cells.values():
        assert NAME.match(cell["traffic"]) and cell["config"] in configs
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
        for sub in ("traffic", "limits"):
            name = cell["traffic"] if sub == "traffic" else cell["name"]
            assert os.path.exists(os.path.join(
                ROOT, "chipbench", sub, name + ".json")), (sub, name)
    for cfg in configs.values():
        with open(os.path.join(ROOT, cfg["file"])) as f:
            data = json.load(f)
        for key in cfg["reduced"]:
            assert data["published"][key] != data[key]
        for key, where in harness.PARTS.items():  # found by name
            assert os.path.exists(os.path.join(
                ROOT, "chipbench", where, data[key] + ".py")), (key, where)

    def reports(metric, cell):
        return cell in metric.get("workloads", cells)

    for m in bench["per_layer"]:
        with open(os.path.join(ROOT, "chipbench", "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert {k: spec[k] for k in m} == m, m["name"]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "readers", spec["reader"] + ".py"))
        assert m["moves"] in e2e, m["name"]
        for cell in m.get("workloads", []):
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
    for cell in cells:  # each cell: setup_s, another, and a per-layer one
        assert sum(reports(m, cell) for m in e2e.values()) >= 2
        assert any(reports(m, cell) and reports(e2e[m["moves"]], cell)
                   for m in bench["per_layer"])


@pytest.fixture(scope="module")
def tiny():
    """A tiny GQA Llama of the program's with the benchmark's weights,
    float32 so that the comparison is of the mathematics."""
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from chipbench.weights import dense_gqa as weights

    w = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "rms_norm_eps": 1e-5, "rope_theta": 1e6}
    cfg = LlamaConfig.tiny(rms_norm_eps=1e-5, rope_theta=1e6,
                           use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    values = {n: v.astype(jnp.float32)
              for n, v in weights.make_all(w, 7, 2).items()}
    for name, p in model.named_parameters():
        p.value = values[name]
    ids = np.random.default_rng(0).integers(0, 256, (2, 24), np.int32)
    return w, model, values, jnp.asarray(ids)


def test_reference_matches_the_programs_llama(tiny):
    import jax.numpy as jnp

    from chipbench.reference import dense_gqa as ref

    w, model, values, ids = tiny
    top, per = ref.split_params(values, 2)
    cos, sin = ref.rope_tables(16, ids.shape[1], 1e6)
    x = top["model.embed_tokens.weight"][ids]
    for lp in per:
        x = ref.decoder_layer(x, lp, w, cos, sin)
    logits = ref.head_logits(x, top, w)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(model(ids)), atol=2e-5)
    loss = ref.lm_loss(values, ids, w, 2)
    np.testing.assert_allclose(float(loss),
                               float(model(ids, labels=ids)), rtol=1e-5)
    assert jnp.isfinite(loss)


def test_control_reads_worse_than_float32(tiny):
    """The reference one precision down (W8A8) in the program's place:
    the token it puts first lies below the float32 best somewhere, and
    its gradients' norms leave the float32 ones."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import dense_gqa as ref

    w, _, values, ids = tiny
    top, per = ref.split_params(values, 2)
    cos, sin = ref.rope_tables(16, ids.shape[1], 1e6)

    def logits(mm):
        x = top["model.embed_tokens.weight"][ids]
        for lp in per:
            x = ref.decoder_layer(x, lp, w, cos, sin, mm)
        return ref.head_logits(x, top, w, mm).reshape(-1, 256)

    full = logits(ref.f32_mm)
    low = jnp.argmax(logits(ref.int8_mm), -1)[:, None]
    gap = jnp.max(full, -1) - jnp.take_along_axis(full, low, -1)[:, 0]
    assert float(jnp.max(gap)) > 1e-3
    g32 = jax.grad(lambda p: ref.lm_loss(p, ids, w, 2))(values)
    g8 = jax.grad(lambda p: ref.lm_loss(p, ids, w, 2, ref.int8_mm))(values)
    worst = max(abs(float(jnp.linalg.norm(g8[n]) - jnp.linalg.norm(g32[n])))
                / float(jnp.linalg.norm(g32[n])) for n in g32)
    assert worst > 1e-3


# the serve cell is out of BENCHMARK.json (PERF.md section 7, row 1); its
# kind and files stay, and stay tested at the toy size
CELLS = {"mistral7b-train-2k": ("mistral-7b-v0.3-train", "train-2k"),
         "mistral7b-serve-chat": ("mistral-7b-v0.3-serve", "chat")}


def _rehearse(workload, fault):
    """The rest of a run without the look for a chip: the kind's own
    ``run`` at the rehearsal size on whatever device the tests have."""
    import jax

    from chipbench import run as harness

    args = types.SimpleNamespace(
        seed=11, seconds=0.3, trace=0, rehearse_cpu=True, mode="run",
        fault=fault)
    config, traffic = CELLS[workload]
    cell = {"name": workload, "config": config, "traffic": traffic,
            "chips": 1}
    ctx = harness.Ctx(args, cell,
                      harness.load_json("configs", config + ".json"),
                      harness.load_json("traffic", traffic + ".json"),
                      harness.load_json("limits", workload + ".json"),
                      jax.devices()[:1])
    return ctx.part("kind").run(ctx)


@pytest.mark.parametrize("workload,fault", [
    ("mistral7b-train-2k", None),
    ("mistral7b-train-2k", "frozen_state"),
    ("mistral7b-train-2k", "half_batch"),
    ("mistral7b-serve-chat", None),
    ("mistral7b-serve-chat", "alter_token"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    res = _rehearse(workload, fault)
    assert res["correct"] is (fault is None), res["compared"]
