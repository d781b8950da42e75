"""Shared serving-engine test helpers.

The greedy-parity machinery (tiny model + engine config factories, the
drain loop, the mixed-prompt workload, and THE spec-off-vs-on parity
comparison) started life in test_spec_decode.py; the quantized-serving
suite (test_quant_serving.py) runs the same comparisons under int8
weights / int8 KV pools, so the helpers live here once instead of being
copy-pasted per suite. Import from test modules as ``import
serving_utils`` (pytest puts tests/ on sys.path).
"""

import numpy as np

from paddle_tpu.analysis.program_audit import (
    tiny_engine_config,
    tiny_model,  # noqa: F401  (re-export: suites import it from here)
)
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.inference.spec_decode import Drafter

# the tiny model/engine factories live with the contract auditor
# (analysis/program_audit.py) — ONE source of truth for the
# CPU-friendly shapes both the audits and these suites trace at
tiny_ecfg = tiny_engine_config


def drain(eng, step=None):
    step = step or eng.step
    while step() or eng._queue or eng.active.any():
        pass


def mixed_prompts(cfg, rng):
    """Repetitive prompts (drafts fire) + a random one + a ragged short
    one — and callers add one request whose 1-token budget can NEVER
    draft (see ``spec_parity_outputs``)."""
    unit = rng.integers(1, cfg.vocab_size, 4)
    return [
        np.concatenate([unit] * 5),                       # periodic
        rng.integers(1, cfg.vocab_size, 11),              # random
        np.concatenate([rng.integers(1, cfg.vocab_size, 3), unit, unit]),
    ]


class ReplayDrafter(Drafter):
    """Drafts what a run without speculation produced: for a history,
    the next ``k`` tokens of the longest recorded sequence (a request's
    prompt plus its output) that starts with that history, nothing
    where none does. If speculation is bit-identical to plain decode
    every draft is accepted, whatever the weights — so ``accepted > 0``
    no longer hangs on a tiny random model falling into a loop the
    n-gram drafter can find; if it is not, the outputs differ."""

    def __init__(self, prompts, outputs):
        self._seqs = sorted(
            (np.concatenate([np.asarray(p, np.int64).reshape(-1),
                             np.asarray(o, np.int64)])
             for p, o in zip(prompts, outputs)),
            key=len, reverse=True)

    def propose(self, history, k):
        h = np.asarray(history, np.int64).reshape(-1)
        for seq in self._seqs:
            if seq.size > h.size and np.array_equal(seq[:h.size], h):
                return seq[h.size:h.size + k].copy()
        return np.zeros((0,), np.int64)


def spec_parity_outputs(model, make_ecfg, prompts, set_flags,
                        max_new_tokens=24, never_drafts_probe=True,
                        flags_extra=None, replay=False):
    """THE greedy spec-parity comparison: the same workload runs
    spec-off and spec-ngram (fresh engine per arm, ``make_ecfg()``
    builds each arm's config), returning ``({mode: outputs},
    {mode: spec_snapshot})``. ``never_drafts_probe`` appends a 1-token
    request whose budget leaves no draft headroom. ``flags_extra``
    merges extra serving flags into each arm (e.g. prefix_cache).
    ``replay`` gives the speculative arm a ``ReplayDrafter`` of the
    ``off`` arm's outputs in place of the engine's n-gram drafter.
    Callers restore flags via their ``serving_flags`` fixture."""
    outs, snaps = {}, {}
    asked = list(prompts) + ([prompts[0]] if never_drafts_probe else [])
    for mode in ("off", "ngram"):
        fl = {"spec_decode": mode}
        if flags_extra:
            fl.update(flags_extra)
        set_flags(fl)
        drafter = ReplayDrafter(asked, outs["off"]) \
            if replay and mode == "ngram" else None
        eng = ContinuousBatchingEngine(model, make_ecfg(), drafter=drafter)
        reqs = eng.run(prompts, max_new_tokens=max_new_tokens)
        if never_drafts_probe:
            reqs += eng.run([prompts[0]], max_new_tokens=1)
        outs[mode] = [r.output for r in reqs]
        snaps[mode] = eng.spec_snapshot()
    return outs, snaps


def assert_spec_parity(outs, snaps, require_accepts=True):
    """Spec-on greedy outputs must be bit-identical to spec-off — and
    the spec arm must actually have accepted drafts (or the comparison
    proves nothing), while the off arm must never have verified."""
    if require_accepts:
        assert snaps["ngram"]["verify_calls"] > 0
        assert snaps["ngram"]["accepted"] > 0
        assert snaps["ngram"]["emitted"] > snaps["ngram"]["verify_calls"]
    assert snaps["off"]["verify_calls"] == 0
    assert snaps["off"]["proposed"] == 0
    assert outs["ngram"] == outs["off"]
