"""ptaudit — jaxpr-level contract auditor for the compiled serving
program set.

ptlint (analysis/lint.py) checks the Python SOURCE and the sanitizer
checks runtime STATE; this module checks the *traced programs
themselves*. Every modeled perf claim in the bench ledger rests on
trace-level promises — in-place KV append via donation, int8/bf16
streams staying narrow until in-kernel dequant, no host transfers
inside a dispatch, a stable program size — and none of those is
visible to an AST scan or a state invariant. ptaudit traces each
program at small CPU-friendly shapes (the same tiny-engine helpers the
serving test suites use — ``tests/serving_utils.py`` imports them from
here) and walks the resulting jaxpr, enforcing one declarative
:data:`PROGRAM_CONTRACTS` entry per ``TRACE_COUNTS`` /
``PROGRAM_LABELS`` program name. ptlint's **PA001** rule keeps that
registry complete, the same shape as OBS001 for timing labels.

Rule families::

    AL001  a contract pool operand is not donated (input/output
           aliasing dropped -> a full pool copy per dispatch)
    AL002  a donated operand the contract does not declare (registry
           drift: the contract must mirror the program)
    DQ001  a narrow value stream (bf16/f16/int8/int4) widens at a
           dtype pair the contract does not allowlist
    DQ002  an allowlisted widening pair's count grew past the
           committed baseline (a new upcast site crept in)
    TX001  host callback/transfer primitive inside a serving program
           (io_callback/pure_callback/debug_callback/infeed/outfeed)
    DD001  dead input leaf the contract's ``dead_ok`` does not cover
    DD002  passthrough or constant output (costs a donation slot /
           a dispatch-time copy for nothing)
    DD003  unused trace constant captured into the program
    SZ001  program op-count grew past the committed baseline
    SZ002  program missing from the committed baseline

Usage::

    python -m paddle_tpu.analysis.audit                 # full repo set
    python -m paddle_tpu.analysis.audit --arms paged-int8 --json
    python -m paddle_tpu.analysis.audit --rules
    python -m paddle_tpu.analysis.audit --write-baseline

Exit status mirrors ptlint: 0 clean, 1 on any violation, 2 on usage
errors. The committed baseline (``.ptaudit-baseline.json``) records
per ``arm::program`` op counts and allowlisted-widening counts — the
CPU-backend trace is canonical (tier-1 runs ``JAX_PLATFORMS=cpu``; on
TPU the fused Pallas kernels change the op mix, so refresh locally
with ``--write-baseline`` before comparing there). Unlike ptlint's
baseline, SHRINKING is also a mismatch (`--write-baseline` to ratchet
down): the committed counts are an exact pin, so program-size drift in
either direction is reviewable in the diff.

Production engines self-audit after warmup via
``PT_FLAGS_audit_on_seal`` (default off = one identity check):
``engine.seal_programs()`` runs the AL/DQ/TX/DD families against the
engine's OWN programs at its real shapes (SZ needs the canonical tiny
arms, so it stays with the CLI) and surfaces the verdict in
``metrics_snapshot()["audit"]``. Audits are trace-only — no compile,
no dispatch — and restore ``TRACE_COUNTS``/``TRACE_SHAPES``, so the
recompile watchdog and the tests' compile-count guards never see them.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from ..kernels.decode_attention import AUDIT_WIDEN_ALLOW
from .lint import find_root

BASELINE_NAME = ".ptaudit-baseline.json"

RULE_DOCS: Dict[str, str] = {
    "AL001": "contract pool operands must be donated (in-place "
             "append / page-copy aliasing, verified structurally)",
    "AL002": "donated operands must be declared in the contract "
             "(the registry mirrors the program, both directions)",
    "DQ001": "narrow streams (bf16/f16/int8/int4) may widen only at "
             "allowlisted dtype pairs (softmax accumulators, "
             "scale-row dequant)",
    "DQ002": "allowlisted widening counts may not grow past the "
             "committed baseline (a new upcast site is a finding)",
    "TX001": "no host callbacks/transfers inside a serving program",
    "DD001": "no dead inputs beyond the contract's dead_ok "
             "(unused leaves still pay dispatch-time flattening)",
    "DD002": "no passthrough/constant outputs (each costs a donation "
             "slot or a device copy for nothing)",
    "DD003": "no unused trace constants captured into the program",
    "SZ001": "per-program op counts are pinned by the committed "
             "baseline (size creep is reviewable like ptlint's)",
    "SZ002": "every audited program must carry a baseline entry "
             "(--write-baseline)",
}


@dataclass
class AuditViolation:
    arm: str
    program: str
    rule: str
    message: str


class AuditError(RuntimeError):
    """A program could not be traced/analyzed at all — a broken probe
    or contract, never a contract *violation* (those report)."""


# ---------------------------------------------------------------------------
# contracts — one per TRACE_COUNTS / PROGRAM_LABELS program name
# (ptlint PA001 keeps this registry complete; the runtime twin in
# tests/test_program_audit.py pins it against PROGRAM_LABELS)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ProgramContract:
    #: cache modes the program exists in
    modes: Tuple[str, ...]
    #: top-level operand names whose EVERY leaf must be donated (AL)
    donate: Tuple[str, ...] = ()
    #: "src->dst" -> justification for a monitored widening pair (DQ)
    widen_allow: Mapping[str, str] = field(default_factory=dict)
    #: fnmatch patterns over leaf labels allowed to be dead (DD001)
    dead_ok: Tuple[str, ...] = ()
    #: fnmatch patterns over leaf labels allowed to pass through (DD002)
    passthrough_ok: Tuple[str, ...] = ()
    note: str = ""


# the static no-sampling arm keeps per-slot sampling params on the
# signature so both arms share one call site; greedy engine-global
# traces leave them (and the PRNG key) unused BY DESIGN
_GREEDY_DEAD = ("key", "samp*")
# contig mode: block tables ride the shared paged/contig signature as
# a [1] sentinel so the two modes keep one call-site shape
_BT_SENTINEL = ("bt",)

PROGRAM_CONTRACTS: Dict[str, ProgramContract] = {
    "prefill_chunk": ProgramContract(
        modes=("paged", "contig"),
        donate=("caches",),
        widen_allow=AUDIT_WIDEN_ALLOW,
        dead_ok=_GREEDY_DEAD + _BT_SENTINEL,
        note="THE [slots, C] chunked prefill: writes straight into "
             "the live global cache at per-slot offsets",
    ),
    "prefill_bucket": ProgramContract(
        modes=("paged", "contig"),
        donate=("caches",),
        widen_allow=AUDIT_WIDEN_ALLOW,
        dead_ok=_GREEDY_DEAD,
        note="legacy per-bucket prefill (the parity oracle) fills a "
             "fresh single-sequence bucket cache in place — the "
             "missing donation here was ptaudit's first real finding",
    ),
    "prefill_insert": ProgramContract(
        modes=("contig",),
        donate=("global_caches",),
        note="pure data movement: bucket cache -> slot rows; no "
             "compute, so no widening is allowed at all",
    ),
    "prefill_scatter": ProgramContract(
        modes=("paged",),
        donate=("layer_caches",),
        note="pure data movement: bucket cache -> the slot's pages",
    ),
    "prefix_insert": ProgramContract(
        modes=("contig",),
        donate=("global_caches",),
        note="cached prefix block -> slot rows (int8 blocks carry "
             "their scale rows; both insert via the same program)",
    ),
    "prefix_read": ProgramContract(
        modes=("contig",),
        donate=(),
        note="read-only: slices a slot's rows into the store's "
             "materialized block — donating would free live cache",
    ),
    "page_copy": ProgramContract(
        modes=("paged",),
        donate=("layer_caches",),
        note="copy-on-write page duplication; scale rows ride along "
             "— an undonated pool here is a full-pool copy per COW",
    ),
    "decode_step": ProgramContract(
        modes=("paged", "contig"),
        donate=("caches",),
        widen_allow=AUDIT_WIDEN_ALLOW,
        dead_ok=_GREEDY_DEAD,
        note="the [slots, 1] decode program (PR-3 in-place append "
             "promise, verified structurally here)",
    ),
    "decode_chunk": ProgramContract(
        modes=("paged", "contig"),
        donate=("caches",),
        widen_allow=AUDIT_WIDEN_ALLOW,
        dead_ok=_GREEDY_DEAD + _BT_SENTINEL,
        note="K-step fused decode (lax.scan); the scan carries the "
             "donated pool through every step on device",
    ),
    "spec_verify": ProgramContract(
        modes=("paged", "contig"),
        donate=("caches",),
        widen_allow=AUDIT_WIDEN_ALLOW,
        dead_ok=_GREEDY_DEAD + _BT_SENTINEL,
        note="the [slots, spec_k+1] verify pass appends every row's "
             "K/V in place; rollback is a host length decrement",
    ),
}


# ---------------------------------------------------------------------------
# tiny-engine helpers (shared with tests/serving_utils.py — ONE source
# of truth for the CPU-friendly shapes the audits and the serving
# suites trace at)
# ---------------------------------------------------------------------------
def tiny_model(seed: int = 0):
    """A tiny llama + its config, deterministically seeded."""
    import paddle_tpu as pt
    from ..models import LlamaConfig, LlamaForCausalLM

    pt.seed(seed)
    cfg = LlamaConfig.tiny()
    return LlamaForCausalLM(cfg), cfg


def tiny_engine_config(paged: bool, **kw):
    """The canonical tiny EngineConfig (2 slots, 128 max_len, 8-token
    pages) every serving test suite and audit arm builds on."""
    from ..inference.serving import EngineConfig

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 128)
    kw.setdefault("seq_buckets", (32,))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("page_size", 8)
    return EngineConfig(paged=paged, **kw)


# the canonical audit arms: both cache modes at bf16, plus the fully
# quantized paged arm (int8 weights x int8 KV — contig rejects int8
# pools at init, so there is no contig-int8 arm to audit)
AUDIT_ARMS: Dict[str, dict] = {
    "contig-bf16": dict(paged=False, cache_dtype=jnp.bfloat16),
    "paged-bf16": dict(paged=True, cache_dtype=jnp.bfloat16),
    "paged-int8": dict(paged=True, cache_dtype="int8",
                       weight_dtype="int8"),
}

# serving flags that shape the traced programs: pinned to their
# registry defaults for the audit arms so the committed baseline is
# reproducible regardless of ambient flag state (callers' flags are
# restored afterwards)
_PINNED_FLAGS = ("prefill_chunk", "fused_decode", "prefix_cache",
                 "spec_decode", "kv_cache_dtype", "serve_weight_dtype")


def build_audit_engine(arm: str, model=None):
    """Build the tiny engine for one canonical audit arm (the caller
    pins flags; :func:`audit_repo` does this for you)."""
    from ..inference.serving import ContinuousBatchingEngine

    if arm not in AUDIT_ARMS:
        raise AuditError(
            f"unknown audit arm {arm!r} (have {sorted(AUDIT_ARMS)})")
    if model is None:
        model, _ = tiny_model()
    return ContinuousBatchingEngine(
        model, tiny_engine_config(**AUDIT_ARMS[arm]))


# ---------------------------------------------------------------------------
# probes: representative example args per program, built from the
# engine's own shapes/state — tracing inputs only, nothing dispatches
# ---------------------------------------------------------------------------
@dataclass
class Probe:
    fn: object          # the engine's jitted wrapper
    args: tuple         # example args (static values included in place)
    static_argnums: Tuple[int, ...]
    argnames: Tuple[str, ...]  # names of the DYNAMIC args, in order


def _samp_vectors(n: int):
    return (jnp.zeros((n,), bool), jnp.ones((n,), jnp.float32),
            jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.float32))


def _probe_common(eng):
    cfg = eng.cfg
    lens = jnp.zeros((cfg.max_slots,), jnp.int32)
    if cfg.paged:
        bt = jnp.asarray(eng.pool.block_tables)
        caches = eng.layer_caches
    else:
        bt = jnp.zeros((1,), jnp.int32)
        caches = eng.caches
    return lens, bt, caches, _samp_vectors(cfg.max_slots), \
        jax.random.PRNGKey(0)


def _probe_decode_step(eng):
    from ..inference.paged import PagedState

    lens, _bt, caches, samp, key = _probe_common(eng)
    toks = jnp.zeros((eng.cfg.max_slots, 1), jnp.int32)
    third = PagedState(block_tables=jnp.asarray(eng.pool.block_tables),
                       seq_lens=lens) if eng.cfg.paged else lens
    return Probe(eng._decode(),
                 (eng._pb, toks, caches, third, key, samp, False),
                 (6,), ("pb", "toks", "caches", "state_or_lens",
                        "key", "samp"))


def _probe_decode_chunk(eng):
    lens, bt, caches, samp, key = _probe_common(eng)
    slots = eng.cfg.max_slots
    toks = jnp.zeros((slots, 1), jnp.int32)
    active = jnp.zeros((slots,), bool)
    budget = jnp.zeros((slots,), jnp.int32)
    # K=2 keeps the scan trace tiny; the contract properties are
    # invariant to the (static) chunk length
    return Probe(eng._decode_n(),
                 (eng._pb, toks, caches, lens, active, budget, bt,
                  key, samp, 2, False),
                 (9, 10), ("pb", "toks", "caches", "lens", "active",
                           "budget", "bt", "key", "samp"))


def _probe_spec_verify(eng):
    lens, bt, caches, samp, key = _probe_common(eng)
    S = eng.cfg.spec_k + 1
    ids = jnp.zeros((eng.cfg.max_slots, S), jnp.int32)
    n_draft = jnp.zeros((eng.cfg.max_slots,), jnp.int32)
    return Probe(eng._verify(),
                 (eng._pb, ids, caches, bt, lens, n_draft, key, samp,
                  False),
                 (8,), ("pb", "ids", "caches", "bt", "start",
                        "n_draft", "key", "samp"))


def _probe_prefill_chunk(eng):
    if eng._chunk_len <= 0:
        # PT_FLAGS_prefill_chunk=0: the engine runs the legacy
        # per-bucket path and the [slots, C] program has no shape
        return "chunked prefill disabled (PT_FLAGS_prefill_chunk=0) " \
               "— the program never dispatches on this engine"
    lens, bt, caches, samp, key = _probe_common(eng)
    ids = jnp.zeros((eng.cfg.max_slots, eng._chunk_len), jnp.int32)
    last_idx = jnp.zeros((eng.cfg.max_slots,), jnp.int32)
    return Probe(eng._prefill_chunked(),
                 (eng._pb, ids, caches, bt, lens, last_idx, key, samp,
                  False),
                 (8,), ("pb", "ids", "caches", "bt", "start",
                        "last_idx", "key", "samp"))


_INT8_LEGACY_SKIP = ("legacy prefill path is rejected at init for "
                     "int8 pools — the program can never run in "
                     "this arm")


def _legacy_prefill_blocked(eng) -> bool:
    # int8 pools reject the legacy per-bucket prefill at engine init
    # (no quantize-on-append path) — those programs can never run, so
    # there is nothing to audit in the int8 arm
    return eng.cache_dtype == jnp.int8


def _one_bucket_avals(eng):
    # aval-only single-sequence bucket cache: eval_shape traces the
    # builder abstractly, so a production-size probe allocates nothing
    bucket = eng._buckets[0]
    return bucket, jax.eval_shape(
        lambda: eng.model.init_kv_caches(1, bucket,
                                         dtype=eng.cache_dtype))


def _probe_prefill_bucket(eng):
    if _legacy_prefill_blocked(eng):
        return _INT8_LEGACY_SKIP
    _lens, _bt, _caches, _samp, key = _probe_common(eng)
    bucket, one = _one_bucket_avals(eng)
    return Probe(eng._prefill(),
                 (eng._pb, jnp.zeros((1, bucket), jnp.int32), one,
                  bucket - 1, key, _samp_vectors(1), False),
                 (6,), ("pb", "ids", "caches", "last_idx", "key",
                        "samp"))


def _probe_prefill_insert(eng):
    if _legacy_prefill_blocked(eng):
        return _INT8_LEGACY_SKIP
    _bucket, one = _one_bucket_avals(eng)
    return Probe(eng._insert_contig(), (eng.caches, one, 0), (),
                 ("global_caches", "one_caches", "slot"))


def _probe_prefill_scatter(eng):
    if _legacy_prefill_blocked(eng):
        return _INT8_LEGACY_SKIP
    _bucket, one = _one_bucket_avals(eng)
    return Probe(eng._scatter_paged(),
                 (eng.layer_caches, one,
                  jnp.asarray(eng.pool.block_tables[0])),
                 (), ("layer_caches", "one_caches", "bt_row"))


def _probe_prefix_insert(eng):
    B = eng._prefix_block
    blk = jax.ShapeDtypeStruct(
        (eng._n_layers, B, eng._kvh, eng._hd),
        jnp.dtype(eng.cache_dtype))
    return Probe(eng._insert_prefix_contig(),
                 (eng.caches, blk, blk, 0, 0), (),
                 ("global_caches", "kblk", "vblk", "slot", "start"))


def _probe_prefix_read(eng):
    return Probe(eng._read_block_contig(), (eng.caches, 0, 0), (),
                 ("global_caches", "slot", "start"))


def _probe_page_copy(eng):
    return Probe(eng._copy_page(), (eng.layer_caches, 0, 1), (),
                 ("layer_caches", "src", "dst"))


_PROBES = {
    "decode_step": _probe_decode_step,
    "decode_chunk": _probe_decode_chunk,
    "spec_verify": _probe_spec_verify,
    "prefill_chunk": _probe_prefill_chunk,
    "prefill_bucket": _probe_prefill_bucket,
    "prefill_insert": _probe_prefill_insert,
    "prefill_scatter": _probe_prefill_scatter,
    "prefix_insert": _probe_prefix_insert,
    "prefix_read": _probe_prefix_read,
    "page_copy": _probe_page_copy,
}


def program_probe(engine, name: str):
    """The :class:`Probe` of one contracted program: the engine's own
    jitted wrapper with example args built from its shapes — what the
    auditor traces, for a caller that lowers or compiles the same
    program (``chip_smoke.py`` reads the Pallas kernel out of the
    compiled decode text). A declining probe returns its reason."""
    return _PROBES[name](engine)


# ---------------------------------------------------------------------------
# jaxpr analysis
# ---------------------------------------------------------------------------
# the narrow value-stream dtypes DQ monitors; index/bool arithmetic
# (int32 positions, bool masks) is not a value stream and stays out
_NARROW = {"bfloat16", "float16", "int8", "uint8", "int4", "uint4"}


def _dtype_name(d) -> str:
    try:
        return np.dtype(d).name
    except TypeError:
        return str(d)


def _monitored_widen(src: str, dst: str) -> bool:
    if src not in _NARROW:
        return False
    if src in ("bfloat16", "float16"):
        return dst in ("float32", "float64")
    # int8/int4: ANY float destination is a dequant-shaped widening —
    # bfloat16 included (it doesn't match "float*" by name, and
    # dequanting to the serving dtype is the most natural regression)
    return dst.startswith("float") or dst == "bfloat16"


def _is_literal(v) -> bool:
    return hasattr(v, "val")  # jaxpr Literals carry .val, Vars don't


def _walk(jxp, visit):
    """Depth-first over ``jxp``'s eqns and every sub-jaxpr hiding in
    eqn params — scan's single ClosedJaxpr, cond's TUPLE of branch
    jaxprs, custom-vjp bodies — so a callback or upcast cannot hide
    inside a branch."""

    def sub(v):
        if hasattr(v, "jaxpr"):              # ClosedJaxpr
            _walk(v.jaxpr, visit)
        elif hasattr(v, "eqns"):             # raw Jaxpr
            _walk(v, visit)
        elif isinstance(v, (tuple, list)):   # cond branches etc.
            for x in v:
                sub(x)

    for e in jxp.eqns:
        visit(e)
        for v in e.params.values():
            sub(v)


def _leaf_labels(args, static_argnums, argnames):
    """(root, label) per flattened dynamic-arg leaf, in invar order."""
    from jax import tree_util

    dyn = [a for i, a in enumerate(args) if i not in set(static_argnums)]
    if len(dyn) != len(argnames):
        raise AuditError(
            f"probe declares {len(argnames)} dynamic arg names but "
            f"{len(dyn)} dynamic args")
    out = []
    for name, a in zip(argnames, dyn):
        for path, _leaf in tree_util.tree_flatten_with_path(a)[0]:
            out.append((name, name + "".join(str(p) for p in path)))
    return out


def _allowed(label_pair, patterns) -> bool:
    root, label = label_pair
    return any(fnmatch.fnmatch(label, p) or root == p
               for p in patterns)


def audit_traced(program: str, fn, args, static_argnums, argnames,
                 contract: ProgramContract, *, arm: str = "engine",
                 baseline_entry: Optional[dict] = None,
                 check_size: bool = False):
    """Trace ``fn`` at ``args`` and audit the jaxpr against
    ``contract``. Returns ``(entry, violations)`` where ``entry`` is
    the report record (op counts, widenings, donation/dead views —
    ``eqns`` + ``widen`` are what the baseline pins). Trace-only: no
    compile, no dispatch, and the serving module's ``TRACE_COUNTS`` /
    ``TRACE_SHAPES`` are restored so compile accounting (watchdog,
    compile_counter guards) never sees the audit."""
    from ..inference import serving as S

    # restore is TARGETED, not a blanket snapshot rollback: tracing
    # ``program`` bumps exactly ITS key once — make_jaxpr opens its
    # own trace context, so the body re-runs even when the wrapper is
    # already warmed at these shapes (verified empirically on this
    # jax line; the audit-identity tests pin it) — so we subtract
    # only our own bump and restore only our own shape note. A
    # CONCURRENT engine's bump to any key (even the same one) during
    # the audit window survives the subtraction arithmetic, and its
    # recompile watchdog still sees what it must see
    before = S.TRACE_COUNTS.get(program, 0)
    shape_before = S.TRACE_SHAPES.get(program)
    had_shape = program in S.TRACE_SHAPES
    # abstract every array-shaped leaf down to its aval: the trace
    # needs only shapes/dtypes, and a seal-time audit on a production
    # engine must not transiently allocate anything (the legacy
    # bucket-cache probes would otherwise build real device buffers
    # at production shapes next to an HBM-full pool)
    args = tuple(
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape") and hasattr(x, "dtype") else x, a)
        if i not in set(static_argnums) else a
        for i, a in enumerate(args))
    ours = None
    try:
        closed = jax.make_jaxpr(
            fn, static_argnums=tuple(static_argnums))(*args)
        ours = S.TRACE_SHAPES.get(program)
    finally:
        if S.TRACE_COUNTS.get(program, 0) > before:
            S.TRACE_COUNTS[program] -= 1
            if S.TRACE_COUNTS[program] == 0:
                del S.TRACE_COUNTS[program]
        # shape-note restore is identity-guarded like the count
        # arithmetic: if a concurrent engine's recompile wrote a
        # FRESH note after our trace, that note must survive for its
        # watchdog artifact — we only roll back our own write
        if ours is not None and S.TRACE_SHAPES.get(program) \
                is not ours:
            pass
        elif had_shape:
            S.TRACE_SHAPES[program] = shape_before
        else:
            S.TRACE_SHAPES.pop(program, None)

    labels = _leaf_labels(args, static_argnums, argnames)
    eqns = closed.jaxpr.eqns
    # jax 0.9 names the jit call's primitive "jit" (it was "pjit");
    # its params still carry the closed inner jaxpr + donated_invars
    if len(eqns) == 1 and eqns[0].primitive.name == "jit" \
            and "jaxpr" in eqns[0].params:
        eq = eqns[0]
        inner = eq.params["jaxpr"].jaxpr
        donated_flags = eq.params.get(
            "donated_invars", (False,) * len(eq.invars))
        jitted = True
    else:
        inner = closed.jaxpr
        donated_flags = (False,) * len(inner.invars)
        jitted = False
    if len(inner.invars) != len(labels):
        raise AuditError(
            f"{arm}::{program}: traced {len(inner.invars)} invars but "
            f"probe flattens to {len(labels)} leaves — probe and "
            "program signature disagree")

    viol: List[AuditViolation] = []

    def v(rule, msg):
        viol.append(AuditViolation(arm, program, rule, msg))

    # ---- AL: donation both directions ----
    donated = {labels[i][0] for i, d in enumerate(donated_flags) if d}
    for name in contract.donate:
        idx = [i for i, (root, _l) in enumerate(labels)
               if root == name]
        if not idx:
            v("AL001", f"contract donates operand {name!r} but the "
                       "probe passes no such arg")
            continue
        missing = [labels[i][1] for i in idx if not donated_flags[i]]
        if missing:
            why = "" if jitted else " (program is not jit-wrapped — " \
                                    "nothing can alias)"
            v("AL001",
              f"pool operand {name!r} not donated: "
              f"{len(missing)}/{len(idx)} leaves un-aliased "
              f"(e.g. {missing[0]}){why} — every dispatch copies "
              "the pool instead of appending in place")
    for root in sorted(donated - set(contract.donate)):
        v("AL002",
          f"operand {root!r} is donated but the contract does not "
          "declare it — declare it (or stop donating): the contract "
          "must mirror the program")

    # ---- walk: op counts, widenings, callbacks ----
    n_eqns = 0
    widen: Counter = Counter()
    callbacks: List[str] = []

    def visit(e):
        nonlocal n_eqns
        n_eqns += 1
        name = e.primitive.name
        if name == "convert_element_type":
            src = _dtype_name(e.invars[0].aval.dtype)
            dst = _dtype_name(e.params["new_dtype"])
            if _monitored_widen(src, dst):
                widen[f"{src}->{dst}"] += 1
        elif name in ("dot_general", "conv_general_dilated"):
            # IMPLICIT widening: preferred_element_type lets a matmul
            # accumulate narrow operands straight into a wide output
            # with no convert eqn — the same stream-rewidening DQ
            # exists to catch, so it counts under the same pair
            order = ("int4", "uint4", "int8", "uint8", "float16",
                     "bfloat16")
            dst = _dtype_name(e.outvars[0].aval.dtype)
            srcs = sorted({_dtype_name(v.aval.dtype) for v in e.invars
                           if hasattr(v.aval, "dtype")
                           and _monitored_widen(
                               _dtype_name(v.aval.dtype), dst)},
                          key=order.index)
            if srcs:  # charge the narrowest operand's stream
                widen[f"{srcs[0]}->{dst}"] += 1
        if "callback" in name or name in ("infeed", "outfeed"):
            callbacks.append(name)

    _walk(inner, visit)

    # ---- TX ----
    for name in sorted(set(callbacks)):
        v("TX001",
          f"host callback/transfer primitive {name!r} "
          f"(x{callbacks.count(name)}) inside the program — serving "
          "dispatches must stay fully on-device/async")

    # ---- DQ ----
    for pair, count in sorted(widen.items()):
        if pair not in contract.widen_allow:
            v("DQ001",
              f"narrow stream widens {pair} x{count} with no "
              "contract allowance — a hidden upcast re-widens the "
              "bytes the perf models price as narrow (allowlist it "
              "in PROGRAM_CONTRACTS with a justification, or fix it)")
    if baseline_entry is not None:
        # exact pin, like SZ001: a count SHRINK left unpinned would be
        # silent headroom for a later upcast site to creep back into
        base_widen = baseline_entry.get("widen", {})
        for pair in sorted(set(widen) | set(base_widen)):
            count, base = int(widen.get(pair, 0)), \
                int(base_widen.get(pair, 0))
            if pair not in contract.widen_allow:
                # present-and-unallowlisted is DQ001's job; but a pin
                # whose pair vanished (site + allowance removed
                # together) must not ride the baseline forever
                if count == 0 and base > 0:
                    v("DQ002",
                      f"baseline pins widening {pair} x{base} but "
                      "the program no longer widens there — stale "
                      "pin, --write-baseline")
                continue
            if count != base:
                how = "grew" if count > base else "shrank"
                v("DQ002",
                  f"allowlisted widening {pair} {how} "
                  f"{base} -> {count} vs the baseline — review the "
                  "change and --write-baseline (a new upcast site "
                  "must not hide behind an existing allowance)")

    # ---- DD ----
    # an input the body hands straight back is a passthrough (DD002
    # below), not a read: with nothing else using it, it is dead too
    invar_ids = {id(var): labels[i][1]
                 for i, var in enumerate(inner.invars)}
    used = set()
    for e in inner.eqns:
        for var in e.invars:
            if not _is_literal(var):
                used.add(id(var))
    for var in inner.outvars:
        if not _is_literal(var) and id(var) not in invar_ids:
            used.add(id(var))
    dead = [labels[i] for i, var in enumerate(inner.invars)
            if id(var) not in used]
    for pair in dead:
        if not _allowed(pair, contract.dead_ok):
            v("DD001",
              f"dead input {pair[1]!r}: the program never reads it "
              "but every dispatch flattens and ships it — drop it "
              "from the signature or allowlist it in dead_ok with "
              "a justification")
    # passthrough outputs are detected on the INNER jaxpr: jax 0.9
    # keeps a returned-unchanged input inside the jit call (the outer
    # eqn binds a fresh outvar for it), so only the body shows an
    # outvar that IS an invar
    for j, var in enumerate(inner.outvars):
        if not _is_literal(var) and id(var) in invar_ids:
            lab = invar_ids[id(var)]
            if not _allowed((lab.split("[")[0].split(".")[0], lab),
                            contract.passthrough_ok):
                v("DD002",
                  f"output [{j}] passes input {lab!r} through "
                  "unchanged — it costs a donation slot / device "
                  "copy for nothing")
    # constant outputs: forward-propagate input dependence through
    # the inner eqns; an output no input reaches (a Literal, or a
    # value computed purely from trace constants) ships a dispatch
    # for something the host already knows
    dep = {id(var) for var in inner.invars}
    for e in inner.eqns:
        if any(not _is_literal(var) and id(var) in dep
               for var in e.invars):
            dep.update(id(o) for o in e.outvars)
    for j, var in enumerate(inner.outvars):
        if _is_literal(var) or id(var) not in dep:
            v("DD002",
              f"output [{j}] is a trace-time constant — compute it "
              "on the host instead of shipping a dispatch for it")
    dead_consts = [i for i, var in enumerate(inner.constvars)
                   if id(var) not in used]
    for i in dead_consts:
        v("DD003", f"trace constant [{i}] is captured but unused")

    # ---- SZ ----
    entry = {"eqns": n_eqns,
             "widen": {k: int(widen[k]) for k in sorted(widen)}}
    if check_size:
        if baseline_entry is None:
            v("SZ002",
              f"no baseline entry for {arm}::{program} — run "
              "--write-baseline and commit the diff")
        elif n_eqns != int(baseline_entry.get("eqns", -1)):
            base = int(baseline_entry.get("eqns", -1))
            how = "grew" if n_eqns > base else "shrank"
            v("SZ001",
              f"program op count {how} {base} -> {n_eqns} eqns vs "
              "the committed baseline — review the size change and "
              "--write-baseline")
    report = dict(entry)
    report["donated"] = sorted(donated)
    report["dead"] = sorted(lab for _r, lab in dead)
    return report, viol


# ---------------------------------------------------------------------------
# engine / repo auditors
# ---------------------------------------------------------------------------
def audit_engine(engine, arm: str = "engine",
                 baseline: Optional[Dict[str, dict]] = None) -> dict:
    """Audit every contracted program this engine can dispatch. SZ
    (op-count pinning) runs only when ``baseline`` entries are given —
    a production engine's op counts depend on its model, so size pins
    stay with the canonical tiny arms."""
    mode = "paged" if engine.cfg.paged else "contig"
    out = {"arm": arm, "programs": {}, "skipped": {}, "violations": []}
    for name in sorted(PROGRAM_CONTRACTS):
        contract = PROGRAM_CONTRACTS[name]
        if mode not in contract.modes:
            out["skipped"][name] = f"not a {mode}-mode program"
            continue
        builder = _PROBES.get(name)
        if builder is None:
            # PA001 forces a contract for every new program; nothing
            # static forces the probe — fail with the actionable
            # message, not a KeyError (the registry-completeness test
            # pins set(_PROBES) == set(PROGRAM_CONTRACTS) so this is
            # unreachable from the committed tree)
            raise AuditError(
                f"contracted program {name!r} has no probe — add a "
                "_PROBES entry in analysis/program_audit.py so the "
                "auditor can trace it")
        probe = builder(engine)
        if not isinstance(probe, Probe):
            # a probe may decline with a reason string (legacy path
            # blocked at init, chunked prefill disabled, ...): the
            # program cannot dispatch on THIS engine, so there is
            # nothing to audit — recorded, never silent
            out["skipped"][name] = probe or "probe declined"
            continue
        key = f"{arm}::{name}"
        entry, viol = audit_traced(
            name, probe.fn, probe.args, probe.static_argnums,
            probe.argnames, contract, arm=arm,
            baseline_entry=None if baseline is None
            else baseline.get(key),
            check_size=baseline is not None)
        out["programs"][name] = entry
        out["violations"].extend(viol)
    return out


def audit_repo(arms: Optional[Sequence[str]] = None,
               baseline: Optional[Dict[str, dict]] = None,
               use_baseline: bool = True) -> dict:
    """Audit the canonical tiny arms (the repo's real serving program
    set). Serving flags that shape the traces are pinned to their
    registry defaults for the duration and restored after, so the
    result is reproducible from any caller (CLI, bench, tests)."""
    arm_names = list(arms) if arms is not None else list(AUDIT_ARMS)
    bad = [a for a in arm_names if a not in AUDIT_ARMS]
    if bad:
        raise AuditError(
            f"unknown audit arm(s) {bad} (have {sorted(AUDIT_ARMS)})")
    if baseline is None and use_baseline:
        baseline = load_baseline(
            os.path.join(find_root(os.path.dirname(__file__)),
                         BASELINE_NAME))
    from ..core import random as _rng

    saved = {n: flags.flag(n) for n in _PINNED_FLAGS}
    flags.set_flags({n: flags.registry()[n]["default"]
                     for n in _PINNED_FLAGS})
    # tiny_model() seeds the global eager RNG stream; the audit must
    # not leak that side effect into the caller's run any more than
    # a flag flip (same save/restore contract)
    saved_state = (_rng._ensure_state().seed,
                   _rng._ensure_state().counter)
    try:
        model, _ = tiny_model()
        report = {"arms": {}, "entries": {}, "violations": []}
        for arm in arm_names:
            eng = build_audit_engine(arm, model=model)
            r = audit_engine(eng, arm=arm, baseline=baseline)
            report["arms"][arm] = r
            for name, entry in r["programs"].items():
                report["entries"][f"{arm}::{name}"] = {
                    "eqns": entry["eqns"], "widen": entry["widen"]}
            report["violations"].extend(r["violations"])
        return report
    finally:
        flags.set_flags(saved)
        st = _rng._ensure_state()
        st.seed, st.counter = saved_state


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------
def load_baseline(path: str) -> Dict[str, dict]:
    """Missing file = empty; a PRESENT but malformed file is a loud
    error, never a vacuously clean audit (ptlint's rule)."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        return {str(k): {"eqns": int(v["eqns"]),
                         "widen": {str(p): int(c)
                                   for p, c in v.get("widen",
                                                     {}).items()}}
                for k, v in data.get("entries", {}).items()}
    except OSError:
        return {}
    except (ValueError, TypeError, KeyError, AttributeError) as e:
        raise ValueError(
            f"invalid ptaudit baseline file {path}: {e} — fix it or "
            "regenerate with --write-baseline") from e


def write_baseline(path: str, entries: Dict[str, dict]):
    payload = {
        "comment": ("ptaudit per-program op-count / allowlisted-"
                    "widening pins, keyed arm::program; the CPU-"
                    "backend trace at the canonical tiny arms is "
                    "canonical. Regenerate with `python -m "
                    "paddle_tpu.analysis.audit --write-baseline` and "
                    "review the diff like any size change."),
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=False)
        f.write("\n")


# ---------------------------------------------------------------------------
# CLI (python -m paddle_tpu.analysis.audit — see audit.py)
# ---------------------------------------------------------------------------
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="ptaudit",
        description="paddle_tpu jaxpr-level contract audit of the "
                    "compiled serving program set (aliasing, dtype "
                    "discipline, transfer bans, size budgets)")
    ap.add_argument("--arms", default=None,
                    help="comma-separated arm subset "
                         f"(default: {','.join(AUDIT_ARMS)})")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: <root>/"
                         f"{BASELINE_NAME})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip SZ/DQ002 baseline comparisons")
    ap.add_argument("--write-baseline", action="store_true",
                    help="pin the current op/widening counts")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--rules", action="store_true", dest="list_rules",
                    help="list rule ids and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rid, doc in sorted(RULE_DOCS.items()):
            print(f"{rid}  {doc}")
        return 0
    arm_names = [a.strip() for a in args.arms.split(",")] \
        if args.arms else None
    if arm_names:
        bad = [a for a in arm_names if a not in AUDIT_ARMS]
        if bad:
            print(f"ptaudit: unknown arm(s) {bad} "
                  f"(have {sorted(AUDIT_ARMS)})", file=sys.stderr)
            return 2
    root = find_root(os.path.dirname(os.path.abspath(__file__)))
    baseline_path = args.baseline or os.path.join(root, BASELINE_NAME)
    try:
        baseline = {} if (args.no_baseline or args.write_baseline) \
            else load_baseline(baseline_path)
    except ValueError as e:
        print(f"ptaudit: {e}", file=sys.stderr)
        return 2

    try:
        report = audit_repo(
            arms=arm_names,
            baseline=None if (args.no_baseline or args.write_baseline)
            else baseline,
            use_baseline=not (args.no_baseline
                              or args.write_baseline))
    except AuditError as e:
        # a broken probe/contract is a TOOLING error with an
        # actionable message, never a silent traceback or a clean exit
        print(f"ptaudit: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        # merge: a partial-arm run must not drop the OTHER arms' pins,
        # but within the arms just audited, stale pins (deleted or
        # renamed programs) are PRUNED — a dead entry nothing audits
        # would otherwise outlive its program and ambush a future
        # re-add with a years-stale SZ001 comparison. A corrupt
        # existing file must not kill the one command that can fix
        # it — warn and regenerate from this run's entries
        try:
            merged = load_baseline(baseline_path)
        except ValueError as e:
            print(f"ptaudit: replacing malformed baseline: {e}",
                  file=sys.stderr)
            merged = {}
        audited = tuple(f"{a}::" for a in report["arms"])
        merged = {k: v for k, v in merged.items()
                  if not k.startswith(audited)}
        merged.update(report["entries"])
        write_baseline(baseline_path, merged)
        print(f"ptaudit: wrote {len(report['entries'])} program "
              f"pin(s) to {baseline_path}")
        # the baseline can only accept SIZE/creep pins — structural
        # violations (AL/DQ001/TX/DD) the same audit found are not
        # waivable by re-pinning and must not ride out silently
        structural = report["violations"]
        if structural:
            for x in structural:
                print(f"{x.arm}::{x.program}: {x.rule} {x.message}")
            print(f"ptaudit: {len(structural)} structural "
                  "violation(s) remain — a baseline write cannot "
                  "accept these", file=sys.stderr)
            return 1
        return 0

    viol = report["violations"]
    if args.as_json:
        print(json.dumps({
            "arms": {a: {"programs": r["programs"],
                         "skipped": r["skipped"]}
                     for a, r in report["arms"].items()},
            "violations": [x.__dict__ for x in viol],
        }, indent=2))
        return 1 if viol else 0
    for x in viol:
        print(f"{x.arm}::{x.program}: {x.rule} {x.message}")
    n_prog = sum(len(r["programs"]) for r in report["arms"].values())
    n_skip = sum(len(r["skipped"]) for r in report["arms"].values())
    print(f"ptaudit: {len(report['arms'])} arm(s), {n_prog} "
          f"program(s) audited ({n_skip} skipped), {len(viol)} "
          "violation(s)")
    return 1 if viol else 0
