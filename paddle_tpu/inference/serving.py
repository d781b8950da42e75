"""Continuous-batching decode engine (parity: the reference's serving
decode path — phi ``fused_multi_transformer`` + ``masked_multihead_
attention``'s batched per-sequence caches, as driven by FastDeploy-style
servers; upgraded with a paged KV pool).

TPU-native shape discipline: ONE compiled decode program with a static
``[slots, 1]`` token batch serves the whole lifetime of the engine.
Sequences enter and leave *as data*: per-slot lengths, an active mask,
and (paged mode) block tables are device arrays the host scheduler
updates — no shape ever changes, so nothing recompiles. Prefill keeps
the same discipline: ONE compiled fixed-size ``[slots, prefill_chunk]``
program writes straight into the live cache at vector per-slot offsets,
driven in a host loop — compute ∝ suffix rounded to the chunk (not the
seq bucket), several queued requests' chunks pack into one call, and
everything dispatches behind the in-flight decode chunk. Admission
first consults the PREFIX CACHE (``prefix_cache.py``): the longest
cached block-aligned prompt prefix is shared into the slot (paged:
refcounted pages, copy-on-write; contiguous: copied blocks) and only
the suffix is prefilled. ``PT_FLAGS_prefill_chunk=0`` restores the
legacy per-bucket prefill — the parity oracle.
"""

from __future__ import annotations

import bisect
import collections
import heapq
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags, generation as G, observability
from ..core.functional import (
    extract_buffers,
    extract_params,
    functional_call,
)
from ..core.module import Layer
from .paged import PagedLayerCache, PagedState, PagePool, init_paged_pool
from .prefix_cache import ContigPrefixStore, PagedPrefixStore, block_hashes
from .resilience import (
    CORRUPT_SITES,
    RUNTIME_ERRORS,
    DegradationController,
    FaultInjector,
    InjectedFault,
)
from .spec_decode import Drafter, NgramDrafter

# trace-time compile accounting: each compiled-program body bumps its
# counter exactly once per jit SPECIALIZATION (python runs at trace
# time only) — the tests' compile-count guard reads deltas here to
# assert chunked prefill never re-specializes across prompt lengths
TRACE_COUNTS: collections.Counter = collections.Counter()

# trace-time shape notes, one per program: the MOST RECENT
# specialization's key arg shapes, recorded next to the compile-count
# bump (python runs at trace time only, so this is free at dispatch
# time). The runtime recompile watchdog attaches this to its
# FlightRecorder artifact — a post-seal recompile dump names the
# offending shapes, not just the program
TRACE_SHAPES: Dict[str, dict] = {}


def _shape_note(program: str, **args):
    """Record the traced args' shapes for ``program`` (called from
    inside jitted bodies, at trace time only)."""
    TRACE_SHAPES[program] = {
        k: tuple(getattr(v, "shape", ())) for k, v in args.items()}


@dataclass
class EngineConfig:
    max_slots: int = 4
    max_len: int = 1024
    seq_buckets: Sequence[int] = (64, 128, 256, 512, 1024)
    paged: bool = False
    # paged mode: tokens per KV page. Contiguous mode reuses it as the
    # prefix-cache block granularity (rolling-hash block length)
    page_size: int = 64
    n_pages: Optional[int] = None  # default: slots*max_len/page_size (+sink)
    # "auto" resolves through PT_FLAGS_kv_cache_dtype: bf16 on TPU
    # (halves decode KV traffic), fp32 elsewhere; explicit dtypes win.
    # "int8" builds quantized pools with per-row f32 scales alongside
    # (quantize-on-append, in-kernel dequant) — requires the chunked
    # prefill path and single-chip serving, both validated at init
    cache_dtype: object = "auto"
    # serving weight stream: "auto" resolves through
    # PT_FLAGS_serve_weight_dtype (default bf16 = the model's own
    # weights). int8/int4 group-wise weight-only quantization happens
    # at ENGINE INIT via quantize_model_weight_only — qweights+scales
    # are buffers, so they ride every compiled program as jit
    # arguments (the seam below) and dequantize in-kernel
    weight_dtype: str = "auto"
    # group size for the weight-only quantization's group-wise scales
    # (layers whose in_features don't divide it fall back to one
    # degenerate whole-column group, same rule as WeightOnlyLinear)
    weight_group_size: int = 128
    # quantize the CALLER'S model tree in place (frees the fp linears
    # as they are replaced — the right trade for a 7B model that fits
    # HBM only once). Default False: the engine deep-copies first, so
    # the caller's model stays servable at full precision (A/B benches
    # and tests build bf16 and int8 engines from ONE model)
    quantize_inplace: bool = False
    # contiguous-mode prefix store cap (blocks of materialized
    # per-layer K/V — real device memory on top of the engine's own
    # cache); None = a QUARTER engine's worth
    # (max_slots * max_len / page_size / 4), so the default can't
    # silently double an engine sized near HBM capacity. Paged mode
    # needs no cap: pool pressure evicts.
    prefix_cache_blocks: Optional[int] = None
    greedy: bool = True
    temperature: float = 1.0
    seed: int = 0
    # speculative decoding (PT_FLAGS_spec_decode): max draft tokens per
    # slot per verify pass — the verify program's fixed token width is
    # spec_k + 1 (drafts + the last accepted token), so this is a
    # compile-time shape, not a runtime knob
    spec_k: int = 4
    # crash recovery (PT_FLAGS_serve_recovery): how many times a
    # request may be re-queued for deterministic replay after a
    # quarantined step before it finishes with reason "failed";
    # add_request(max_retries=) overrides per request
    max_retries: int = 2


def _resolve_cache_dtype(requested):
    """EngineConfig.cache_dtype → concrete dtype. ``"auto"`` defers to
    the ``PT_FLAGS_kv_cache_dtype`` flag (auto = bfloat16 on TPU,
    float32 elsewhere — decode is KV-bandwidth-bound, so the cache
    dtype IS the decode traffic); explicit dtypes pass through.
    ``"int8"`` selects quantized KV pools (per-row f32 scales stored
    alongside; quantize-on-append, dequant in-kernel)."""
    named = {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
             "float16": jnp.float16, "fp16": jnp.float16,
             "float32": jnp.float32, "fp32": jnp.float32,
             "int8": jnp.int8}

    def lookup(val, origin):
        if val not in named:
            raise ValueError(
                f"{origin} must be 'auto' or one of {sorted(named)}; "
                f"got {val!r}")
        return named[val]

    if isinstance(requested, str) and requested != "auto":
        return lookup(requested, "EngineConfig.cache_dtype")
    if requested not in (None, "auto"):
        return requested
    val = str(flags.flag("kv_cache_dtype")).lower()
    if val == "auto":
        return (jnp.bfloat16 if jax.default_backend() == "tpu"
                else jnp.float32)
    return lookup(val, "PT_FLAGS_kv_cache_dtype")


_WEIGHT_DTYPES = ("bf16", "int8", "int4")


def _resolve_weight_dtype(requested) -> str:
    """EngineConfig.weight_dtype → "bf16" | "int8" | "int4".
    ``"auto"`` defers to ``PT_FLAGS_serve_weight_dtype``; "bf16" means
    "serve the model's weights as they are" (no quantization pass)."""
    origin = "EngineConfig.weight_dtype"
    if requested in (None, "auto"):
        requested = flags.flag("serve_weight_dtype")
        origin = "PT_FLAGS_serve_weight_dtype"
    val = str(requested).lower()
    if val == "bfloat16":
        val = "bf16"
    if val not in _WEIGHT_DTYPES:
        raise ValueError(
            f"{origin} must be 'auto' or one of {list(_WEIGHT_DTYPES)}; "
            f"got {requested!r}")
    return val


def _validate_buckets(cfg: "EngineConfig") -> List[int]:
    """seq_buckets sanity at engine init: entries must be positive
    ints; the working table is normalized (sorted, deduped, clamped to
    max_len) so unsorted input can't break the bisect lookup and an
    oversized bucket can't over-allocate a one-shot prefill cache."""
    buckets = list(cfg.seq_buckets)
    if not buckets:
        raise ValueError("EngineConfig.seq_buckets must be non-empty")
    for b in buckets:
        if isinstance(b, bool) or not isinstance(b, (int, np.integer)) \
                or b <= 0:
            raise ValueError(
                f"EngineConfig.seq_buckets entries must be positive "
                f"ints; got {b!r}")
    return sorted({min(int(b), cfg.max_len) for b in buckets})


# per-request SLO classes (ROADMAP item 5): default TTFT / per-request
# TPOT targets per class; explicit add_request targets override. The
# engine only ACCOUNTS attainment here (pt_serve_slo_* counters,
# slo_snapshot, goodput) — the SLO-aware scheduler that acts on these
# classes is the next PR, and it reads exactly this bookkeeping.
SLO_CLASSES: Dict[str, Dict[str, float]] = {
    # deadline_ms is the class's default HARD deadline (enforced by
    # the scheduler: the request finishes with reason "timeout" and
    # its slot/pages/prefix refs are released), distinct from the
    # soft attainment targets above; add_request(deadline_ms=)
    # overrides, untracked requests default to no deadline
    "interactive": {"ttft_target_ms": 250.0, "tpot_target_ms": 100.0,
                    "deadline_ms": 30_000.0},
    "batch": {"ttft_target_ms": 5000.0, "tpot_target_ms": 1000.0,
              "deadline_ms": 300_000.0},
}


def new_slo_bucket() -> Dict[str, int]:
    """One per-class SLO accounting bucket. Engine- and fleet-level
    ``slo_stats`` share this shape (the router's ``slo_snapshot``
    merges replica buckets key-by-key), so a key added here reaches
    both sides at once."""
    return {
        "met": 0, "violated": 0, "cancelled": 0,
        "ttft_violations": 0, "tpot_violations": 0,
        "timeouts": 0, "met_tokens": 0, "total_tokens": 0,
    }


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    output: List[int] = field(default_factory=list)
    ttft_ms: Optional[float] = None
    slot: Optional[int] = None
    done: bool = False
    cancelled: bool = False
    # why the request left its slot: eos | max_new_tokens | max_len |
    # cancel | timeout | failed (None while in flight)
    finish_reason: Optional[str] = None
    # hard deadline: wall-clock budget from submission; the scheduler
    # expires the request (queued OR mid-decode) once it passes,
    # freeing slot/pages/prefix refs through the one teardown path
    deadline_ms: Optional[float] = None
    # per-request replay-retry bound (None = EngineConfig.max_retries)
    max_retries: Optional[int] = None
    # multi-tenant identity (None = the anonymous shared tenant "-"):
    # drives the SLO-fair scheduler's weighted fair share + quotas,
    # the per-tenant prefix-cache namespace, and the tenant label on
    # serve metrics — never the compiled programs (pure host policy)
    tenant: Optional[str] = None
    # SLO class + targets (None = untracked); tpot_ms is the
    # per-request mean decode latency, computed once at finish
    slo: Optional[str] = None
    ttft_target_ms: Optional[float] = None
    tpot_target_ms: Optional[float] = None
    tpot_ms: Optional[float] = None
    slo_met: Optional[bool] = None
    # per-request sampling params (None = engine-global config). Any
    # explicit temperature/top_k/top_p implies sampling for this
    # request; ``greedy`` overrides that inference either way.
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    greedy: Optional[bool] = None
    # attributed device cost (ms), accumulated step by step: each
    # step's measured program-ms (profiler sample; sync-wall estimate
    # on unsampled steps) split across the requests the step advanced,
    # proportional to tokens advanced. device_ms_profiled is the
    # portion backed by MEASURED samples (the rest is the honest
    # host-wall upper bound). Travels in request_ledger, so cost
    # survives failover/drain handoffs.
    device_ms: float = 0.0
    device_ms_profiled: float = 0.0
    _submit_t: float = 0.0
    _admit_t: float = 0.0
    # absolute deadline instant (perf_counter seconds; 0 = none)
    _deadline_t: float = 0.0
    # finish-time cost already recorded (idempotency guard: a request
    # can reach a terminal path more than once across flush points)
    _cost_recorded: bool = False
    # replay re-queues consumed so far (crash recovery)
    _retries: int = 0
    # prompt block digests, computed once — a pool-blocked request is
    # re-matched every scheduler tick and must not re-hash each time
    _hashes: Optional[List[bytes]] = None
    # speculative-decoding accounting (drives the auto-mode throttle
    # and the engine's acceptance stats)
    _spec_proposed: int = 0
    _spec_accepted: int = 0


def build_request(rid: int, prompt, max_new_tokens: int = 32,
                  eos_token_id: Optional[int] = None,
                  temperature: Optional[float] = None,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None,
                  greedy: Optional[bool] = None,
                  tenant: Optional[str] = None,
                  slo: Optional[str] = None,
                  ttft_target_ms: Optional[float] = None,
                  tpot_target_ms: Optional[float] = None,
                  deadline_ms: Optional[float] = None,
                  max_retries: Optional[int] = None,
                  *, max_len: int) -> Request:
    """Validate request arguments and construct a :class:`Request` —
    THE admission validation, factored out of ``add_request`` so the
    multi-engine router (``router.py``) applies the exact same checks
    when it builds a request before picking a replica. ``rid`` is the
    caller's: the engine passes its own counter, the router a
    fleet-unique one."""
    prompt = np.asarray(prompt).reshape(-1)
    if prompt.size == 0:
        # an empty prompt would "sample" from the last PADDED
        # position (last_idx = -1) — garbage logits, not a request
        raise ValueError("add_request needs a non-empty prompt")
    if prompt.size + max_new_tokens > max_len:
        raise ValueError(
            f"prompt({prompt.size}) + max_new_tokens({max_new_tokens}) "
            f"exceeds max_len={max_len}")
    if temperature is not None and temperature <= 0:
        raise ValueError(f"temperature must be > 0; got {temperature}")
    if top_k is not None and top_k < 0:
        raise ValueError(f"top_k must be >= 0; got {top_k}")
    if top_p is not None and not 0 < top_p <= 1:
        raise ValueError(f"top_p must be in (0, 1]; got {top_p}")
    if tenant is not None:
        if not isinstance(tenant, str) or not tenant \
                or len(tenant) > 64 \
                or any(c.isspace() or not c.isprintable()
                       for c in tenant):
            # the tenant string becomes a metric label, a prefix-cache
            # hash namespace and a scheduler dict key — reject shapes
            # that could mangle any of the three
            raise ValueError(
                "tenant must be a non-empty printable string without "
                f"whitespace, at most 64 chars; got {tenant!r}")
        if tenant == "-":
            raise ValueError(
                'tenant "-" is reserved for untagged requests')
    if slo is None and (ttft_target_ms is not None
                        or tpot_target_ms is not None):
        slo = "custom"  # explicit targets are an SLO by themselves
    if slo is not None and slo != "custom" and slo not in SLO_CLASSES:
        raise ValueError(
            f"slo must be one of {sorted(SLO_CLASSES)} (or custom "
            f"targets); got {slo!r}")
    if slo == "custom" and ttft_target_ms is None \
            and tpot_target_ms is None:
        # a targetless "custom" request would trivially count as
        # met every time — goodput inflation, not accounting
        raise ValueError(
            'slo="custom" needs ttft_target_ms and/or '
            "tpot_target_ms")
    for tname, t in (("ttft_target_ms", ttft_target_ms),
                     ("tpot_target_ms", tpot_target_ms)):
        if t is not None and t <= 0:
            raise ValueError(f"{tname} must be > 0; got {t}")
    defaults = SLO_CLASSES.get(slo, {})
    if slo is not None:
        if ttft_target_ms is None:
            ttft_target_ms = defaults.get("ttft_target_ms")
        if tpot_target_ms is None:
            tpot_target_ms = defaults.get("tpot_target_ms")
        if deadline_ms is None:
            deadline_ms = defaults.get("deadline_ms")
    if deadline_ms is not None:
        if deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be > 0; got {deadline_ms}")
        if deadline_ms < 1.0:
            raise ValueError(
                f"deadline_ms={deadline_ms} is shorter than a "
                f"single scheduler step can honor (deadlines are "
                f"checked once per step; minimum 1 ms)")
    if max_retries is not None and (
            isinstance(max_retries, bool)
            or not isinstance(max_retries, (int, np.integer))
            or max_retries < 0):
        raise ValueError(
            f"max_retries must be a non-negative int; got "
            f"{max_retries!r}")
    req = Request(rid, prompt, max_new_tokens, eos_token_id,
                  temperature=temperature, top_k=top_k, top_p=top_p,
                  greedy=greedy, tenant=tenant, slo=slo,
                  ttft_target_ms=ttft_target_ms,
                  tpot_target_ms=tpot_target_ms,
                  deadline_ms=deadline_ms, max_retries=max_retries,
                  _submit_t=time.perf_counter())
    if deadline_ms is not None:
        req._deadline_t = req._submit_t + deadline_ms / 1e3
    return req


def request_namespace(req: Request) -> str:
    """The request's prefix-cache hash namespace: its tenant when
    tenant isolation is on (``PT_FLAGS_tenant_prefix_namespace``),
    else the shared default chain. ONE function for the engine's
    admission match and the router's affinity probe — the two must
    hash identically or affinity would steer traffic at pages the
    replica can never share."""
    if req.tenant and bool(flags.flag("tenant_prefix_namespace")):
        return req.tenant
    return ""


def request_ledger(req: Request) -> dict:
    """Serialize a request's HOST TOKEN LEDGER — the replay source of
    truth — into a plain dict another engine can re-admit via
    ``admit_ledger``: prompt + every generated token, sampling params,
    SLO targets and the ABSOLUTE deadline instant, plus the original
    submit/admit timestamps and TTFT so SLO accounting on the new
    engine stays the honest wall from FIRST submission. Timestamps are
    ``perf_counter`` values: the handoff contract is in-process (the
    router's replicas) or same-host."""
    return {
        "rid": int(req.rid),
        "prompt": [int(t) for t in req.prompt],
        "output": [int(t) for t in req.output],
        "max_new_tokens": int(req.max_new_tokens),
        "eos_token_id": req.eos_token_id,
        "temperature": req.temperature,
        "top_k": req.top_k,
        "top_p": req.top_p,
        "greedy": req.greedy,
        "tenant": req.tenant,
        "slo": req.slo,
        "ttft_target_ms": req.ttft_target_ms,
        "tpot_target_ms": req.tpot_target_ms,
        # absolute instant (perf_counter seconds; None = no deadline):
        # a handed-off request keeps its ORIGINAL budget — the move
        # must not grant it a fresh clock
        "deadline_t": req._deadline_t or None,
        "max_retries": req.max_retries,
        "retries": int(req._retries),
        "ttft_ms": req.ttft_ms,
        "submit_t": req._submit_t,
        "admit_t": req._admit_t,
        # attributed device cost so far: the move must not zero what
        # the request already burned (per-request cost accounting
        # survives failover/drain exactly like its SLO clock)
        "device_ms": float(req.device_ms),
        "device_ms_profiled": float(req.device_ms_profiled),
    }


class ContinuousBatchingEngine:
    """Slot-based continuous batching over a causal-LM Layer.

    The model must expose ``init_kv_caches`` and accept ``kv_caches`` /
    ``cache_index`` (vector per-slot lengths) in forward — the contract
    ``models/llama.py`` implements.
    """

    def __init__(self, model: Layer, config: Optional[EngineConfig] = None,
                 mesh=None, drafter: Optional[Drafter] = None,
                 fault_injector: Optional[FaultInjector] = None):
        """``drafter``: optional ``spec_decode.Drafter`` override for
        speculative decoding (default: ``NgramDrafter`` when
        ``PT_FLAGS_spec_decode`` is ``ngram``/``auto`` — the flag gates
        the path either way, so a custom drafter with the flag off is
        inert).

        ``fault_injector``: optional ``resilience.FaultInjector``
        override for chaos testing (default: built from
        ``PT_FLAGS_fault_inject``; None when the flag is empty).

        ``mesh``: optional ``jax.sharding.Mesh`` with a ``tp`` axis —
        tensor-parallel serving (parity: the reference's multi-GPU
        FastDeploy/fleet predictor). Params shard by their logical
        ``Parameter.spec`` (Column/RowParallelLinear carry tp specs);
        KV caches shard the kv-head axis; every compiled program runs
        under the mesh and GSPMD inserts the TP collectives. Requires
        num_key_value_heads divisible by the tp degree."""
        self.cfg = config or EngineConfig()
        cfg = self.cfg
        self.mesh = mesh

        # ---- quantized-serving config validation (at INIT, not at
        # first dispatch: a weight/cache dtype combination with no
        # kernel path must fail before any program compiles) ----
        self.weight_dtype = _resolve_weight_dtype(cfg.weight_dtype)
        self.cache_dtype = _resolve_cache_dtype(cfg.cache_dtype)
        if not isinstance(cfg.weight_group_size, (int, np.integer)) \
                or isinstance(cfg.weight_group_size, bool) \
                or cfg.weight_group_size < 1:
            raise ValueError(
                f"EngineConfig.weight_group_size must be a positive "
                f"int; got {cfg.weight_group_size!r}")
        if self.weight_dtype != "bf16" and mesh is not None:
            raise ValueError(
                f"weight_dtype={self.weight_dtype!r} has no "
                "tensor-parallel kernel path — quantized weight "
                "streaming is single-chip serving today (drop the "
                "mesh, or serve bf16 weights under it)")
        if self.cache_dtype == jnp.int8:
            if mesh is not None:
                raise ValueError(
                    "cache_dtype='int8' has no tensor-parallel kernel "
                    "path (scale pools are not mesh-sharded) — drop "
                    "the mesh or use a float cache dtype")
            if int(flags.flag("prefill_chunk")) <= 0:
                raise ValueError(
                    "cache_dtype='int8' requires the chunked prefill "
                    "path (PT_FLAGS_prefill_chunk > 0): the legacy "
                    "per-bucket prefill's one-shot insert programs "
                    "have no quantize-on-append path")

        # ---- weight-only quantization (the tentpole seam): replace
        # every linear with WeightOnlyLinear BEFORE param/buffer
        # extraction so the int8/int4 qweights + group scales become
        # buffers and ride every compiled program as jit arguments ----
        if self.weight_dtype != "bf16":
            import copy

            from ..quantization import quantize_model_weight_only

            if not cfg.quantize_inplace:
                model = copy.deepcopy(model)
            model = quantize_model_weight_only(
                model, weight_dtype=self.weight_dtype,
                group_size=cfg.weight_group_size)

        self.model = model
        model.eval()
        self.params = extract_params(model)
        # buffers (rope tables, int8/int4 qweights+scales after
        # quantize_model_weight_only) ride as ARGUMENTS, never as jit
        # constants — a 7B int8 model would otherwise bake ~7 GB of
        # weights into every compiled program
        self.buffers = extract_buffers(model)
        if self.weight_dtype != "bf16":
            # PTQ's act_scale calibration buffers are dead in every
            # weight-only serving forward (ptaudit DD001 found them
            # riding each compiled program as 15 unread args on the
            # tiny model alone) — drop them from the per-dispatch
            # buffer args; they stay on the Layer tree for
            # state_dict round-trips
            self.buffers = {n: v for n, v in self.buffers.items()
                            if not n.endswith(".act_scale")}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..core.functional import extract_param_objs
            from ..distributed.sharding import model_shardings
            from ..distributed.strategy import DistributedStrategy

            if "tp" not in mesh.axis_names:
                raise ValueError(
                    f"tensor-parallel serving needs a mesh with a 'tp' "
                    f"axis; got axes {mesh.axis_names}")
            tp = mesh.shape["tp"]
            kvh = model.config.num_key_value_heads
            if kvh % tp:
                raise ValueError(
                    f"num_key_value_heads={kvh} not divisible by tp "
                    f"degree {tp} — KV caches shard the kv-head axis")
            strat = DistributedStrategy()  # logical specs only, no fsdp
            objs = extract_param_objs(model)
            shardings = model_shardings(model, mesh, strat,
                                        filter_to_mesh=True)
            self.params = {
                n: jax.device_put(v, shardings[n])
                for n, v in self.params.items()
            }
            # buffers replicate (rope tables; TP-sharded quantized
            # serving would thread specs here)
            repl = NamedSharding(mesh, P())
            self.buffers = {n: jax.device_put(v, repl)
                            for n, v in self.buffers.items()}
            # rebind the Layer tree to the placed arrays: keeping the
            # original single-device copies alive would hold the WHOLE
            # model on device 0 next to its 1/tp shard — an OOM exactly
            # when the model needs TP to fit
            for n, obj in objs.items():
                obj.value = self.params[n]
            owners = dict(model.named_sublayers(include_self=True))
            for n, v in self.buffers.items():
                mod_name, _, bname = n.rpartition(".")
                sub = owners.get(mod_name)
                if sub is not None and bname in sub._buffers:
                    sub._buffers[bname] = v
        self._pb = {"p": self.params, "b": self.buffers}

        self.seq_lens = np.zeros((cfg.max_slots,), np.int64)
        self.active = np.zeros((cfg.max_slots,), bool)
        self.last_tok = np.zeros((cfg.max_slots,), np.int64)
        # O(log slots) admission bookkeeping: a min-heap of free slots
        # (lowest index first, matching the old scan's choice) and a
        # sorted bucket table for bisect lookup — _admit_dispatch used
        # to rescan all slots twice and all buckets per queued request
        self._free_heap = list(range(cfg.max_slots))
        self._buckets = _validate_buckets(cfg)
        self._slot_req: Dict[int, Request] = {}
        self._queue: collections.deque = collections.deque()
        self._next_rid = 0
        # rid mint/advance is a read-modify-write shared between
        # producer-thread add_request callers and the scheduler's
        # handoff paths — unlocked, two producers could mint the
        # same rid and their finish records would collide
        self._rid_lock = threading.Lock()
        self._finished: Dict[int, Request] = {}
        self._key = jax.random.PRNGKey(cfg.seed)

        mcfg = model.config
        self._n_layers = mcfg.num_hidden_layers
        self._kvh = mcfg.num_key_value_heads
        self._hd = mcfg.head_dim
        if cfg.page_size < 1:
            # load-bearing in BOTH modes now: paged page granularity,
            # and the prefix-cache hash block length in contiguous mode
            raise ValueError(
                f"EngineConfig.page_size must be >= 1; got "
                f"{cfg.page_size}")
        if cfg.paged:
            if cfg.max_len % cfg.page_size:
                raise ValueError("max_len must be divisible by page_size")
            for bkt in cfg.seq_buckets:
                if min(bkt, cfg.max_len) % cfg.page_size:
                    raise ValueError(
                        f"seq bucket {bkt} not divisible by page_size="
                        f"{cfg.page_size} — prefill scatters whole pages")
        self._init_cache_state()

        self._decode_c = None
        self._decode_nc = None
        self._verify_c = None
        self._prefill_c = None
        self._insert_c = None
        self._scatter_c = None
        self._prefill_chunk_c = None
        self._insert_prefix_c = None
        self._read_block_c = None
        self._copy_page_c = None

        # single-program chunked prefill (PT_FLAGS_prefill_chunk): one
        # fixed [slots, C] chunk program in a host loop replaces the
        # per-bucket jit specializations; 0 = legacy bucketed prefill
        # floor of 2: a 1-token chunk would hit the models' s == 1
        # decode branch, whose append CLAMPS out-of-range positions —
        # the idle-slot start=max_len sentinel must always route
        # through the s > 1 scatter-with-drop path
        chunk = int(flags.flag("prefill_chunk"))
        self._chunk_len = max(2, min(chunk, cfg.max_len)) if chunk > 0 \
            else 0
        # prefix KV reuse (PT_FLAGS_prefix_cache) rides the chunked
        # path only: suffix-only prefill needs the vector-cache_index
        # chunk program, which the legacy bucketed oracle doesn't have
        self._prefix = None
        self._prefix_block = cfg.page_size
        if bool(flags.flag("prefix_cache")) and self._chunk_len:
            if cfg.paged:
                self._prefix = PagedPrefixStore()
            else:
                cap = cfg.prefix_cache_blocks
                if cap is None:
                    cap = max(cfg.max_slots * cfg.max_len
                              // max(self._prefix_block, 1) // 4, 1)
                self._prefix = ContigPrefixStore(cap)
        self.prefix_stats = {
            "hits": 0, "misses": 0, "hit_tokens": 0,
            "prompt_tokens": 0, "evictions": 0, "cow_copies": 0,
        }

        # speculative decoding (PT_FLAGS_spec_decode): host-side n-gram
        # drafting + ONE compiled [slots, spec_k+1] verify program.
        # "off" keeps this path entirely dark — today's decode trace,
        # bit for bit (the parity oracle the spec tests compare against)
        mode = str(flags.flag("spec_decode")).lower()
        if mode not in ("off", "ngram", "auto"):
            raise ValueError(
                f"PT_FLAGS_spec_decode must be off|ngram|auto; got "
                f"{mode!r}")
        if cfg.spec_k < 1:
            raise ValueError(
                f"EngineConfig.spec_k must be >= 1; got {cfg.spec_k}")
        self._spec_mode = mode
        self._drafter = None
        if mode != "off":
            self._drafter = drafter if drafter is not None \
                else NgramDrafter()
        self.spec_stats = {
            "proposed": 0, "accepted": 0, "emitted": 0,
            "verify_calls": 0, "fallback_steps": 0,
        }

        # SLO attainment bookkeeping (host counters — available even
        # with telemetry off, like prefix_stats/spec_stats): class ->
        # met/violated/target-miss/token counts, written at finish
        self.slo_stats: Dict[str, Dict[str, int]] = {}
        # ---- SLO-aware multi-tenant scheduler seam ----
        # optional host-side admission policy (serving_api.scheduler.
        # SLOFairScheduler is the shipped one; None = FIFO, today's
        # exact behavior). Pure policy: zero new compiled programs —
        # it only reorders which queued request claims a slot, caps
        # per-slot chunk budgets, and may preempt (see set_scheduler)
        self._sched = None
        self.sched_stats = {"policy": "fifo", "preemptions": 0}
        # tenant -> cumulative host counters (telemetry-off-safe,
        # like slo_stats); written at finish/preempt on the
        # scheduler thread, read via tenant_snapshot()
        self.tenant_stats: Dict[str, Dict[str, float]] = {}
        # set by the admission paths when the head request is blocked
        # on KV-pool pages (slots free, pool exhausted) — the PAGED
        # engine's dominant saturation mode, which a free-slot count
        # alone cannot see; read by backpressure()/healthz.
        # _pool_blocked_prev holds the PREVIOUS admission pass's
        # verdict (the live flag resets at each pass's start) — the
        # scheduler policy's preemption window reads it, because
        # "slots free but no pages" is exactly the saturation mode
        # where preempting a page-holding victim helps
        self._pool_blocked = False
        self._pool_blocked_prev = False

        # telemetry (None when PT_FLAGS_telemetry=off → scheduling loop
        # pays a single identity check per hook site)
        self._tel = (observability.ServingTelemetry()
                     if observability.enabled() else None)
        # lifecycle tracer (observability/tracing.py): same off-switch
        # as telemetry, thinned by PT_FLAGS_trace_sample; records
        # request spans + per-step composition into a bounded ring.
        # Pure host bookkeeping — adds zero compiled programs (pinned
        # by test_tracing's compile-count guard).
        self._tracer = None
        if self._tel is not None and float(flags.flag("trace_sample")) > 0:
            self._tracer = observability.Tracer(
                engine_id=self._tel.engine_id)

        # ---------------- resilience layer ----------------
        # seeded fault injector (PT_FLAGS_fault_inject; ctor override
        # for tests/benches) — None in production, zero overhead
        self._injector = (fault_injector if fault_injector is not None
                          else FaultInjector.from_flag())
        rec = str(flags.flag("serve_recovery")).lower()
        if rec not in ("auto", "all", "off"):
            raise ValueError(
                f"PT_FLAGS_serve_recovery must be auto|all|off; got "
                f"{rec!r}")
        self._recovery_mode = rec
        # graceful-degradation ladder (PT_FLAGS_degradation)
        self._degctl = (DegradationController()
                        if bool(flags.flag("degradation")) else None)
        # drain(): admission stopped, in-flight runs to completion
        self._draining = False
        # faults observed since the last health tick (feeds the ladder)
        self._faults_tick = 0
        # host counters (available with telemetry off, like spec_stats)
        self.resilience_stats = {
            "recoveries": 0, "retries": 0, "failed": 0, "timeouts": 0,
            "rebuilds": 0, "nan_steps": 0, "faults": {},
        }
        # lazy flight recorder for NaN-storm postmortem dumps (rides
        # PR 2's recorder: the dump attaches the tracer tail)
        self._recorder = None

        # ---------------- invariant sanitizer ----------------
        # PT_FLAGS_sanitize (analysis/sanitizer.py): per-tick state
        # invariants (page/refcount conservation, slot-heap +
        # block-table + scale-pool agreement, seq_len bounds vs the
        # host token ledger) and thread-ownership of scrape reads.
        # None when off — every hook site below pays a single identity
        # check, the telemetry=off pattern (pinned by test).
        self._san = None
        if bool(flags.flag("sanitize")):
            from ..analysis.sanitizer import EngineSanitizer

            self._san = EngineSanitizer(self)

        # ---------------- program profiler + recompile watchdog ------
        # PT_FLAGS_profile_programs (observability/profiling.py):
        # cadence-sampled block-until-ready timing around every
        # compiled dispatch — sampled dispatches record MEASURED
        # device ms (pt_serve_program_ms) + the schedule/dispatch/
        # device decomposition; unsampled dispatches stay fully async.
        # Off = None: one identity check per seam, zero new compiled
        # programs, outputs bit-identical (pinned by test).
        self._prof = None
        if bool(flags.flag("profile_programs")):
            self._prof = observability.ProgramProfiler(
                engine_id=(self._tel.engine_id
                           if self._tel is not None else None))
        # PT_FLAGS_recompile_watchdog: seal the expected program set
        # after warmup (tick budget, or engine.seal_programs()) and
        # count + flight-record any post-seal TRACE_COUNTS growth in
        # one of THIS engine's own ticks — the production complement
        # to ptlint TS003 and the test-only compile-count guards
        self._watchdog = None
        if bool(flags.flag("recompile_watchdog")):
            self._watchdog = observability.RecompileWatchdog(
                TRACE_COUNTS, TRACE_SHAPES,
                engine_id=(self._tel.engine_id
                           if self._tel is not None
                           else (self._prof.engine_id
                                 if self._prof is not None else "-")))
        # PT_FLAGS_audit_on_seal (analysis/program_audit.py): run the
        # jaxpr contract audit (AL/DQ/TX/DD rule families) over THIS
        # engine's own programs at its real shapes when the program
        # set seals — trace-only self-audit, no compile, no dispatch,
        # TRACE_COUNTS restored. Off (default) = one identity check
        # at seal; the verdict surfaces in metrics_snapshot()["audit"]
        self._audit_on_seal = bool(flags.flag("audit_on_seal"))
        self._audit_report = None
        # ---------------- flight data: history + alerts + cost -------
        # PT_FLAGS_timeseries (observability/timeseries.py): a bounded
        # ring of fixed-cadence windowed samples over this engine's
        # metrics, tick-driven (wall-clock-free in every decision) and
        # copy-on-read for the scrape thread. PT_FLAGS_alerts rides it:
        # rule-based detectors (SLO burn-rate, queue growth, hit-rate /
        # acceptance collapse, post-seal recompiles, HBM residency)
        # evaluate each closed window with hysteresis. Off = None —
        # one identity check per tick, zero new compiled programs,
        # outputs bit-identical (pinned by test).
        self._ts = None
        self._alerts = None
        if bool(flags.flag("timeseries")):
            label = (self._tel.engine_id if self._tel is not None
                     else None)
            self._ts = observability.TimeSeriesStore(label=label)
            if bool(flags.flag("alerts")):
                self._alerts = observability.AlertManager(
                    self._ts.label, tracer=self._tracer)
        # the degradation ladder's read-only burn-rate hook
        # (PT_FLAGS_slo_degradation, default off: the ladder's inputs
        # are untouched and its outputs pinned identical)
        self._slo_degradation = bool(flags.flag("slo_degradation"))
        # host tick/token counters the time-series collector windows
        # (cheap ints, always maintained — like prefix/spec stats)
        self._tokens_emitted = 0

        # per-request device-cost attribution (PT_FLAGS_cost_
        # attribution): split each step's measured program-ms
        # (profiler sample; sync-wall estimate on unsampled steps)
        # across the requests the step advanced, proportional to
        # tokens advanced. Pure host arithmetic over stamps the step
        # paths already take — zero device syncs, zero new compiled
        # programs; off = one identity check per seam.
        self._cost_enabled = bool(flags.flag("cost_attribution"))
        self.cost_stats = {
            # program -> total attributed ms (measured + estimated)
            "attributed_ms": {},
            # split by evidence: profiled_ms is backed by MEASURED
            # block-until-ready samples, estimated_ms by the honest
            # sync-wall upper bound on unsampled steps
            "profiled_ms": 0.0, "estimated_ms": 0.0,
            "requests_finished": 0,
            "request_device_ms_total": 0.0,
            # slo class (or "untracked") -> {requests, device_ms_total}
            "by_slo": {},
        }
        # recent finished-request costs (p50 over the window)
        self._cost_window: collections.deque = collections.deque(
            maxlen=512)
        # requests that reached a terminal state mid-step: their
        # finish-time cost recording is deferred past the step's
        # attribution pass (the final chunk's share must be included)
        self._cost_pending: List[Request] = []

        # live HBM residency gauges (host metadata only): the weight
        # components are immutable after init — computed ONCE here so
        # profiler-sampled refreshes only re-walk the (small) dynamic
        # parts; baseline the gauges now that the pools exist
        self._hbm_weights = observability.profiling \
            .weight_bytes_by_dtype(self.params, self.buffers)
        self._hbm_update()

    def _init_cache_state(self):
        """(Re)build the KV-cache device arrays and the page-pool
        bookkeeping — called at init and by hard crash recovery
        (``_rebuild_caches``). Shapes are identical across rebuilds,
        so the jitted programs never re-specialize (pinned by the
        recovery compile-count guard)."""
        cfg = self.cfg
        if cfg.paged:
            max_pages_per_slot = cfg.max_len // cfg.page_size
            # +1: page 0 is the inactive-slot write sink, never allocated
            n_pages = cfg.n_pages or \
                cfg.max_slots * max_pages_per_slot + 1
            self.pool = PagePool(n_pages, cfg.page_size, cfg.max_slots,
                                 max_pages_per_slot, reserve_sink=True)
            self.layer_caches = init_paged_pool(
                self._n_layers, n_pages, cfg.page_size, self._kvh,
                self._hd, dtype=self.cache_dtype)
            if self.mesh is not None:
                self.layer_caches = [
                    PagedLayerCache(self._shard_kv(c.k_pages, axis=0),
                                    self._shard_kv(c.v_pages, axis=0))
                    for c in self.layer_caches]
        else:
            self.pool = None
            self.caches = self.model.init_kv_caches(
                cfg.max_slots, cfg.max_len, dtype=self.cache_dtype)
            if self.mesh is not None:
                self.caches = [
                    (self._shard_kv(k), self._shard_kv(v))
                    for k, v in self.caches]

    def _shard_kv(self, arr, axis=-2):
        """Shard the kv-head axis over tp (requires kv_heads % tp == 0):
        axis -2 for contiguous [..., kv_heads, head_dim] caches, axis 0
        for the head-major paged pool."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = [None] * arr.ndim
        spec[axis] = "tp"
        return jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))

    def _ctx(self):
        import contextlib

        if self.mesh is None:
            return contextlib.nullcontext()
        from ..distributed.sharding import mesh_context

        return mesh_context(self.mesh)

    # ---------------- scheduler policy seam ----------------
    def set_scheduler(self, policy):
        """Install (or clear, with ``None``) the admission scheduler
        policy — the SLO-aware multi-tenant scheduler's seam into the
        engine. The policy is consulted on the SCHEDULER THREAD only,
        at three points:

        * ``pick(engine, candidates)`` — admission ORDER: choose the
          next queued request to claim a slot (replaces FIFO).
        * ``before_admission(engine)`` — the preemption window before
          each admission wave; may call ``engine.preempt(slot)`` and
          returns the preempted rids (excluded from this wave).
        * ``slot_caps(engine)`` — per-slot decode-token caps applied
          to each chunk's budget vector (``None`` = uncapped).
        * ``note_admit(engine, req)`` — fair-share accounting hook,
          called when a pick's claim commits.

        Pure host-side policy: the compiled program set is untouched
        (pinned by the compile-counter guards) and greedy outputs are
        per-request bit-identical under any admission order. Policy
        rides the CHUNKED admission path only — the legacy bucketed
        prefill (``PT_FLAGS_prefill_chunk=0``) stays FIFO, like the
        prefix cache."""
        self._sched = policy
        self.sched_stats["policy"] = (
            "fifo" if policy is None
            else getattr(policy, "name", type(policy).__name__))

    def _pick_admission(self, skip, fifo_cursor):
        """Admission-order seam: the next queued request to TRY (a
        peek — removal happens only when its slot/page claim commits),
        or None to stop this wave. ``skip`` holds rids already
        deferred OR committed this wave (shed batch / draining /
        preempted / claimed). Default FIFO rides ``fifo_cursor`` — a
        wave-local ``[snapshot, index]`` pair, ONE queue copy per
        wave with a monotone index (a deep shed/drain wave must stay
        O(queue), not O(queue²)). With a policy: the policy re-ranks
        a fresh snapshot per pick (usage/urgency move as the wave
        claims slots)."""
        if self._sched is None:
            if not skip:
                # pure-FIFO fast path: head peek, O(1) — the
                # snapshot is not even taken until something defers
                return self._queue[0] if self._queue else None
            cands, i = fifo_cursor
            if cands is None:
                cands = fifo_cursor[0] = list(self._queue)
            while i < len(cands) and cands[i].rid in skip:
                i += 1
            fifo_cursor[1] = i
            return cands[i] if i < len(cands) else None
        cands = [r for r in list(self._queue) if r.rid not in skip]
        if not cands:
            return None
        return self._sched.pick(self, cands)

    def preempt(self, slot: int) -> bool:
        """Preempt the ACTIVE request in ``slot``: release its
        slot/KV pages/prefix refs through the one teardown path and
        re-queue it at the FRONT with its generated history intact.
        Re-admission replays prompt+history through the existing
        ``[slots, C]`` chunked prefill program — the crash-recovery
        path — so greedy outputs stay bit-identical and ZERO new
        programs compile. TTFT/admit instants and attributed cost are
        preserved (the request is the same object); the price is the
        replay's prefill recompute, which the scheduler policy must
        weigh (and bound) before calling.

        Scheduler-thread only, same contract as ``cancel``: an
        in-flight chunk's writes to the freed pages are stream-ordered
        before any successor's prefill writes, and the host loop
        discards the preempted slot's remaining chunk tokens via the
        ``active`` mask."""
        req = self._slot_req.get(slot)
        if req is None:
            return False
        self._release_slot(slot)
        req.slot = None
        # replay ids grow by the generated history: stale digests
        # (hashed at admission) no longer cover them
        req._hashes = None
        self._queue.appendleft(req)
        self.sched_stats["preemptions"] += 1
        self._tenant_bucket(req.tenant)["preemptions"] += 1
        if self._tel is not None:
            self._tel.on_preempt()
        tr = self._tracer
        if tr is not None and tr.want_request(req.rid):
            tr.request(req.rid, "preempt", slot=slot,
                       tokens=len(req.output),
                       tenant=req.tenant or "-")
        return True

    # ---------------- request lifecycle ----------------
    def add_request(self, prompt, max_new_tokens: int = 32,
                    eos_token_id: Optional[int] = None,
                    temperature: Optional[float] = None,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    greedy: Optional[bool] = None,
                    tenant: Optional[str] = None,
                    slo: Optional[str] = None,
                    ttft_target_ms: Optional[float] = None,
                    tpot_target_ms: Optional[float] = None,
                    deadline_ms: Optional[float] = None,
                    max_retries: Optional[int] = None) -> int:
        """``temperature``/``top_k``/``top_p``: per-request sampling
        params, routed through ``generation.process_logits_batch``
        IN-JIT as per-slot vectors — setting any of them makes this
        request sample (``greedy=True`` overrides back to argmax;
        leaving all four ``None`` keeps the engine-global
        ``EngineConfig.greedy``/``temperature`` behavior and its exact
        compiled trace). Sampling requests never draft for speculative
        decoding — greedy acceptance needs an argmax chain to verify
        against.

        ``tenant``: multi-tenant identity (non-empty printable
        string, no whitespace, ≤64 chars; ``None`` = untagged). Drives
        the SLO-fair scheduler's weighted fair share and quotas, the
        per-tenant prefix-cache namespace
        (``PT_FLAGS_tenant_prefix_namespace``) and the tenant label on
        serve metrics — never the compiled programs.

        ``slo``: latency class (``"interactive"`` | ``"batch"``) whose
        TTFT / per-request-TPOT targets (``SLO_CLASSES``, overridable
        via ``ttft_target_ms``/``tpot_target_ms``; explicit targets
        alone imply class ``"custom"``) are checked at finish —
        attainment lands in ``pt_serve_slo_{met,violated}_total``, the
        goodput gauge and ``engine.slo_snapshot()``. ``None`` leaves
        the request SLO-untracked.

        ``deadline_ms``: hard wall-clock budget from submission — the
        scheduler expires the request (queued or mid-decode) once it
        passes, finishing it with ``finish_reason="timeout"`` and
        provably freeing its slot, KV pages and prefix refs. Defaults
        to the SLO class's ``deadline_ms`` when ``slo`` is set, else
        no deadline. Must be >= 1 ms: the scheduler checks deadlines
        once per step, so a sub-millisecond deadline is shorter than a
        single step can honor and would expire unconditionally.

        ``max_retries``: per-request bound on crash-recovery replay
        re-queues (default ``EngineConfig.max_retries``); past it the
        request finishes with ``finish_reason="failed"``."""
        req = build_request(
            0, prompt, max_new_tokens, eos_token_id,
            temperature=temperature, top_k=top_k, top_p=top_p,
            greedy=greedy, tenant=tenant, slo=slo,
            ttft_target_ms=ttft_target_ms,
            tpot_target_ms=tpot_target_ms, deadline_ms=deadline_ms,
            max_retries=max_retries, max_len=self.cfg.max_len)
        # mint AFTER validation (a rejected request burns no rid) and
        # under the lock: concurrent producer threads reading the
        # counter before either advanced it would share a rid
        with self._rid_lock:
            req.rid = self._next_rid
            self._next_rid += 1
        return self.submit_request(req)

    def submit_request(self, req: Request) -> int:
        """Enqueue an externally built, NEVER-RUN :class:`Request`
        directly — the router's first-placement fast path (the caller
        owns the rid space and already validated via
        ``build_request``). Requests carrying history (failover
        replay, drain handoff) move between engines via
        ``admit_ledger`` instead, which rebuilds state from the token
        ledger."""
        with self._rid_lock:
            self._next_rid = max(self._next_rid, req.rid + 1)
        self._queue.append(req)
        if self._tel is not None:
            self._tel.on_submit(len(self._queue))
        tr = self._tracer
        if tr is not None and tr.want_request(req.rid):
            tr.request(req.rid, "queued", t0=req._submit_t,
                       prompt_tokens=int(req.prompt.size),
                       max_new_tokens=int(req.max_new_tokens),
                       slo=req.slo or "")
        return req.rid

    def admit_ledger(self, ledger: dict) -> int:
        """Re-admit a request handed off from ANOTHER engine — the
        receiving half of the handoff API (``drain()['unfinished']`` /
        the router's cross-replica failover). The ledger's generated
        tokens are host-side truth, so admission replays
        prompt+history through the existing ``[slots, C]`` chunked
        prefill program (``_prefill_ids``) and greedy decoding
        continues bit-identically; the ORIGINAL submit/admit instants,
        TTFT and absolute deadline carry over, so SLO accounting never
        resets across the move. The caller owns the rid space
        (fleet-unique rids) — a rid this engine already knows is
        rejected, the dual-ownership the fleet sanitizer forbids."""
        rid = int(ledger["rid"])
        known = rid in self._finished
        if not known:
            try:
                known = any(
                    r.rid == rid for r in list(self._queue)) \
                    or any(r.rid == rid
                           for r in list(self._slot_req.values()))
            except RuntimeError:
                # a producer-thread handoff racing the scheduler's own
                # structure mutation: the uniqueness guard is
                # best-effort off-thread — true dual ownership is
                # still caught by the fleet sanitizer at the next tick
                known = False
        if known:
            raise ValueError(
                f"admit_ledger: rid {rid} is already owned by this "
                "engine (queued, active, or finished) — a handoff "
                "must MOVE a request, never copy it")
        req = build_request(
            rid, np.asarray(ledger["prompt"], np.int64),
            int(ledger["max_new_tokens"]), ledger.get("eos_token_id"),
            temperature=ledger.get("temperature"),
            top_k=ledger.get("top_k"), top_p=ledger.get("top_p"),
            greedy=ledger.get("greedy"), tenant=ledger.get("tenant"),
            slo=ledger.get("slo"),
            ttft_target_ms=ledger.get("ttft_target_ms"),
            tpot_target_ms=ledger.get("tpot_target_ms"),
            max_retries=ledger.get("max_retries"),
            max_len=self.cfg.max_len)
        req.output = [int(t) for t in ledger.get("output", ())]
        req.ttft_ms = ledger.get("ttft_ms")
        req._retries = int(ledger.get("retries", 0))
        req.device_ms = float(ledger.get("device_ms", 0.0) or 0.0)
        req.device_ms_profiled = float(
            ledger.get("device_ms_profiled", 0.0) or 0.0)
        # original instants win over build_request's fresh stamps: the
        # move must not shrink queue-wait out of TTFT or grant a fresh
        # deadline clock
        if ledger.get("submit_t"):
            req._submit_t = float(ledger["submit_t"])
        if ledger.get("admit_t"):
            req._admit_t = float(ledger["admit_t"])
        req._deadline_t = float(ledger.get("deadline_t") or 0.0)
        # keep the local counter ahead of adopted rids so standalone
        # add_request on this engine can never collide with a handoff
        with self._rid_lock:
            self._next_rid = max(self._next_rid, rid + 1)
        self._queue.append(req)
        if self._tel is not None:
            self._tel.on_submit(len(self._queue))
        tr = self._tracer
        if tr is not None and tr.want_request(rid):
            tr.request(rid, "queued", t0=req._submit_t,
                       prompt_tokens=int(req.prompt.size),
                       max_new_tokens=int(req.max_new_tokens),
                       slo=req.slo or "", handoff=True,
                       replayed_tokens=len(req.output))
        return rid

    def _req_greedy(self, req: Request) -> bool:
        if req.greedy is not None:
            return req.greedy
        if (req.temperature is not None or req.top_k is not None
                or req.top_p is not None):
            return False  # explicit sampling params imply sampling
        return self.cfg.greedy

    def _req_nondefault(self, req: Request) -> bool:
        """True when the request's EFFECTIVE next-token selection
        differs from the engine-global config — only then must the
        compiled programs take the per-slot sampling arm (and pay its
        vocab sort). Merely *passing* an override that lands on the
        default (``greedy=True`` on a greedy engine, ``top_k=0``,
        ``top_p=1.0``, the engine's own temperature) keeps the plain
        arm and its exact trace."""
        g = self._req_greedy(req)
        if g != bool(self.cfg.greedy):
            return True
        if g:
            return False  # argmax is argmax; temp/top-k/top-p unused
        return ((req.temperature is not None
                 and req.temperature != self.cfg.temperature)
                or bool(req.top_k)
                or (req.top_p is not None and req.top_p < 1.0))

    def _slot_sampling(self, reqs=None):
        """(use_samp, per-slot param vectors) for the compiled
        programs. ``use_samp`` is False when every live request rides
        the engine-global config — the programs' static no-sampling arm
        then reproduces the pre-per-request-params trace exactly (and
        never pays the vocab sort). ``reqs``: optional explicit
        (slot, Request) pairs (a prefill wave); defaults to the active
        slot map."""
        cfg = self.cfg
        items = list(self._slot_req.items()) if reqs is None else reqs
        greedy = np.full((cfg.max_slots,), bool(cfg.greedy))
        temp = np.full((cfg.max_slots,), max(cfg.temperature, 1e-6),
                       np.float32)
        tk = np.zeros((cfg.max_slots,), np.int32)
        tp = np.ones((cfg.max_slots,), np.float32)
        use = False
        for slot, req in items:
            use = use or self._req_nondefault(req)
            greedy[slot] = self._req_greedy(req)
            if req.temperature is not None:
                temp[slot] = max(req.temperature, 1e-6)
            if req.top_k is not None:
                tk[slot] = req.top_k
            if req.top_p is not None:
                tp[slot] = req.top_p
        samp = (jnp.asarray(greedy), jnp.asarray(temp),
                jnp.asarray(tk), jnp.asarray(tp))
        return use, samp

    def _sample_rows(self, rows, key, samp, use_samp):
        """Next-token selection over ``[slots, vocab]`` rows inside the
        compiled programs. The static ``use_samp`` arm routes per-slot
        params through ``generation.process_logits_batch`` (greedy
        slots keep pure argmax — a sampling neighbor can't perturb
        them); the other arm is the engine-global config, compiled
        exactly as before per-request params existed."""
        if use_samp:
            greedy_mask, temp, tk, tp = samp
            g = jnp.argmax(rows, axis=-1)
            s = jax.random.categorical(
                key, G.process_logits_batch(rows, temp, tk, tp), axis=-1)
            return jnp.where(greedy_mask, g, s)
        if self.cfg.greedy:
            return jnp.argmax(rows, axis=-1)
        return jax.random.categorical(
            key, rows / self.cfg.temperature, axis=-1)

    def _free_slots(self) -> List[int]:
        return sorted(self._free_heap)

    # ---------------- compiled programs ----------------
    def _bucket(self, n: int) -> int:
        i = bisect.bisect_left(self._buckets, n)
        return self._buckets[i] if i < len(self._buckets) \
            else self.cfg.max_len

    def _prefill(self):
        # one jitted fn serves every bucket: jit specializes per shape.
        # Samples the first token IN-JIT so only a scalar crosses to the
        # host — never the [1, bucket, vocab] logits tensor.
        if self._prefill_c is None:
            def fn(pb, ids, caches, last_idx, key, samp, use_samp):
                TRACE_COUNTS["prefill_bucket"] += 1
                _shape_note("prefill_bucket", ids=ids)
                pos = jnp.broadcast_to(
                    jnp.arange(ids.shape[1])[None, :], ids.shape)
                logits, filled = functional_call(
                    self.model, pb["p"], ids, position_ids=pos,
                    kv_caches=caches, cache_index=0, buffers=pb["b"])
                last = logits[0, last_idx]
                if use_samp:
                    # single-request program: samp carries [1] vectors
                    first = self._sample_rows(last[None], key, samp,
                                              True)[0]
                elif self.cfg.greedy:
                    first = jnp.argmax(last)
                else:
                    first = jax.random.categorical(
                        key, last / self.cfg.temperature)
                return first, filled
            # caches (the fresh per-call bucket cache) is donated: the
            # program fills it in place and the caller only ever uses
            # the returned `filled`. ptaudit AL001 found the missing
            # donation — without it every legacy prefill paid a full
            # bucket-cache copy on top of the fill
            self._prefill_c = jax.jit(fn, static_argnums=(6,),
                                      donate_argnums=(2,))
        return self._prefill_c

    def _insert_contig(self):
        # write a single-sequence prefill cache into slot `slot` of the
        # global contiguous cache (dynamic_update_slice over slot axis)
        if self._insert_c is None:
            def fn(global_caches, one_caches, slot):
                TRACE_COUNTS["prefill_insert"] += 1
                _shape_note("prefill_insert", one_k=one_caches[0][0])
                out = []
                for (gk, gv), (ok, ov) in zip(global_caches, one_caches):
                    pad = gk.shape[1] - ok.shape[1]
                    ok = jnp.pad(ok, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    ov = jnp.pad(ov, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    gk = jax.lax.dynamic_update_slice_in_dim(
                        gk, ok.astype(gk.dtype), slot, 0)
                    gv = jax.lax.dynamic_update_slice_in_dim(
                        gv, ov.astype(gv.dtype), slot, 0)
                    out.append((gk, gv))
                return out
            self._insert_c = jax.jit(fn, donate_argnums=(0,))
        return self._insert_c

    def _scatter_paged(self):
        # scatter a [1, bucket] prefill cache into this slot's pages;
        # bucket/n_used come from the traced shapes, so one jitted fn
        # specializes per bucket automatically
        if self._scatter_c is None:
            ps = self.cfg.page_size

            def fn(layer_caches, one_caches, bt_row):
                TRACE_COUNTS["prefill_scatter"] += 1
                _shape_note("prefill_scatter", one_k=one_caches[0][0], bt_row=bt_row)
                out = []
                for cache, (ok, ov) in zip(layer_caches, one_caches):
                    n_used = ok.shape[1] // ps
                    pages = bt_row[:n_used]
                    # [1, bucket, kvh, d] -> head-major [kvh, n_used, ps, d]
                    okp = ok[0].reshape(n_used, ps, *ok.shape[2:]) \
                        .transpose(2, 0, 1, 3)
                    ovp = ov[0].reshape(n_used, ps, *ov.shape[2:]) \
                        .transpose(2, 0, 1, 3)
                    # _replace (not positional rebuild): this legacy
                    # path never serves int8 pools (rejected at init),
                    # but a positional ctor would silently DROP scale
                    # arrays if that ever changed
                    out.append(cache._replace(
                        k_pages=cache.k_pages.at[:, pages].set(
                            okp.astype(cache.k_pages.dtype)),
                        v_pages=cache.v_pages.at[:, pages].set(
                            ovp.astype(cache.v_pages.dtype)),
                    ))
                return out
            self._scatter_c = jax.jit(fn, donate_argnums=(0,))
        return self._scatter_c

    def _prefill_chunked(self):
        """THE prefill program: one fixed-shape [slots, C] chunk,
        writing straight into the live global cache at per-slot
        offsets. A host loop drives chunk k over suffix tokens
        [k·C, (k+1)·C); slots not prefilling this call carry a
        ``start = max_len`` sentinel (their writes drop, their outputs
        are ignored). Samples a first token per slot in-jit from the
        per-slot ``last_idx`` row — only scalars ever cross to the
        host; the host uses the sample from each request's final chunk.
        One jit specialization serves EVERY prompt length (the compile
        count the trace guard asserts), and multiple queued requests'
        chunks pack into the same call. The shape is [slots, C] like
        the decode program's [slots, 1]: a lone admission still
        computes every slot's rows (sentinels included) — the win is
        per-REQUEST marginal cost under packing, not the cost of an
        unpacked call."""
        if self._prefill_chunk_c is None:
            paged = self.cfg.paged
            C = self._chunk_len

            def fn(pb, ids, caches, bt, start, last_idx, key, samp,
                   use_samp):
                TRACE_COUNTS["prefill_chunk"] += 1
                _shape_note("prefill_chunk", ids=ids, start=start)
                pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)
                if paged:
                    state = PagedState(block_tables=bt, seq_lens=start)
                    kv = [(c, state) for c in caches]
                else:
                    kv = caches
                logits, new_kv = functional_call(
                    self.model, pb["p"], ids, position_ids=pos,
                    kv_caches=kv, cache_index=start, buffers=pb["b"])
                rows = logits[jnp.arange(logits.shape[0]), last_idx]
                toks = self._sample_rows(rows, key, samp, use_samp)
                if paged:
                    return toks, [c for c, _ in new_kv]
                return toks, new_kv
            self._prefill_chunk_c = jax.jit(fn, static_argnums=(8,),
                                            donate_argnums=(2,))
        return self._prefill_chunk_c

    def _insert_prefix_contig(self):
        """Write one cached prefix block (k/v stacked over layers,
        [n_layers, B, kvh, d]) into a slot's contiguous cache rows at
        ``start`` — the contiguous-mode prefix 'share' is a copy.
        One dispatch per matched block (a variable-count batched write
        would re-specialize per hit length); fine for the contiguous
        mode's scale — production paged serving shares pages with zero
        copies instead."""
        if self._insert_prefix_c is None:
            from .paged import QuantizedKV

            def ins(g, blk, i, slot, start):
                if isinstance(g, QuantizedKV):
                    # int8 caches: the stored block carries its scale
                    # rows — payload and scales insert together
                    return QuantizedKV(
                        jax.lax.dynamic_update_slice(
                            g.q, blk.q[i][None].astype(g.q.dtype),
                            (slot, start, 0, 0)),
                        jax.lax.dynamic_update_slice(
                            g.scale, blk.scale[i][None],
                            (slot, start, 0)))
                return jax.lax.dynamic_update_slice(
                    g, blk[i][None].astype(g.dtype), (slot, start, 0, 0))

            def fn(global_caches, kblk, vblk, slot, start):
                TRACE_COUNTS["prefix_insert"] += 1
                _shape_note("prefix_insert", kblk=kblk, vblk=vblk)
                out = []
                for i, (gk, gv) in enumerate(global_caches):
                    out.append((ins(gk, kblk, i, slot, start),
                                ins(gv, vblk, i, slot, start)))
                return out
            self._insert_prefix_c = jax.jit(fn, donate_argnums=(0,))
        return self._insert_prefix_c

    def _read_block_contig(self):
        """Slice one block of a slot's rows out of every layer's
        contiguous cache, stacked [n_layers, B, kvh, d] — the store's
        materialized copy of a fresh prefix block."""
        if self._read_block_c is None:
            B = self._prefix_block
            from .paged import QuantizedKV

            def rd(g, slot, start):
                if isinstance(g, QuantizedKV):
                    qsz = (1, B) + g.q.shape[2:]
                    ssz = (1, B) + g.scale.shape[2:]
                    return QuantizedKV(
                        jax.lax.dynamic_slice(
                            g.q, (slot, start, 0, 0), qsz)[0],
                        jax.lax.dynamic_slice(
                            g.scale, (slot, start, 0), ssz)[0])
                sz = (1, B) + g.shape[2:]
                return jax.lax.dynamic_slice(
                    g, (slot, start, 0, 0), sz)[0]

            def stack(blks):
                if isinstance(blks[0], QuantizedKV):
                    # the store's block keeps its scale rows: dequant
                    # state survives insert into a future slot
                    return QuantizedKV(
                        jnp.stack([b.q for b in blks]),
                        jnp.stack([b.scale for b in blks]))
                return jnp.stack(blks)

            def fn(global_caches, slot, start):
                TRACE_COUNTS["prefix_read"] += 1
                _shape_note("prefix_read", k0=global_caches[0][0])
                ks, vs = [], []
                for gk, gv in global_caches:
                    ks.append(rd(gk, slot, start))
                    vs.append(rd(gv, slot, start))
                return stack(ks), stack(vs)
            self._read_block_c = jax.jit(fn)
        return self._read_block_c

    def _copy_page(self):
        """Copy-on-write device copy: duplicate page ``src`` into
        ``dst`` across every layer's pool (src/dst are traced scalars —
        one specialization ever)."""
        if self._copy_page_c is None:
            def copy1(arr, src, dst):
                return jax.lax.dynamic_update_slice_in_dim(
                    arr,
                    jax.lax.dynamic_slice_in_dim(arr, src, 1, axis=1),
                    dst, axis=1)

            def fn(layer_caches, src, dst):
                TRACE_COUNTS["page_copy"] += 1
                _shape_note("page_copy", k_pages=layer_caches[0].k_pages)
                out = []
                for c in layer_caches:
                    rep = {"k_pages": copy1(c.k_pages, src, dst),
                           "v_pages": copy1(c.v_pages, src, dst)}
                    if c.k_scale is not None:
                        # int8 pools: a COW'd page keeps its dequant
                        # state — the scale rows copy with the page
                        rep["k_scale"] = copy1(c.k_scale, src, dst)
                        rep["v_scale"] = copy1(c.v_scale, src, dst)
                    out.append(c._replace(**rep))
                return out
            self._copy_page_c = jax.jit(fn, donate_argnums=(0,))
        return self._copy_page_c

    def _decode(self):
        if self._decode_c is None:
            paged = self.cfg.paged

            def fn(pb, toks, caches, state_or_lens, key, samp, use_samp):
                # only `caches` (arg 2) is donated; the per-slot lengths /
                # block tables must NOT alias it (f(donate(a), a) trap)
                TRACE_COUNTS["decode_step"] += 1
                _shape_note("decode_step", toks=toks)
                if paged:
                    state = state_or_lens
                    seq_lens = state.seq_lens
                    kv = [(c, state) for c in caches]
                else:
                    seq_lens = state_or_lens
                    kv = caches
                pos = seq_lens[:, None]
                logits, new_kv = functional_call(
                    self.model, pb["p"], toks, position_ids=pos,
                    kv_caches=kv, cache_index=seq_lens, buffers=pb["b"])
                logits = logits[:, -1, :]
                nxt = self._sample_rows(logits, key, samp, use_samp)
                if paged:
                    new_caches = [c for c, _ in new_kv]
                    return nxt, new_caches
                return nxt, new_kv
            self._decode_c = jax.jit(fn, static_argnums=(6,),
                                     donate_argnums=(2,))
        return self._decode_c

    def _decode_n(self):
        """K decode steps fused into one device program (lax.scan): the
        sampled token feeds the next step ON DEVICE; the host syncs once
        per K tokens instead of per token. K is FIXED at
        ``cfg.decode_chunk``-or-caller's max_chunk so exactly one program
        ever compiles; per-slot ``budget`` (a traced vector) freezes a
        slot once it has produced its remaining tokens — its length stops
        advancing, so overflow steps rewrite the same in-allocation cache
        position with discarded garbage. Inactive slots likewise never
        advance (their writes land in the slot's own row / the paged sink
        page, both overwritten or freed at admission)."""
        if self._decode_nc is None:
            paged = self.cfg.paged

            def fn(pb, toks, caches, lens, active, budget, bt, key, samp,
                   K, use_samp):
                TRACE_COUNTS["decode_chunk"] += 1
                _shape_note("decode_chunk", toks=toks, budget=budget)

                def one(carry, k):
                    toks, caches, lens = carry
                    if paged:
                        state = PagedState(block_tables=bt, seq_lens=lens)
                        kv = [(c, state) for c in caches]
                    else:
                        kv = caches
                    logits, new_kv = functional_call(
                        self.model, pb["p"], toks,
                        position_ids=lens[:, None],
                        kv_caches=kv, cache_index=lens, buffers=pb["b"])
                    logits = logits[:, -1, :]
                    nxt = self._sample_rows(
                        logits, jax.random.fold_in(key, k), samp,
                        use_samp)
                    nxt = nxt.astype(toks.dtype)
                    if paged:
                        new_caches = [c for c, _ in new_kv]
                    else:
                        new_caches = new_kv
                    advance = active & (k < budget)
                    new_lens = lens + advance.astype(lens.dtype)
                    new_toks = jnp.where(advance[:, None], nxt[:, None],
                                         toks)
                    return (new_toks, new_caches, new_lens), nxt

                (toks, caches, lens), toks_all = jax.lax.scan(
                    one, (toks, caches, lens), jnp.arange(K))
                return toks_all, caches, lens

            self._decode_nc = jax.jit(
                fn, static_argnums=(9, 10), donate_argnums=(2,))
        return self._decode_nc

    def _verify(self):
        """THE speculative-decoding program: one compiled fixed
        ``[slots, spec_k+1]`` target-model pass that scores each slot's
        last accepted token plus up to K drafted tokens, with GREEDY
        ACCEPTANCE computed in-jit — only ``[slots]``-sized preds and
        accepted-lengths cross to the host, never logits.

        Same shape discipline as the chunked prefill program (it rides
        the models' identical per-slot s>1 branches: vector
        ``cache_index``, scatter-with-drop appends, per-row causal
        history mask): slots with no draft this step carry
        ``n_draft = 0`` and degrade to a normal one-token decode within
        the same program — row 0's prediction IS the decode token;
        inactive slots carry the ``start = max_len`` write-drop
        sentinel. Every row's K/V is appended to the cache (pad rows
        write garbage PAST the slot's live length); the host then
        advances ``seq_lens`` by only ``accepted+1``, which is the
        whole rollback — rows beyond the accepted length sit above
        every later query's causal mask (append-only pages make the
        retreat a pure length decrement; contiguous mode overwrites the
        same rows on the next step).

        Greedy acceptance: draft j is accepted iff it equals the
        program's own argmax after consuming rows 0..j-1 AND every
        earlier draft was accepted — so the emitted chain
        ``draft[:a] + preds[a]`` is exactly the argmax chain plain
        greedy decode would produce, token for token.

        Per-request SAMPLING slots never draft (no argmax chain to
        verify); under the static ``use_samp`` arm their row-0 token is
        sampled in-jit through the same per-slot param stack the
        decode programs use."""
        if self._verify_c is None:
            paged = self.cfg.paged
            S = self.cfg.spec_k + 1

            def fn(pb, ids, caches, bt, start, n_draft, key, samp,
                   use_samp):
                TRACE_COUNTS["spec_verify"] += 1
                _shape_note("spec_verify", ids=ids, n_draft=n_draft)
                pos = start[:, None] + jnp.arange(S, dtype=jnp.int32)
                if paged:
                    state = PagedState(block_tables=bt, seq_lens=start)
                    kv = [(c, state) for c in caches]
                else:
                    kv = caches
                logits, new_kv = functional_call(
                    self.model, pb["p"], ids, position_ids=pos,
                    kv_caches=kv, cache_index=start, buffers=pb["b"])
                preds = jnp.argmax(logits, axis=-1)  # [slots, S]
                match = (preds[:, :-1] == ids[:, 1:]) & \
                    (jnp.arange(S - 1, dtype=n_draft.dtype)[None, :]
                     < n_draft[:, None])
                # accepted = longest all-accepted prefix of the drafts
                accepted = jnp.sum(
                    jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
                if use_samp:
                    greedy_mask, temp, tk, tp = samp
                    s0 = jax.random.categorical(
                        key, G.process_logits_batch(
                            logits[:, 0], temp, tk, tp), axis=-1)
                    preds = preds.at[:, 0].set(
                        jnp.where(greedy_mask, preds[:, 0], s0))
                if paged:
                    return preds, accepted, [c for c, _ in new_kv]
                return preds, accepted, new_kv
            self._verify_c = jax.jit(fn, static_argnums=(8,),
                                     donate_argnums=(2,))
        return self._verify_c

    # ---------------- prefix cache ----------------
    def _prefill_ids(self, req: Request) -> np.ndarray:
        """The token sequence admission must prefill for ``req``: its
        prompt — plus, for a request re-queued by crash recovery,
        every token it had already generated (host-side truth the
        quarantined step cannot lose). Replaying prompt+history
        through the SAME chunked-prefill program recomputes the KV the
        quarantine discarded and samples the NEXT token of the greedy
        chain, so greedy outputs stay bit-identical to a fault-free
        run."""
        if req.output:
            return np.concatenate(
                [req.prompt, np.asarray(req.output, np.int64)])
        return req.prompt

    def _match_prefix(self, req: Request, ids=None):
        """Longest cached block-aligned prefix for the request's
        prefill ids (prompt, or prompt+history on replay; ``ids``
        passes the caller's already-built array — a pool-blocked head
        request retries every tick and must not re-concatenate):
        (hashes, matched entries, prefix_len, full_cover), with the
        full-cover clamp — a fully-cached sequence still recomputes
        its LAST token so prefill has a row to sample from
        (``full_cover`` reports that the clamp fired: the recompute
        row lands inside the last shared page). The single site for
        the clamp rule: both cache modes' admission arms go through
        here."""
        if ids is None:
            ids = self._prefill_ids(req)
        if req._hashes is None:
            req._hashes = block_hashes(
                ids, self._prefix_block,
                namespace=request_namespace(req))
        hashes = req._hashes
        matched = self._prefix.match(hashes)
        prefix_len = len(matched) * self._prefix_block
        full_cover = prefix_len >= ids.size
        if full_cover:
            prefix_len = ids.size - 1
        return hashes, matched, prefix_len, full_cover

    def _note_prefix(self, prefix_len: int, n: int,
                     req: Optional[Request] = None):
        tenant = (req.tenant or "-") if req is not None else "-"
        tr = self._tracer
        if tr is not None and req is not None \
                and tr.want_request(req.rid):
            tr.request(req.rid, "prefix_lookup",
                       hit_tokens=int(prefix_len),
                       prompt_tokens=int(n))
        if n < self._prefix_block:
            # no full block: block_hashes yields nothing, so the prompt
            # can never hit — counting it as a miss would drag the
            # hit-rate toward 0 on short-prompt traffic the cache was
            # never meant to serve
            return
        st = self.prefix_stats
        st["prompt_tokens"] += n
        if prefix_len > 0:
            st["hits"] += 1
            st["hit_tokens"] += prefix_len
        else:
            st["misses"] += 1
        if self._tel is not None:
            self._tel.on_prefix(prefix_len, n,
                                self._prefix.cached_pages,
                                tenant=tenant)

    def _evict_pages(self, n_pages: int,
                     prefer_ns: Optional[str] = None) -> int:
        """Reclaim pool pages from cache-only prefix entries (LRU).
        ``prefer_ns``: evict the requesting tenant's own namespace
        first — its pool pressure spends its own cold entries before
        it can flush another tenant's cached system prompt."""
        if self._prefix is None or not self.cfg.paged:
            return 0
        freed = self._prefix.evict(self.pool, n_pages,
                                   prefer_ns=prefer_ns)
        if freed:
            self.prefix_stats["evictions"] += freed
            if self._tel is not None:
                self._tel.on_prefix_evict(freed,
                                          self._prefix.cached_pages)
            if self._tracer is not None:
                self._tracer.engine_event(
                    "prefix_evict", freed_pages=int(freed),
                    cached_pages=int(self._prefix.cached_pages))
        return freed

    def _cow_block(self, slot: int, block_idx: int) -> bool:
        """Copy-on-write the shared page at ``block_idx`` of ``slot``:
        fresh page (evicting if the free list is dry), device copy,
        block-table swap. False when no page can be found."""
        old = int(self.pool.block_tables[slot, block_idx])
        if self.pool.free_pages == 0 and not self._evict_pages(1):
            return False
        new = self.pool.cow(slot, block_idx)
        if new is None:
            return False
        prof = self._prof
        p_want = prof is not None and prof.want("page_copy")
        t0 = time.perf_counter()
        with self._ctx():
            self.layer_caches = self._copy_page()(
                self.layer_caches, old, new)
        if p_want:
            # t_call == t0: the COW has no host scheduling stage
            prof.observe("page_copy", t0, t0, time.perf_counter(),
                         self.layer_caches[0].k_pages)
        self.prefix_stats["cow_copies"] += 1
        tr = self._tracer
        if tr is not None:
            # rid is unknown during admission claim (the slot joins
            # _slot_req only after the whole wave claims cleanly)
            req = self._slot_req.get(slot)
            if req is not None and tr.want_request(req.rid):
                tr.request(req.rid, "cow", slot=slot,
                           block=int(block_idx), src_page=old,
                           dst_page=int(new))
            elif req is None:
                tr.engine_event("cow", slot=slot, block=int(block_idx),
                                src_page=old, dst_page=int(new))
        return True

    def _cow_for_decode(self, k_steps: int):
        """Before a decode dispatch: every page the next ``k_steps``
        appends can touch must be exclusively owned — a shared page
        (prefix store or another slot holds a ref) is copied first, so
        a decode write can never mutate a cached prefix entry. The
        admission path's block-aligned sharing makes this structurally
        rare (writes land past the shared prefix), but it is the
        invariant the prefix cache's correctness rests on — so the
        check deliberately reads the pool's REAL refcounts for the
        write-window pages (≤2 per slot per dispatch), not admission
        bookkeeping: it must catch sharing from any source, as the
        guard test's external retain() does."""
        if self._prefix is None or not self.cfg.paged \
                or self.pool.shared_pages == 0:
            return
        ps = self.cfg.page_size
        for slot in range(self.cfg.max_slots):
            if not self.active[slot]:
                continue
            lo = int(self.seq_lens[slot]) // ps
            hi = (int(self.seq_lens[slot]) + max(k_steps, 1) - 1) // ps
            n_have = len(self.pool.pages_of[slot])
            for b_idx in range(lo, min(hi, n_have - 1) + 1):
                page = int(self.pool.block_tables[slot, b_idx])
                if self.pool.ref.get(page, 0) > 1:
                    if not self._cow_block(slot, b_idx):
                        raise RuntimeError(
                            "copy-on-write needs a free page but the "
                            "pool is exhausted — size n_pages up")

    def _paged_prefix_admit(self, slot: int, req: Request, need: int,
                            ids=None):
        """Claim pages for a request, sharing the longest cached
        block-aligned prefix. Returns (prefix_len, hashes) or None when
        the pool can't fit the request (slot left clean). A FULL-cover
        hit (prompt entirely cached) adopts every matched page and
        recomputes only the last token — the page it rewrites is
        shared, so it is copy-on-written first."""
        pool = self.pool
        store = None if self._prefix_disabled() else self._prefix
        hashes: List[bytes] = []
        shared: List[int] = []
        prefix_len = 0
        full_cover = False
        if store is not None:
            hashes, shared, prefix_len, full_cover = \
                self._match_prefix(req, ids)
        # feasibility precheck: pages the slot still needs from the
        # free list (adopted pages aren't on it; the full-cover COW
        # consumes one more). A pool-blocked request retries every
        # scheduler tick — without this gate each retry would pay the
        # adopt/release churn, a wasted COW device copy, and worst of
        # all drain LRU store entries via eviction that can't cover
        # the shortfall anyway.
        required = pool.pages_needed(need) - len(shared)
        if full_cover and shared:
            required += 1  # the COW's fresh private page
        supply = pool.free_pages
        # eviction supply reads the REAL store, not the degradation-
        # gated one: min_service only disables ADOPTION — pages the
        # store retains stay evictable, and hiding them here would
        # turn a reclaimable pool into a spurious "size n_pages up"
        # crash (or a permanent pool-block that pins the ladder)
        evict_src = self._prefix
        if required > supply and evict_src is not None:
            supply += evict_src.evictable_pages(pool, exclude=shared)
            if full_cover and shared \
                    and pool.ref.get(shared[-1], 0) == 1:
                # the COW un-borrows the last shared page (back to
                # store-only), so eviction can reclaim it afterwards
                supply += 1
        if required > supply:
            return None  # can't fit even after eviction
        try:
            if shared:
                if not pool.adopt(slot, shared):
                    # over-long share can't happen while add_request
                    # bounds prompt+max_new to max_len — but a silent
                    # no-op here would mean attending over sink pages
                    raise RuntimeError(
                        f"prefix share of {len(shared)} pages exceeds "
                        f"max_pages_per_slot={pool.max_pages_per_slot}")
                if full_cover:
                    # the clamped recompute row ALWAYS lands inside the
                    # last shared page (for page_size 1 it IS that
                    # page, aligned or not — the modulo is no proxy)
                    if not self._cow_block(slot, len(shared) - 1):
                        # can't afford the copy: fall back to
                        # recomputing the whole last block into a fresh
                        # page instead
                        pool.release(pool.pages_of[slot].pop())
                        self.pool.block_tables[slot, len(shared) - 1] = 0
                        prefix_len = (len(shared) - 1) * \
                            self.cfg.page_size
            if not pool.alloc(slot, need):
                missing = pool.pages_needed(need) \
                    - len(pool.pages_of[slot])
                self._evict_pages(missing - pool.free_pages,
                                  prefer_ns=request_namespace(req))
                if not pool.alloc(slot, need):
                    pool.free(slot)  # releases adopted refs too
                    return None
            return prefix_len, hashes
        except BaseException:
            # an error mid-claim (e.g. the COW device dispatch) must
            # leave the slot clean: it never joined the wave's jobs
            # list, so the admission rollback won't free it — stale
            # adopted pages here would wedge the next adopt() or let a
            # later occupant write SHARED pages without copy-on-write
            pool.free(slot)
            raise

    def _prefix_store_insert(self, slot: int, prompt: np.ndarray,
                             hashes: List[bytes], n_matched: int,
                             ns: str = ""):
        """After a request's prefill is dispatched, publish its full
        prompt blocks to the store. Paged: refcount the slot's pages
        (zero copies — the chunk programs already queued the writes on
        the stream, so any future reader is ordered after them).
        Contiguous: slice the new blocks out of the slot's rows."""
        store = None if self._prefix_disabled() else self._prefix
        if store is None or not hashes:
            return
        B = self._prefix_block
        if self.cfg.paged:
            for i, digest in enumerate(hashes):
                store.insert(digest, int(self.pool.block_tables[slot, i]),
                             self.pool, ns=ns)
        else:
            for i in range(n_matched, len(hashes)):
                if hashes[i] in store:
                    continue
                with self._ctx():
                    k, v = self._read_block_contig()(
                        self.caches, slot, i * B)
                # protect the chain being inserted: same-ns eviction
                # must not eat this prompt's own earlier blocks
                store.insert(hashes[i], k, v, ns=ns, protect=hashes)
            evicted = store.evictions - self.prefix_stats["evictions"]
            if evicted > 0:
                self.prefix_stats["evictions"] = store.evictions
                if self._tel is not None:
                    self._tel.on_prefix_evict(evicted,
                                              store.cached_pages)

    # ---------------- scheduling ----------------
    def _admit_dispatch(self):
        """Dispatch prefill programs for every admissible queued request
        WITHOUT syncing the host (JAX dispatch is async: everything
        queues on the device stream behind any in-flight decode chunk).
        Default path: prefix-cache lookup + single-program CHUNKED
        prefill; ``PT_FLAGS_prefill_chunk=0`` selects the legacy
        per-bucket path (the parity oracle). Returns the pending
        (req, slot, first_token_future) list for
        ``_admit_integrate``."""
        # fresh verdict each attempt: the flag self-heals the moment an
        # admission pass no longer blocks on the pool (the previous
        # verdict survives in _pool_blocked_prev for the policy's
        # preemption window, which runs before this pass can re-judge)
        self._pool_blocked_prev = self._pool_blocked
        self._pool_blocked = False
        if not self._queue:
            return []
        if self._draining and (not self._chunk_len
                               or not self._drain_pending()):
            # drain(): stop admitting FRESH requests — they stay
            # queued for a resume() or the router to re-dispatch. The
            # exception: crash-recovery replays (requests that were
            # already in flight once) stay admissible on the chunked
            # path, or a quarantine mid-drain would silently strand
            # its victims behind a closed admission gate
            return []
        inj = self._injector
        if inj is not None and inj.fire("pool"):
            # simulated KV-pool exhaustion: admission blocks this tick
            # exactly like a real pool-blocked head request would —
            # backpressure()/healthz report saturated, the ladder sees
            # a capacity signal (never a fault), and the next clean
            # tick self-heals
            self._note_fault("pool", "admission")
            self._pool_blocked = True
            return []
        if self._chunk_len:
            return self._admit_dispatch_chunked()
        return self._admit_dispatch_bucketed()

    def _admit_dispatch_chunked(self):
        """Chunked admission wave: claim slots + pages (prefix-aware)
        for every admissible request, then drive ONE fixed-shape chunk
        program over all of them together — request A's chunk 2 and
        request B's chunk 0 ride the same call, packed behind the
        in-flight decode chunk. All-or-nothing on error: a failure
        mid-wave rolls every claimed request back into the queue (FIFO
        preserved) before propagating. Within one wave a request
        cannot hit blocks published by an earlier request of the SAME
        wave (store inserts land at the end); across waves it does."""
        C = self._chunk_len
        cfg = self.cfg
        ctl = self._degctl
        shed = ctl is not None and ctl.shed_batch
        throttle = ctl is not None and ctl.throttle
        jobs = []  # [req, slot, prefix_len, hashes, n_matched, cursor,
        #            ids] — ids: the prefill token sequence (prompt, or
        #            prompt+history for a crash-recovery replay)
        # rids deferred this wave (shed batch / draining-fresh /
        # just-preempted): they stay IN the queue at their position —
        # deferral is a skip, never a reorder. fifo_cursor: the FIFO
        # path's wave-local [snapshot, index] (see _pick_admission)
        skip = set()
        fifo_cursor = [None, 0]
        if self._sched is not None:
            # the policy's preemption window: it may release slots
            # (engine.preempt → requeued at the front) for this very
            # wave; preempted rids must not re-admit in the same wave
            # (their freed slots are what the wave is FOR)
            skip.update(self._sched.before_admission(self) or ())
        try:
            while self._free_heap:
                if throttle and jobs:
                    break  # degraded: at most one admission per wave
                req = self._pick_admission(skip, fifo_cursor)
                if req is None:
                    break
                if shed and req.slo == "batch":
                    # degradation L1+: defer (never drop) batch-class
                    # admissions; they keep their queue position
                    skip.add(req.rid)
                    continue
                if self._draining and not (req._retries or req.output):
                    # draining: only in-flight-once replays admit;
                    # fresh requests defer in place
                    skip.add(req.rid)
                    continue
                slot = self._free_heap[0]  # peek; claimed below
                ids = self._prefill_ids(req)
                n = ids.size
                # replay: the history is part of ids, so the new-token
                # budget shrinks by what was already generated — the
                # page need is identical to the original admission's
                need = n + req.max_new_tokens - len(req.output)
                prefix_len, hashes, n_matched = 0, [], 0
                if cfg.paged:
                    got = self._paged_prefix_admit(slot, req, need, ids)
                    if got is None:
                        if not self.active.any() and not jobs:
                            raise RuntimeError(
                                f"request {req.rid} needs "
                                f"{self.pool.pages_needed(need)} pages "
                                f"but the pool has "
                                f"{self.pool.free_pages} free with no "
                                "request running — size n_pages up")
                        self._pool_blocked = True
                        break  # pool exhausted: wait for a finisher
                    prefix_len, hashes = got
                    n_matched = prefix_len // cfg.page_size
                elif not self._prefix_disabled() \
                        and self._prefix is not None:
                    hashes, matched, prefix_len, _full = \
                        self._match_prefix(req, ids)
                    n_matched = len(matched)
                    B = self._prefix_block
                    with self._ctx():
                        for i, (kb, vb) in enumerate(matched):
                            self.caches = self._insert_prefix_contig()(
                                self.caches, kb, vb, slot, i * B)
                # commit: head popleft when possible (the FIFO fast
                # path's O(1) twin), else remove by IDENTITY (the
                # policy may have picked mid-queue; deque.remove
                # matches `is` first)
                if self._queue and self._queue[0] is req:
                    self._queue.popleft()
                else:
                    self._queue.remove(req)
                if skip:
                    # cursor mode: the wave snapshot may still hold
                    # this (now-claimed) request — mark it consumed
                    skip.add(req.rid)
                heapq.heappop(self._free_heap)
                self.active[slot] = True
                req.slot = slot
                self._slot_req[slot] = req
                if self._sched is not None:
                    self._sched.note_admit(self, req)
                # 6th element: the prefill cursor (starts at the
                # prefix boundary; _drive_prefill_chunks advances it —
                # prefix_len itself stays pristine for the stats
                # commit)
                jobs.append(
                    [req, slot, prefix_len, hashes, n_matched,
                     prefix_len, ids])
            if not jobs:
                return []
            return self._drive_prefill_chunks(jobs)
        except BaseException as e:
            # all-or-nothing rollback: free claimed slots/pages and
            # requeue in submission order so a caught admission error
            # neither shrinks the engine nor strands a request
            for req, slot, *_ in reversed(jobs):
                self.active[slot] = False
                self._slot_req.pop(slot, None)
                req.slot = None
                heapq.heappush(self._free_heap, slot)
                if self.pool is not None:
                    self.pool.free(slot)
                self._queue.appendleft(req)
            if isinstance(e, InjectedFault) \
                    and self._recovery_mode != "off":
                # injected prefill-seam fault: the rollback above IS
                # the quarantine (requests back in the queue, slots
                # and pages clean) — count the recovery, charge each
                # wave member one retry, and admit again next tick
                self._after_admission_fault(e, [j[0] for j in jobs])
                return []
            raise

    def _drive_prefill_chunks(self, jobs):
        """Host loop over suffix chunks for a wave of claimed requests.
        Each iteration packs every still-prefilling request's next C
        tokens into one [slots, C] call; slots with nothing to prefill
        (or actively decoding) carry the ``start = max_len`` sentinel —
        their writes drop in-program and their sampled output is
        ignored."""
        C = self._chunk_len
        cfg = self.cfg
        sentinel = cfg.max_len
        pending = []
        remaining = list(jobs)
        # block tables are fixed once the claim loop ends — upload once
        # per wave, not per chunk iteration
        bt = (jnp.asarray(self.pool.block_tables) if cfg.paged
              else jnp.zeros((1,), jnp.int32))
        # first-token sampling params for the wave's requests (slots
        # not in the wave carry defaults — their sampled output is the
        # ignored sentinel row)
        use_samp, samp = self._slot_sampling(
            [(job[1], job[0]) for job in jobs])
        tr = self._tracer
        while remaining:
            t0 = time.perf_counter()
            # fault seam: an injected fault here quarantines the WHOLE
            # wave through the admission rollback (slots/pages freed,
            # requests requeued, one retry charged each)
            self._fault_point("prefill_chunk")
            ids = np.zeros((cfg.max_slots, C), np.int64)
            start = np.full((cfg.max_slots,), sentinel, np.int32)
            last_idx = np.zeros((cfg.max_slots,), np.int32)
            finishing = []
            packed = 0
            call_shares = [] if self._cost_enabled else None
            for job in remaining:
                req, slot, p, job_ids = job[0], job[1], job[5], job[6]
                take = min(C, job_ids.size - p)
                ids[slot, :take] = job_ids[p:p + take]
                start[slot] = p
                if p + take >= job_ids.size:
                    last_idx[slot] = job_ids.size - 1 - p
                    finishing.append(job)
                job[5] = p + take
                packed += take
                if call_shares is not None:
                    call_shares.append((req, take))
                if tr is not None and tr.want_request(req.rid):
                    tr.request(req.rid, "prefill_chunk", start=int(p),
                               tokens=int(take), slot=slot)
            self._key, sub = jax.random.split(self._key)
            caches = self.layer_caches if cfg.paged else self.caches
            prof = self._prof
            p_want = prof is not None and prof.want("prefill_chunk")
            p_dec = None
            t_call = time.perf_counter()
            with jax.profiler.TraceAnnotation(
                    "pt.engine.dispatch", program="prefill_chunk"), \
                    self._ctx():
                toks, caches = self._prefill_chunked()(
                    self._pb, jnp.asarray(ids, jnp.int32), caches, bt,
                    jnp.asarray(start), jnp.asarray(last_idx), sub,
                    samp, use_samp)
            if cfg.paged:
                self.layer_caches = caches
            else:
                self.caches = caches
            if p_want:
                # sampled: measure the chunk program itself (its
                # device time otherwise surfaces only inside the NEXT
                # decode/verify step's sync window)
                p_dec = prof.observe("prefill_chunk", t0, t_call,
                                     time.perf_counter(), toks)
                if call_shares:
                    # prefill cost attributes only on MEASURED calls:
                    # an unsampled chunk is async — its device time
                    # surfaces in the next step's sync window, and
                    # charging host-dispatch wall as device cost
                    # would be dishonest. Reconciliation holds at
                    # profile_sample_every=1.
                    self._attribute_cost(
                        "prefill_chunk", p_dec["device_ms"], True,
                        call_shares)
            if tr is not None:
                # unsampled dispatches stay a dispatch-only span: the
                # chunk program is async — its device time surfaces in
                # the NEXT decode/verify step's sync window, so only
                # host dispatch wall is honest without the profiler
                seq = tr.next_step()
                if tr.want_step(seq):
                    tr.step(seq, "prefill_chunk", t0,
                            time.perf_counter(),
                            prefilling=len(remaining),
                            tokens_packed=packed, chunk=C,
                            chunk_budget_spent=packed,
                            occupancy=float(self.active.sum())
                            / cfg.max_slots,
                            rids=[int(j[0].rid) for j in remaining],
                            **(dict(p_dec, profiled=True)
                               if p_dec is not None else {}))
            for job in finishing:
                pending.append((job[0], job[1], job[6].size,
                                toks[job[1]]))
            done_slots = {j[1] for j in finishing}  # slots are unique
            remaining = [j for j in remaining if j[1] not in done_slots]
        # the wave is committed: only now do the prompts' blocks
        # publish and hit/miss stats count — the all-or-nothing
        # rollback path can't double-count a requeued request. Insert
        # BEFORE note so the cached-pages gauge reflects this
        # request's own published blocks.
        for req, slot, prefix_len, hashes, n_matched, _cursor, ids_arr \
                in jobs:
            self._prefix_store_insert(slot, ids_arr, hashes, n_matched,
                                      ns=request_namespace(req))
            if self._prefix is not None and not self._prefix_disabled():
                self._note_prefix(prefix_len, ids_arr.size, req)
        return pending

    def _admit_dispatch_bucketed(self):
        """Legacy per-bucket admission (PT_FLAGS_prefill_chunk=0): one
        jit specialization per seq bucket, whole-prompt recompute — the
        pre-chunking trace, kept as the parity oracle."""
        pending = []
        while self._queue and self._free_heap:
            req = self._queue[0]
            slot = self._free_heap[0]  # peek; claimed only on success
            ids_arr = self._prefill_ids(req)
            n = ids_arr.size
            # paged: allocate for the full prefill bucket too — the
            # prefill scatter writes bucket//page_size whole pages, and
            # a bucket coarser than prompt+max_new must not spill into
            # the sink page or pages owned by other slots
            need = max(n + req.max_new_tokens - len(req.output),
                       self._bucket(n))
            if self.cfg.paged and not self.pool.alloc(slot, need):
                if not self.active.any() and not pending:
                    raise RuntimeError(
                        f"request {req.rid} needs "
                        f"{self.pool.pages_needed(need)} pages but the "
                        f"pool has {self.pool.free_pages} free with no "
                        "request running — size n_pages up")
                self._pool_blocked = True
                break  # pool exhausted: wait for a finisher
            self._queue.popleft()
            heapq.heappop(self._free_heap)
            t0 = time.perf_counter()
            try:
                bucket = self._bucket(n)
                padded = np.zeros((1, bucket), np.int64)
                padded[0, :n] = ids_arr
                one_caches = self.model.init_kv_caches(
                    1, bucket, dtype=self.cache_dtype)
                self._key, sub = jax.random.split(self._key)
                use_samp = self._req_nondefault(req)
                samp = (
                    jnp.asarray([self._req_greedy(req)]),
                    jnp.asarray([max(
                        req.temperature if req.temperature is not None
                        else self.cfg.temperature, 1e-6)], jnp.float32),
                    jnp.asarray([req.top_k or 0], jnp.int32),
                    jnp.asarray([req.top_p if req.top_p is not None
                                 else 1.0], jnp.float32))
                prof = self._prof
                p_want = prof is not None \
                    and prof.want("prefill_bucket")
                p_dec = None
                t_call = time.perf_counter()
                with self._ctx():
                    first_dev, filled = self._prefill()(
                        self._pb, jnp.asarray(padded, jnp.int32),
                        one_caches, n - 1, sub, samp, use_samp)
                    if p_want:
                        p_dec = prof.observe(
                            "prefill_bucket", t0, t_call,
                            time.perf_counter(), (first_dev, filled))
                        if self._cost_enabled:
                            # single-request program: the whole
                            # measured wall is this request's
                            self._attribute_cost(
                                "prefill_bucket", p_dec["device_ms"],
                                True, [(req, n)])
                    if self.cfg.paged:
                        self.layer_caches = self._scatter_paged()(
                            self.layer_caches, filled,
                            jnp.asarray(self.pool.block_tables[slot]))
                    else:
                        self.caches = self._insert_contig()(
                            self.caches, filled, slot)
            except BaseException:
                # the heap no longer self-heals from the active mask:
                # give the claimed slot (and its pages) back AND requeue
                # the request before propagating, or a caught admission
                # error would shrink the engine by one slot forever and
                # strand the request's rid incomplete. Requests admitted
                # EARLIER in this call are already active — integrate
                # them now (lengths/first tokens) so a caller that
                # catches the error doesn't decode them from seq_len 0
                heapq.heappush(self._free_heap, slot)
                if self.pool is not None:
                    self.pool.free(slot)
                self._queue.appendleft(req)
                self._admit_integrate(pending)
                raise
            # mark the slot taken now so the next iteration can't hand
            # it out again; lengths/last_tok land at integrate
            self.active[slot] = True
            req.slot = slot
            self._slot_req[slot] = req
            pending.append((req, slot, n, first_dev))
            tr = self._tracer
            if tr is not None:
                seq = tr.next_step()
                if tr.want_step(seq):
                    tr.step(seq, "prefill_bucket", t0,
                            time.perf_counter(), rid=int(req.rid),
                            bucket=int(bucket), prompt_tokens=int(n),
                            occupancy=float(self.active.sum())
                            / self.cfg.max_slots,
                            **(dict(p_dec, profiled=True)
                               if p_dec is not None else {}))
        return pending

    def _admit_integrate(self, pending):
        """Sync each admitted request's first token (a scalar transfer)
        and finish its bookkeeping; the sequence joins the NEXT decode
        chunk. ``n_ctx`` is the prefilled context length — the prompt,
        or prompt+history for a crash-recovery replay, whose original
        TTFT and admit instant are preserved (per-request TPOT stays
        the honest wall from FIRST admission to last token, fault
        stalls included). Returns the fresh admissions and the sum
        of their submit-to-admit times, the arguments of the caller's
        ``pt.engine.admit`` span."""
        admitted, wait_ms = 0, 0.0
        for req, slot, n_ctx, first_dev in pending:
            # a wait for the device like the step's own (the prefill
            # program has to finish), and named like it
            with jax.profiler.TraceAnnotation("pt.engine.sync"):
                first = int(first_dev)  # scalar, not [1, bucket, vocab]
            now = time.perf_counter()
            fresh = req.ttft_ms is None
            if fresh:
                req._admit_t = now
                req.ttft_ms = (now - req._submit_t) * 1e3
                admitted += 1
                wait_ms += req.ttft_ms
            req.output.append(first)
            # the prefill-sampled first token counts toward the
            # flight-data token counter too (telemetry's on_admit/
            # on_readmit make the same call) — a prefill-heavy window
            # must not read as zero tokens
            self._tokens_emitted += 1
            self.seq_lens[slot] = n_ctx
            self.last_tok[slot] = first
            if self._tel is not None:
                if fresh:
                    self._tel.on_admit(req.ttft_ms)
                else:
                    self._tel.on_readmit()
            tr = self._tracer
            if tr is not None and tr.want_request(req.rid):
                if fresh:
                    # the span covers queue wait + prefill: exactly TTFT
                    tr.request(req.rid, "admitted", t0=req._submit_t,
                               t1=now, slot=slot,
                               ttft_ms=req.ttft_ms, first_tokens=1,
                               prompt_tokens=int(req.prompt.size))
                else:
                    tr.request(req.rid, "readmitted", slot=slot,
                               retries=int(req._retries),
                               replayed_tokens=int(n_ctx
                                                   - req.prompt.size))
            self._maybe_finish(slot, first)
        return {"admitted": admitted, "queue_wait_ms_sum": wait_ms}

    def _admit(self):
        """Blocking admission (dispatch + integrate) with the same
        crash-recovery coverage as the step paths: JAX dispatch is
        async, so a prefill program's runtime failure surfaces at its
        first-token SYNC in ``_admit_integrate`` — without this guard
        the exact fault class ``serve_recovery`` promises to survive
        would crash the idle-engine admission path."""
        try:
            with jax.profiler.TraceAnnotation("pt.engine.admit") as span:
                span.set_metadata(**self._admit_integrate(
                    self._admit_dispatch()))
        except BaseException as e:
            if not self._recoverable(e):
                raise
            self._recover_step(e, self.active.copy(), "admit")

    def _integrate_guarded(self, pending, program: str):
        """``_admit_integrate`` as a recovery point: the first-token
        sync is where an async prefill failure actually lands."""
        try:
            with jax.profiler.TraceAnnotation("pt.engine.admit") as span:
                span.set_metadata(**self._admit_integrate(pending))
        except BaseException as e:
            if not self._recoverable(e):
                raise
            self._recover_step(e, self.active.copy(), program)

    def _slo_bucket(self, slo: str) -> Dict[str, int]:
        st = self.slo_stats.get(slo)
        if st is None:
            st = self.slo_stats[slo] = new_slo_bucket()
        return st

    def _tenant_bucket(self, tenant: Optional[str]) -> Dict[str, float]:
        """Cumulative per-tenant host counters (``"-"`` = untagged) —
        written at finish/preempt on the scheduler thread, read via
        ``tenant_snapshot()``."""
        key = tenant or "-"
        st = self.tenant_stats.get(key)
        if st is None:
            st = self.tenant_stats[key] = {
                "finished": 0, "cancelled": 0, "timeouts": 0,
                "failed": 0, "tokens": 0, "device_ms": 0.0,
                "slo_met": 0, "slo_violated": 0, "preemptions": 0,
            }
        return st

    def _finish_accounting(self, req: Request, reason: str):
        """Shared finish/cancel bookkeeping: per-request TPOT, SLO
        attainment (host ``slo_stats`` + telemetry counters + goodput
        gauge), and the tracer's closing ``active`` span. Pure host
        arithmetic over values the scheduler already holds."""
        now = time.perf_counter()
        req.finish_reason = reason
        n_decode = len(req.output) - 1  # first token priced into TTFT
        if req._admit_t and n_decode > 0:
            req.tpot_ms = (now - req._admit_t) * 1e3 / n_decode
        tst = self._tenant_bucket(req.tenant)
        tst["tokens"] += len(req.output)
        if reason in ("cancel", "timeout", "failed"):
            tst[{"cancel": "cancelled", "timeout": "timeouts",
                 "failed": "failed"}[reason]] += 1
        else:
            tst["finished"] += 1
        if req.slo is not None and reason == "cancel":
            self._slo_bucket(req.slo)["cancelled"] += 1
        elif req.slo is not None and reason in ("timeout", "failed"):
            # an expired or retries-exhausted request never delivered:
            # forced SLO violation — a timed-out request that happened
            # to meet its TTFT must not inflate goodput
            st = self._slo_bucket(req.slo)
            req.slo_met = False
            st["violated"] += 1
            tst["slo_violated"] += 1
            if reason == "timeout":
                st["timeouts"] += 1
            st["total_tokens"] += len(req.output)
            if self._tel is not None:
                self._tel.on_slo(req.slo, False,
                                 tenant=req.tenant or "-")
        elif req.slo is not None:
            st = self._slo_bucket(req.slo)
            ttft_ok = (req.ttft_target_ms is None
                       or (req.ttft_ms is not None
                           and req.ttft_ms <= req.ttft_target_ms))
            tpot_ok = (req.tpot_target_ms is None or req.tpot_ms is None
                       or req.tpot_ms <= req.tpot_target_ms)
            req.slo_met = ttft_ok and tpot_ok
            st["met" if req.slo_met else "violated"] += 1
            tst["slo_met" if req.slo_met else "slo_violated"] += 1
            if not ttft_ok:
                st["ttft_violations"] += 1
            if not tpot_ok:
                st["tpot_violations"] += 1
            st["total_tokens"] += len(req.output)
            if req.slo_met:
                st["met_tokens"] += len(req.output)
            if self._tel is not None:
                self._tel.on_slo(req.slo, req.slo_met,
                                 tenant=req.tenant or "-")
        tr = self._tracer
        if tr is not None and tr.want_request(req.rid):
            t0 = req._admit_t or now
            if reason == "cancel":
                tr.request(req.rid, "cancel",
                           stage="active" if req._admit_t else "queued",
                           tokens=len(req.output))
            else:
                tr.request(req.rid, "active", t0=t0, t1=now,
                           tokens=len(req.output), reason=reason,
                           tpot_ms=req.tpot_ms, slo=req.slo or "",
                           slo_met=req.slo_met)

    def _release_slot(self, slot: int):
        """Return a slot to the scheduler: active flag, length, free
        heap, request map, and (paged) every page ref — the ONE
        teardown path finish and cancel both use."""
        self.active[slot] = False
        self.seq_lens[slot] = 0
        heapq.heappush(self._free_heap, slot)
        del self._slot_req[slot]
        if self.pool is not None:
            self.pool.free(slot)  # releases adopted prefix refs too

    def _maybe_finish(self, slot: int, tok: int):
        req = self._slot_req.get(slot)
        if req is None:
            return
        hit_eos = (req.eos_token_id is not None and tok == req.eos_token_id)
        if hit_eos:
            reason = "eos"
        elif len(req.output) >= req.max_new_tokens:
            reason = "max_new_tokens"
        elif self.seq_lens[slot] + 1 >= self.cfg.max_len:
            reason = "max_len"
        else:
            return
        req.done = True
        self._finished[req.rid] = req
        self._release_slot(slot)
        self._finish_accounting(req, reason)
        if self._cost_enabled:
            # defer the finish-time cost record past the step's
            # attribution pass: this request's final chunk share has
            # not been split yet (flushed in the step wrapper)
            self._cost_pending.append(req)
        if self._tel is not None:
            self._tel.on_finish(req.tpot_ms)

    def cancel(self, request_id: int) -> bool:
        """Cancel a request mid-flight, leak-free: a QUEUED request is
        removed from the queue; an ACTIVE one frees its slot and
        releases every paged KV page AND prefix-cache ref it holds
        (``pool.free`` decrements per-page refcounts, so shared prefix
        pages survive in the store — only this request's ownership is
        dropped). Returns False for unknown / already-finished ids.

        Call from the scheduler thread (the same contract as ``step``):
        an in-flight decode chunk's later writes to the freed pages are
        stream-ordered BEFORE any re-allocation's prefill writes, so
        cancellation never corrupts a successor — the host loop skips
        the cancelled slot's remaining chunk tokens via the ``active``
        mask. The canonical drain primitive ROADMAP item 5's
        timeout/priority scheduler builds on."""
        # queued: remove without ever granting a slot. Snapshot-then-
        # remove-by-identity: add_request may append from a producer
        # thread, and deque iteration raises on concurrent mutation
        # while remove() is a single atomic op.
        req = next((r for r in list(self._queue)
                    if r.rid == request_id), None)
        if req is not None:
            try:
                self._queue.remove(req)
            except ValueError:
                req = None  # raced out of the queue
        if req is None:
            # active: free the slot + pages
            slot = next((s for s, r in self._slot_req.items()
                         if r.rid == request_id), None)
            if slot is None:
                return False
            req = self._slot_req[slot]
            self._release_slot(slot)
        req.done = True
        req.cancelled = True
        self._finished[request_id] = req
        self._finish_accounting(req, "cancel")
        # record immediately: a cancel lands between ticks, with no
        # pending step share to wait for
        self._record_cost_finish(req)
        if self._tel is not None:
            self._tel.on_cancel()
        return True

    # ---------------- resilience ----------------
    def _prefix_disabled(self) -> bool:
        """True while the degradation ladder has switched prefix-cache
        adoption off (min_service) — admission neither matches nor
        publishes; outputs are unchanged, only prefill work grows."""
        return self._degctl is not None and self._degctl.disable_prefix

    def _finish_request(self, req: Request, reason: str):
        """Terminal bookkeeping for a request that leaves the engine
        WITHOUT a normal finish: deadline expiry (``timeout``) or
        retry exhaustion (``failed``). The caller has already removed
        it from the queue or released its slot."""
        req.done = True
        self._finished[req.rid] = req
        self._finish_accounting(req, reason)
        # record immediately: timeout expiry runs at tick START and
        # retry exhaustion inside a quarantine — neither has a pending
        # step share (the failed step's device work is never
        # attributed), and a reclaimed replica may never tick again
        self._record_cost_finish(req)
        if self._tel is not None:
            if reason == "timeout":
                self._tel.on_timeout()
            elif reason == "failed":
                self._tel.on_failed()

    def _expire_deadlines(self):
        """Enforce per-request deadlines: queued requests leave the
        queue, active ones release their slot/pages/prefix refs
        through the one teardown path (``_release_slot``), and both
        finish with reason ``"timeout"``. Checked once per scheduler
        tick — the granularity ``add_request`` validates deadlines
        against."""
        now = time.perf_counter()
        # queued: snapshot-then-remove-by-identity (same concurrency
        # contract as cancel(): add_request may append from a producer
        # thread; deque.remove is a single atomic op)
        for req in list(self._queue):
            if req._deadline_t and now >= req._deadline_t:
                try:
                    self._queue.remove(req)
                except ValueError:
                    continue  # raced out of the queue
                self.resilience_stats["timeouts"] += 1
                self._finish_request(req, "timeout")
        for slot in range(self.cfg.max_slots):
            if not self.active[slot]:
                continue
            req = self._slot_req[slot]
            if req._deadline_t and now >= req._deadline_t:
                self._release_slot(slot)
                self.resilience_stats["timeouts"] += 1
                self._finish_request(req, "timeout")

    def _bump_retry(self, req: Request) -> bool:
        """Charge one replay retry. Returns True while the request may
        be re-queued; past its bound it finishes with reason
        ``"failed"`` (and is pulled from the queue if it sits there)."""
        req._retries += 1
        req._hashes = None  # replay ids differ: stale digests invalid
        limit = (req.max_retries if req.max_retries is not None
                 else self.cfg.max_retries)
        if req._retries > limit:
            try:
                self._queue.remove(req)
            except ValueError:
                pass
            self.resilience_stats["failed"] += 1
            self._finish_request(req, "failed")
            return False
        self.resilience_stats["retries"] += 1
        if self._tel is not None:
            self._tel.on_retry()
        return True

    def _note_fault(self, site: str, program: str):
        st = self.resilience_stats
        st["faults"][site] = st["faults"].get(site, 0) + 1
        if self._tel is not None:
            self._tel.on_fault(site)
        if self._tracer is not None:
            self._tracer.engine_event("fault", site=site,
                                      program=program)

    def _fault_point(self, program: str):
        """One dispatch seam: consult the injector's latency schedule
        (stall in place), then the raising sites — an
        ``InjectedFault`` raised HERE precedes the compiled call, so
        the device cache state is untouched and recovery can requeue
        without rebuilding."""
        inj = self._injector
        if inj is None:
            return
        if inj.fire("latency"):
            self._note_fault("latency", program)
            time.sleep(inj.latency_ms / 1e3)
        for site in ("step", "nan"):
            if inj.fire(site):
                raise InjectedFault(site, program)

    def _recoverable(self, exc: BaseException) -> bool:
        """PT_FLAGS_serve_recovery policy: injected faults always
        recover (unless off); ``auto`` additionally recovers XLA
        runtime errors (device failures) but NEVER host logic errors —
        a plain RuntimeError from scheduler code must propagate;
        ``all`` recovers any Exception."""
        mode = self._recovery_mode
        if mode == "off":
            return False
        if isinstance(exc, InjectedFault):
            return True
        if mode == "all":
            return isinstance(exc, Exception)
        return bool(RUNTIME_ERRORS) and isinstance(exc, RUNTIME_ERRORS)

    def _after_admission_fault(self, exc: InjectedFault,
                               reqs: List[Request]):
        """An injected prefill-seam fault after the wave rollback:
        the quarantine already happened (slots/pages freed, requests
        requeued in order) — account it and charge retries."""
        st = self.resilience_stats
        st["recoveries"] += 1
        site = exc.site
        st["faults"][site] = st["faults"].get(site, 0) + 1
        if site == "nan":
            st["nan_steps"] += 1
            self._nan_dump(exc.program, len(reqs))
        self._faults_tick += 1
        for req in reqs:
            self._bump_retry(req)
        if self._tel is not None:
            self._tel.on_fault(site)
            self._tel.on_recovery(len(reqs))
        if self._tracer is not None:
            self._tracer.engine_event(
                "recovery", site=site, program=exc.program,
                requeued=len(reqs), hard=False)

    def _recover_step(self, exc: BaseException, participants,
                      program: str):
        """Quarantine a failed step: discard its device effects and
        re-queue the affected in-flight requests for deterministic
        replay. Generated tokens live host-side, so replay re-prefills
        prompt+history through the existing chunked-prefill program —
        greedy outputs stay bit-identical to a fault-free run, and the
        replayed admission re-uses the SAME compiled programs (zero
        new specializations, pinned by test).

        Severity: an ``InjectedFault`` fires BEFORE dispatch, so the
        caches are intact — only the step's participants requeue and
        the prefix store survives. Any other (real) runtime failure
        means donated buffers may be gone: every active request
        requeues, the prefix store is dropped and the cache pools are
        rebuilt (same shapes — nothing recompiles)."""
        hard = not isinstance(exc, InjectedFault)
        site = getattr(exc, "site", "error")
        st = self.resilience_stats
        st["recoveries"] += 1
        st["faults"][site] = st["faults"].get(site, 0) + 1
        if site == "nan":
            st["nan_steps"] += 1
        self._faults_tick += 1
        victims = [s for s in range(self.cfg.max_slots)
                   if self.active[s] and (hard or participants[s])]
        requeued = 0
        # reversed + appendleft: victims land at the queue front in
        # ascending slot order, ahead of younger arrivals
        for slot in reversed(victims):
            req = self._slot_req[slot]
            self._release_slot(slot)
            req.slot = None
            if self._bump_retry(req):
                self._queue.appendleft(req)
                requeued += 1
        if hard:
            st["rebuilds"] += 1
            self._rebuild_caches()
        if site == "nan":
            self._nan_dump(program, requeued)
        if self._tel is not None:
            self._tel.on_fault(site)
            self._tel.on_recovery(requeued)
        if self._tracer is not None:
            self._tracer.engine_event(
                "recovery", site=site, program=program,
                requeued=requeued, failed=len(victims) - requeued,
                hard=hard, error=type(exc).__name__)

    def _rebuild_caches(self):
        """Hard crash recovery: after a non-injected runtime failure
        the device cache state is untrusted (the failed call may have
        consumed its donated buffers), so rebuild the pools from
        scratch and DROP the prefix store — paged entries reference
        pages of the discarded pool; contiguous blocks are content-
        addressed but a corrupted write can't be ruled out. Every slot
        was already released by the caller. Same shapes → the jitted
        programs never re-specialize."""
        if self._prefix is not None:
            if self.cfg.paged:
                # all slots freed → every entry is un-borrowed: this
                # empties the store and returns its refs to the pool
                # being discarded (keeps the refcount audit clean)
                self._evict_pages(10 ** 9)
            else:
                self._prefix = ContigPrefixStore(self._prefix.max_blocks)
        self._init_cache_state()

    def _nan_dump(self, program: str, requeued: int):
        """NaN-logits storm postmortem: ride PR 2's flight recorder —
        the dump attaches the lifecycle tracer's tail, so the artifact
        shows WHAT the engine was doing, not just that logits went
        non-finite. Telemetry off → no artifact (host counters still
        count)."""
        if self._tel is None:
            return
        if self._recorder is None:
            self._recorder = observability.FlightRecorder(
                capacity=int(flags.flag("telemetry_flight_window")),
                dump_dir=str(flags.flag("telemetry_dump_dir")))
        # no wall-clock stamp here: dump() writes its own unix_time,
        # and the engine's deterministic paths stay perf_counter-only
        self._recorder.record(
            kind="serve_nan", program=program, requeued=requeued,
            engine=self._tel.engine_id)
        self._recorder.dump(
            f"serving NaN-logits storm in {program} "
            f"(engine {self._tel.engine_id})")

    def _observe_health(self):
        """One degradation-ladder tick: saturation from the live
        admission state, faults accumulated since the last tick.
        Under ``PT_FLAGS_slo_degradation`` (default off) an ACTIVE
        SLO burn-rate alert also counts as saturation pressure — the
        documented read-only ``AlertManager.is_active`` hook: the
        engine is missing latency targets, which is a capacity
        problem, so sustained burn climbs the capacity rungs (shed
        batch / throttle) and never the fault jump. With the flag off
        the ladder's inputs are untouched (outputs pinned
        identical)."""
        if self._degctl is None:
            self._faults_tick = 0
            return
        qd = len(self._queue)
        sat = qd > 0 and (len(self._free_heap) == 0
                          or self._pool_blocked)
        if self._slo_degradation and self._alerts is not None \
                and self._alerts.is_active("slo_burn_rate"):
            sat = True
        before = self._degctl.level
        level = self._degctl.observe(saturated=bool(sat),
                                     faults=self._faults_tick)
        self._faults_tick = 0
        if level != before:
            if self._tel is not None:
                self._tel.on_degradation(level)
            if self._tracer is not None:
                self._tracer.engine_event(
                    "degrade", level=level, previous=before,
                    level_name=self._degctl.name)

    def _drain_pending(self) -> List[Request]:
        """Queued requests that were already in flight once (crash-
        recovery replays): drain() owes these completion — they are
        'in-flight' work even while they sit in the queue."""
        return [r for r in self._queue if r._retries or r.output]

    def drain(self, deadline_ms: Optional[float] = None,
              max_chunk: int = 8) -> dict:
        """Graceful shutdown primitive: stop admitting fresh requests
        (they stay queued for the router to re-dispatch), run every
        in-flight request to completion — INCLUDING requests a
        mid-drain quarantine re-queued for replay — or to
        ``deadline_ms``, past which the stragglers finish with reason
        ``"timeout"`` and their slots/pages/prefix refs are provably
        freed. ``/healthz`` reports ``draining`` (503) for the
        duration and after, until ``resume()``.

        Returns a summary dict whose ``"unfinished"`` entry carries
        the HANDOFF PAYLOAD: one :func:`request_ledger` per request
        that did not finish here — deadline-expired stragglers first
        (ledger captured BEFORE their timeout teardown), then the
        still-queued fresh requests in queue order. A caller (the
        router's rebalance/failover path, or any operator script) can
        re-admit each ledger elsewhere via ``admit_ledger`` and the
        request continues bit-identically with its original TTFT/SLO
        clock."""
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be > 0; got {deadline_ms}")
        self._draining = True
        if self._tel is not None:
            self._tel.on_drain(True)
        if self._tracer is not None:
            self._tracer.engine_event(
                "drain_begin", active=int(self.active.sum()),
                queued=len(self._queue))
        t_end = (None if deadline_ms is None
                 else time.perf_counter() + deadline_ms / 1e3)
        expired = 0
        unfinished: List[dict] = []
        while self.active.any() or self._drain_pending():
            if t_end is not None and time.perf_counter() >= t_end:
                for slot in range(self.cfg.max_slots):
                    if not self.active[slot]:
                        continue
                    req = self._slot_req[slot]
                    # ledger BEFORE teardown: the straggler times out
                    # HERE, but its history survives in the payload so
                    # a caller may still re-admit it elsewhere
                    unfinished.append(request_ledger(req))
                    self._release_slot(slot)
                    self.resilience_stats["timeouts"] += 1
                    self._finish_request(req, "timeout")
                    expired += 1
                for req in self._drain_pending():
                    # replay victims still waiting on a slot expire
                    # too — a drain deadline leaves NOTHING in limbo
                    try:
                        self._queue.remove(req)
                    except ValueError:
                        continue
                    unfinished.append(request_ledger(req))
                    self.resilience_stats["timeouts"] += 1
                    self._finish_request(req, "timeout")
                    expired += 1
                break
            self.step_chunk(max_chunk)
        # fresh requests the closed admission gate kept queued: theirs
        # is the other half of the handoff payload (they stay queued
        # here too, for a resume() — re-admitting one elsewhere makes
        # cancelling it here the caller's job)
        unfinished.extend(request_ledger(r) for r in list(self._queue))
        if self._tracer is not None:
            self._tracer.engine_event(
                "drain_end", expired=expired, queued=len(self._queue))
        return {"drained": True, "expired": expired,
                "active": int(self.active.sum()),
                "queued": len(self._queue),
                "unfinished": unfinished}

    def resume(self):
        """Leave the draining state: admission restarts on the next
        scheduler tick."""
        self._draining = False
        if self._tel is not None:
            self._tel.on_drain(False)

    def resilience_snapshot(self) -> dict:
        """Fault/recovery/degradation counters (plain host counters —
        available even with PT_FLAGS_telemetry=off, like
        prefix/spec/slo snapshots)."""
        if self._san is not None:
            self._san.check_read("resilience_snapshot")
        # copy-on-read: the /healthz scrape thread calls this while
        # the scheduler writes counters; "faults" grows a key on a
        # site's first fault, so both levels iterate list() copies
        st = {k: v for k, v in list(self.resilience_stats.items())}
        st["faults"] = {k: v for k, v in list(st["faults"].items())}
        st["recovery_mode"] = self._recovery_mode
        st["max_retries"] = self.cfg.max_retries
        st["draining"] = self._draining
        st["degradation"] = (self._degctl.snapshot()
                             if self._degctl is not None
                             else {"enabled": False, "level": 0,
                                   "degraded": False})
        st["injector"] = (self._injector.snapshot()
                          if self._injector is not None
                          else {"enabled": False})
        return st

    def step(self) -> bool:
        """One per-token scheduler tick (see ``_step_impl``),
        bracketed by the sanitizer's ownership + invariant hooks and
        the chaos corruption seam — each a single identity check when
        its subsystem is off."""
        san = self._san
        if san is not None:
            san.note_tick("step")
        wd = self._watchdog
        if wd is not None:
            wd.tick_begin()
        with jax.profiler.TraceAnnotation("pt.engine.tick") as span:
            self._tick_args(span)
            out = self._step_impl()
            self._tick_epilogue(wd, san, "step")
        return out

    def _tick_args(self, span):
        """The state the tick finds (before its own admission: the
        active slots are the ones it will decode), as the arguments of
        its ``pt.engine.tick`` span (observability/spans.py): read
        only while a profiler trace is running."""
        if span.is_enabled():
            queued, _occ, used, total = self._tel_state()
            span.set_metadata(
                active=int(self.active.sum()), queued=queued,
                pages_used=int(used), pages_total=int(total))

    def _tick_epilogue(self, wd, san, site: str):
        """Shared post-step sequence for the step()/step_chunk()
        wrappers: watchdog diff, deferred cost-finish flush, flight
        tick, chaos corruption seam, sanitizer invariants — ONE list,
        so the two step paths can never desynchronize on a per-tick
        feature. Every hook is a single identity check when its
        subsystem is off."""
        if wd is not None:
            wd.tick_end()
        if self._cost_pending:
            self._flush_cost()
        if self._ts is not None:
            self._flight_tick()
        if self._injector is not None:
            self._corrupt_point()
        if san is not None:
            san.check_tick(self, site)

    def _step_impl(self) -> bool:
        """Admit waiting requests, run one decode step for all active
        slots — or, with speculative decoding enabled and at least one
        slot holding a draft, one multi-token verify pass. Returns
        False when there is nothing left to do."""
        self._expire_deadlines()
        self._observe_health()
        self._admit()
        if not self.active.any():
            return bool(self._queue)
        if self._spec_mode != "off" and not (
                self._degctl is not None and self._degctl.disable_spec):
            drafts = self._propose_drafts()
            if drafts:
                return self._spec_step(drafts)
            self.spec_stats["fallback_steps"] += 1
            if self._tel is not None:
                self._tel.on_spec_fallback()
        t0 = time.perf_counter()
        tr = self._tracer
        seq = tr.next_step() if tr is not None else 0
        adv = {} if tr is not None and tr.want_step(seq) else None
        occ = float(self.active.sum()) / self.cfg.max_slots
        participants = self.active.copy()
        p_dec = None
        try:
            self._fault_point("decode")
            self._cow_for_decode(1)
            use_samp, samp = self._slot_sampling()
            self._key, sub = jax.random.split(self._key)
            toks = jnp.asarray(self.last_tok[:, None], jnp.int32)
            lens = jnp.asarray(self.seq_lens, jnp.int32)
            prof = self._prof
            p_want = prof is not None and prof.want("decode_step")
            t_call = time.perf_counter()
            with jax.profiler.TraceAnnotation(
                    "pt.engine.dispatch", program="decode_step"), \
                    self._ctx():
                if self.cfg.paged:
                    state = PagedState(
                        block_tables=jnp.asarray(self.pool.block_tables),
                        seq_lens=lens)
                    nxt, self.layer_caches = self._decode()(
                        self._pb, toks, self.layer_caches, state, sub,
                        samp, use_samp)
                else:
                    nxt, self.caches = self._decode()(
                        self._pb, toks, self.caches, lens, sub, samp,
                        use_samp)
            t_disp = time.perf_counter()
            if p_want:
                # sampled dispatch: MEASURED schedule/dispatch/device
                # decomposition (block_until_ready on the program's
                # own outputs — the sync below was due anyway)
                p_dec = prof.observe("decode_step", t0, t_call,
                                     t_disp, nxt)
                self._hbm_update()
            with jax.profiler.TraceAnnotation("pt.engine.sync"):
                nxt = np.asarray(nxt)
        except BaseException as e:
            if not self._recoverable(e):
                raise
            self._recover_step(e, participants, "decode")
            return True
        t_sync = time.perf_counter()
        emitted = 0
        cost_shares = [] if self._cost_enabled else None
        with jax.profiler.TraceAnnotation("pt.engine.emit") as span:
            for slot in range(self.cfg.max_slots):
                if not self.active[slot]:
                    continue
                tok = int(nxt[slot])
                req = self._slot_req[slot]
                req.output.append(tok)
                self.seq_lens[slot] += 1
                self.last_tok[slot] = tok
                emitted += 1
                if adv is not None:
                    adv[req.rid] = 1
                if cost_shares is not None:
                    cost_shares.append((req, 1))
                self._maybe_finish(slot, tok)
            span.set_metadata(tokens=emitted)
        self._tokens_emitted += emitted
        if cost_shares:
            # attributed device wall: the measured sample when this
            # dispatch was profiled, else the dispatch-done→token-sync
            # host wall (the documented upper-bound fallback)
            self._attribute_cost(
                "decode_step",
                p_dec["device_ms"] if p_dec is not None
                else (t_sync - t_disp) * 1e3,
                p_dec is not None, cost_shares)
        if adv is not None:
            # sampled dispatches report the MEASURED decomposition
            # (schedule_ms/dispatch_ms/device_ms, profiled=True);
            # unsampled keep the SAME schedule/dispatch windows (the
            # stamps cost nothing) plus the honest fallback:
            # sync_wall_ms is the HOST wall from dispatch-done to
            # token sync — an upper bound on device time, not a
            # measurement (the field PR 6 called device_wall_ms_est)
            timing = (dict(p_dec, profiled=True) if p_dec is not None
                      else {"schedule_ms": (t_call - t0) * 1e3,
                            "dispatch_ms": (t_disp - t_call) * 1e3,
                            "sync_wall_ms": (t_sync - t_disp) * 1e3})
            tr.step(seq, "decode", t0, time.perf_counter(),
                    occupancy=occ, tokens_advanced=emitted,
                    chunk_budget_spent=1, advanced=adv, **timing)
        if self._tel is not None:
            self._tel.on_tokens(emitted,
                                (time.perf_counter() - t0) * 1e3)
            self._tel.on_state(*self._tel_state())
        return True

    # ---------------- speculative decoding ----------------
    def _draft_budget(self, slot: int) -> int:
        """Max draft tokens this slot may carry in a verify pass, 0 if
        it is ineligible. O(1) host checks only — callers use it both
        to draft and to SKIP the O(history) drafter scan when a verify
        pass could not dispatch anyway. Eligibility: the request
        decodes GREEDILY (acceptance verifies against the argmax
        chain), has budget for at least one draft + the bonus token,
        and — in ``auto`` mode — hasn't proven its traffic undraftable
        (per-request throttle: after 16 proposed tokens at < 1/8
        acceptance, stop paying the verify width for it)."""
        req = self._slot_req[slot]
        if not self._req_greedy(req):
            return 0
        remaining = min(
            req.max_new_tokens - len(req.output),
            self.cfg.max_len - 1 - int(self.seq_lens[slot]))
        max_d = min(self.cfg.spec_k, remaining - 1)
        if max_d <= 0:
            return 0
        if self._spec_mode == "auto" and req._spec_proposed >= 16 \
                and req._spec_accepted * 8 < req._spec_proposed:
            return 0
        return max_d

    def _propose_drafts(self) -> Dict[int, np.ndarray]:
        """Host-side drafting for the next verify pass: slot → proposed
        token ids (1..spec_k of them) for every eligible slot (see
        ``_draft_budget``) whose drafter actually proposes."""
        if self._drafter is None:
            return {}
        cfg = self.cfg
        out: Dict[int, np.ndarray] = {}
        for slot in range(cfg.max_slots):
            if not self.active[slot]:
                continue
            max_d = self._draft_budget(slot)
            if max_d <= 0:
                continue
            req = self._slot_req[slot]
            hist = np.concatenate(
                [req.prompt, np.asarray(req.output, np.int64)])
            d = np.asarray(self._drafter.propose(hist, max_d)).reshape(-1)
            if d.size:
                out[slot] = d[:max_d]
        return out

    def _spec_step(self, drafts: Dict[int, np.ndarray]) -> bool:
        """One speculative step: dispatch the fixed ``[slots, K+1]``
        verify program over every active slot (drafted slots carry
        their proposals, the rest degrade to a 1-token decode in the
        same call), overlap admission dispatch behind it, then sync and
        advance each slot by ``accepted + 1`` tokens.

        ROLLBACK is the non-advance: the program appended K+1 KV rows
        per active slot, but ``seq_lens`` moves only past the accepted
        prefix — rejected rows sit above every later causal mask and
        are rewritten by the next append at the same positions (paged:
        a pure length decrement on append-only pages; contiguous: same
        rows overwritten next step). The COW guard runs over the FULL
        K+1 write window first: even a pad row's garbage write must
        never land on a page the prefix store (or another slot) still
        shares."""
        cfg = self.cfg
        S = cfg.spec_k + 1
        t0 = time.perf_counter()
        tr = self._tracer
        seq = tr.next_step() if tr is not None else 0
        adv = {} if tr is not None and tr.want_step(seq) else None
        spec_by_rid = {} if adv is not None else None
        occ = float(self.active.sum()) / cfg.max_slots
        chunk_slots = self.active.copy()
        # dispatch-time occupants: the overlapped admission below may
        # preempt + re-claim a slot — the verify pass's tokens must
        # never credit the new occupant (identity-checked at sync)
        chunk_reqs = {s: self._slot_req[s]
                      for s in range(cfg.max_slots) if chunk_slots[s]}
        p_dec = None
        try:
            self._fault_point("verify")
            self._cow_for_decode(S)
            sentinel = cfg.max_len
            ids = np.zeros((cfg.max_slots, S), np.int64)
            start = np.full((cfg.max_slots,), sentinel, np.int32)
            n_draft = np.zeros((cfg.max_slots,), np.int32)
            for slot in range(cfg.max_slots):
                if not chunk_slots[slot]:
                    continue
                ids[slot, 0] = self.last_tok[slot]
                d = drafts.get(slot)
                if d is not None and d.size:
                    ids[slot, 1:1 + d.size] = d
                    n_draft[slot] = d.size
                start[slot] = self.seq_lens[slot]
            use_samp, samp = self._slot_sampling()
            self._key, sub = jax.random.split(self._key)
            bt = (jnp.asarray(self.pool.block_tables) if cfg.paged
                  else jnp.zeros((1,), jnp.int32))
            caches = self.layer_caches if cfg.paged else self.caches
            prof = self._prof
            p_want = prof is not None and prof.want("spec_verify")
            t_call = time.perf_counter()
            with jax.profiler.TraceAnnotation(
                    "pt.engine.dispatch", program="spec_verify"), \
                    self._ctx():
                preds, accepted, caches = self._verify()(
                    self._pb, jnp.asarray(ids, jnp.int32), caches, bt,
                    jnp.asarray(start), jnp.asarray(n_draft), sub, samp,
                    use_samp)
            if cfg.paged:
                self.layer_caches = caches
            else:
                self.caches = caches
            t_disp = time.perf_counter()
            t_admit0 = t_disp
            if p_want:
                # measured device wall of the verify program itself —
                # blocks BEFORE the overlapped admission dispatch, so
                # the sample is the program, not the overlap window
                p_dec = prof.observe("spec_verify", t0, t_call, t_disp,
                                     (preds, accepted))
                self._hbm_update()
                # admit_dispatch_ms windows the admission work only
                t_admit0 = time.perf_counter()
            # admission dispatches behind the in-flight verify (stream
            # order, exactly like step_chunk's decode-chunk overlap)
            with jax.profiler.TraceAnnotation("pt.engine.admit"):
                pending = self._admit_dispatch()
            t_admit = time.perf_counter()
            with jax.profiler.TraceAnnotation("pt.engine.sync"):
                preds_np = np.asarray(preds)  # ONE sync, S tokens/slot
                acc_np = np.asarray(accepted)
        except BaseException as e:
            if not self._recoverable(e):
                raise
            self._recover_step(e, chunk_slots, "verify")
            return True
        t_sync = time.perf_counter()
        emitted = 0
        proposed_tot = accepted_tot = 0
        cost_shares = [] if self._cost_enabled else None
        with jax.profiler.TraceAnnotation("pt.engine.emit") as span:
            for slot in range(cfg.max_slots):
                req = chunk_reqs.get(slot)
                if req is None or self._slot_req.get(slot) is not req:
                    continue  # finished at sync, or preempted+re-claimed
                n = int(n_draft[slot])
                a = min(int(acc_np[slot]), n)
                toks = [int(ids[slot, 1 + j]) for j in range(a)]
                toks.append(int(preds_np[slot, a]))
                slot_emitted = 0
                for tok in toks:
                    if req.done:
                        break  # EOS mid-chain: later tokens discarded
                    req.output.append(tok)
                    self.seq_lens[slot] += 1
                    self.last_tok[slot] = tok
                    emitted += 1
                    slot_emitted += 1
                    if adv is not None:
                        adv[req.rid] = adv.get(req.rid, 0) + 1
                    self._maybe_finish(slot, tok)
                if cost_shares is not None and slot_emitted:
                    cost_shares.append((req, slot_emitted))
                if spec_by_rid is not None and n:
                    spec_by_rid[req.rid] = [n, a]
                if n:
                    req._spec_proposed += n
                    req._spec_accepted += a
                    proposed_tot += n
                    accepted_tot += a
                    if self._tel is not None:
                        self._tel.on_spec_slot(n, a)
            span.set_metadata(tokens=emitted)
        self.spec_stats["verify_calls"] += 1
        self.spec_stats["proposed"] += proposed_tot
        self.spec_stats["accepted"] += accepted_tot
        self.spec_stats["emitted"] += emitted
        self._tokens_emitted += emitted
        if cost_shares:
            # unsampled fallback conflates the overlapped admission
            # dispatch (the sync_wall_ms caveat); the profiled sample
            # is the verify program alone
            self._attribute_cost(
                "spec_verify",
                p_dec["device_ms"] if p_dec is not None
                else (t_sync - t_disp) * 1e3,
                p_dec is not None, cost_shares)
        if adv is not None:
            # sampled: measured schedule/dispatch/device decomposition
            # (the profiler blocked on the verify outputs BEFORE the
            # admission overlap). Unsampled fallback: same schedule/
            # dispatch windows, plus sync_wall_ms spanning
            # dispatch-done -> token sync — a HOST-wall upper bound
            # that conflates the overlapped admission work, which is
            # reported separately so a reader can subtract it when a
            # first-time prefill compile (host side) dominates
            timing = (dict(p_dec, profiled=True) if p_dec is not None
                      else {"schedule_ms": (t_call - t0) * 1e3,
                            "dispatch_ms": (t_disp - t_call) * 1e3,
                            "sync_wall_ms": (t_sync - t_disp) * 1e3})
            tr.step(seq, "verify", t0, time.perf_counter(),
                    occupancy=occ, tokens_advanced=emitted,
                    chunk_budget_spent=S, advanced=adv,
                    proposed=proposed_tot, accepted=accepted_tot,
                    spec=spec_by_rid,
                    admit_dispatch_ms=(t_admit - t_admit0) * 1e3,
                    **timing)
        self._integrate_guarded(pending, "verify_integrate")
        if self._tel is not None:
            self._tel.on_tokens(emitted, (t_sync - t0) * 1e3)
            self._tel.on_spec_verify(
                proposed_tot, accepted_tot,
                self.spec_stats["accepted"], self.spec_stats["proposed"])
            self._tel.on_state(*self._tel_state())
        return True

    def _slot_budgets(self) -> np.ndarray:
        """Per-slot remaining token budget (max_new_tokens and max_len
        caps) — frozen slots stop advancing inside the fixed-K chunk.

        The scheduler policy's CHUNK-SPLIT seam: ``slot_caps`` may
        shrink individual slots' budgets within the fixed-shape chunk
        (the program still computes every slot's rows — the cap
        bounds which tokens COMMIT, i.e. a tenant's emission and
        paged page-growth per chunk, not the chunk's device time).
        A cap set that would freeze EVERY active slot is ignored: a
        chunk that can emit nothing would spin the scheduler."""
        budget = np.zeros((self.cfg.max_slots,), np.int32)
        for slot in range(self.cfg.max_slots):
            if not self.active[slot]:
                continue
            req = self._slot_req[slot]
            budget[slot] = max(0, min(
                req.max_new_tokens - len(req.output),
                self.cfg.max_len - 1 - int(self.seq_lens[slot])))
        if self._sched is not None:
            caps = self._sched.slot_caps(self)
            if caps is not None:
                capped = np.minimum(
                    budget, np.asarray(caps, np.int32))
                if capped.max(initial=0) > 0 \
                        or budget.max(initial=0) == 0:
                    budget = capped
        return budget

    def step_chunk(self, max_chunk: int = 8) -> bool:
        """One chunked scheduler tick (see ``_step_chunk_impl``),
        bracketed by the sanitizer's ownership + invariant hooks and
        the chaos corruption seam — each a single identity check when
        its subsystem is off."""
        san = self._san
        if san is not None:
            san.note_tick("step_chunk")
        wd = self._watchdog
        if wd is not None:
            wd.tick_begin()
        with jax.profiler.TraceAnnotation("pt.engine.tick") as span:
            self._tick_args(span)
            out = self._step_chunk_impl(max_chunk)
            self._tick_epilogue(wd, san, "step_chunk")
        return out

    def _corrupt_point(self):
        """State-corruption chaos seam: consulted once per tick, AFTER
        the step's host integration. A firing site mangles the
        engine's own bookkeeping — how a ``PT_FLAGS_sanitize`` run
        proves the invariant checker catches real damage (and how the
        sanitizer tests seed their corruptions). Production injector
        specs leave these rates at 0; with no injector this seam is
        never reached."""
        inj = self._injector
        for site in CORRUPT_SITES:
            if inj.fire(site) and self._apply_corruption(site):
                # counted only when damage actually landed — a no-op
                # fire (e.g. scale_desync on a float cache) must not
                # report an injected fault the sanitizer then
                # "misses"
                self._note_fault(site, "corrupt")

    def _apply_corruption(self, site: str) -> bool:
        """Deterministic minimal damage per corruption site, aimed at
        the first active slot (pool/heap when none is active).
        Returns True when state was actually corrupted."""
        slots = [s for s in range(self.cfg.max_slots)
                 if self.active[s]]
        if site == "seq_shrink":
            # cache length falls behind the host token ledger — the
            # replay-source-of-truth desync class
            if slots:
                self.seq_lens[slots[0]] -= 1
                return True
        elif site == "leak_ref":
            if self.pool is not None:
                # a refcount with no owner: the page can never free
                for s in slots:
                    if self.pool.pages_of[s]:
                        p = self.pool.pages_of[s][0]
                        self.pool.ref[p] = self.pool.ref.get(p, 0) + 1
                        return True
            elif self._free_heap:
                # contiguous mode has no pool: leak a slot instead
                heapq.heappop(self._free_heap)
                return True
        elif site == "scale_desync":
            # int8 caches only: shear a dequant-scale array off its
            # payload pool (shape metadata change — no device sync)
            if self.pool is not None:
                c = self.layer_caches[0]
                if c.k_scale is not None:
                    self.layer_caches[0] = c._replace(
                        k_scale=c.k_scale[:, :, :-1])
                    return True
            else:
                from .paged import QuantizedKV

                k, v = self.caches[0]
                if isinstance(k, QuantizedKV):
                    self.caches[0] = (
                        QuantizedKV(k.q, k.scale[:, :-1]), v)
                    return True
        return False

    def _step_chunk_impl(self, max_chunk: int) -> bool:
        """Run ``max_chunk`` decode steps in ONE device program, with
        admission OVERLAPPED: the decode chunk is dispatched first (no
        host sync), then prefill + cache-insert programs for queued
        requests are dispatched behind it on the device stream, and only
        then does the host read the chunk's tokens back. In-flight
        decode never stalls on admission (the round-3 head-of-line
        blocking), prefill host work (bucketing, padding) overlaps the
        chunk's device time, and admitted sequences join the next chunk.
        K is fixed, so exactly one decode program compiles for the
        engine's lifetime; per-slot budgets freeze finished slots
        device-side and the host discards EOS/budget overshoot."""
        self._expire_deadlines()
        self._observe_health()
        if not self.active.any():
            # nothing decoding: plain blocking admission
            self._admit()
            if not self.active.any():
                return bool(self._queue)
        if self._spec_mode != "off" and not (
                self._degctl is not None and self._degctl.disable_spec):
            # A verify pass buys accepted+1 tokens per DRAFTING slot
            # for one weight stream, but costs every OTHER active slot
            # its chunk amortization: the pass is one host sync that
            # emits exactly 1 token for a draftless slot, vs max_chunk
            # tokens per sync from the plain chunk below. Preempting
            # the chunk for a single drafting slot would collapse a
            # mixed batch's throughput (7 slots × K tokens/sync → 7 ×
            # 1), so verify only preempts when drafting slots are at
            # least HALF the active set — the regime where the weight-
            # stream amortization outweighs the lost sync amortization.
            # step() keeps the unconditional preempt: there the
            # alternative is a 1-token pass, and verify strictly
            # dominates it. The O(1) eligibility count runs before the
            # O(history) drafter scan: when the gate cannot pass even
            # if every eligible slot proposed, don't pay the scan.
            n_active = int(self.active.sum())
            eligible = sum(
                1 for s in range(self.cfg.max_slots)
                if self.active[s] and self._draft_budget(s) > 0)
            drafts = (self._propose_drafts()
                      if 2 * eligible >= n_active else {})
            if drafts and 2 * len(drafts) >= n_active:
                return self._spec_step(drafts)
            self.spec_stats["fallback_steps"] += 1
            if self._tel is not None:
                self._tel.on_spec_fallback()
        t0 = time.perf_counter()
        tr = self._tracer
        seq = tr.next_step() if tr is not None else 0
        adv = {} if tr is not None and tr.want_step(seq) else None
        occ = float(self.active.sum()) / self.cfg.max_slots
        K = max_chunk
        # capture the chunk's view BEFORE admission: newly admitted
        # slots must not decode mid-chunk (their lengths land at
        # integrate). The OCCUPANTS are captured too: the overlapped
        # admission may PREEMPT a slot and re-claim it in the same
        # tick, and the chunk's tokens must never credit the new
        # occupant (identity-checked in the sync loop below)
        chunk_slots = self.active.copy()
        chunk_reqs = {s: self._slot_req[s]
                      for s in range(self.cfg.max_slots)
                      if chunk_slots[s]}
        p_dec = None
        try:
            self._fault_point("decode_chunk")
            self._cow_for_decode(K)
            budget = self._slot_budgets()
            use_samp, samp = self._slot_sampling()
            self._key, sub = jax.random.split(self._key)
            toks = jnp.asarray(self.last_tok[:, None], jnp.int32)
            lens = jnp.asarray(self.seq_lens, jnp.int32)
            act = jnp.asarray(chunk_slots)
            bt = (jnp.asarray(self.pool.block_tables) if self.cfg.paged
                  else jnp.zeros((1,), jnp.int32))
            caches = self.layer_caches if self.cfg.paged else self.caches
            prof = self._prof
            p_want = prof is not None and prof.want("decode_chunk")
            t_call = time.perf_counter()
            with jax.profiler.TraceAnnotation(
                    "pt.engine.dispatch", program="decode_chunk"), \
                    self._ctx():
                toks_all, caches, _ = self._decode_n()(
                    self._pb, toks, caches, lens, act,
                    jnp.asarray(budget), bt, sub, samp, K, use_samp)
            if self.cfg.paged:
                self.layer_caches = caches
            else:
                self.caches = caches
            t_disp = time.perf_counter()
            t_admit0 = t_disp
            if p_want:
                # measured device wall of the chunk itself: blocks on
                # the chunk's outputs BEFORE the overlapped admission
                # dispatch, so the sample is the program, not the
                # dispatch-to-token-sync window sync_wall_ms estimates
                p_dec = prof.observe("decode_chunk", t0, t_call,
                                     t_disp, toks_all)
                self._hbm_update()
                # admit_dispatch_ms must window the ADMISSION work
                # only — the measured device wait above is not it
                t_admit0 = time.perf_counter()
            # admission dispatches behind the in-flight chunk (stream
            # order: chunk → prefills → inserts into the chunk's
            # output caches)
            with jax.profiler.TraceAnnotation("pt.engine.admit"):
                pending = self._admit_dispatch()
            t_admit = time.perf_counter()
            with jax.profiler.TraceAnnotation("pt.engine.sync"):
                toks_np = np.asarray(toks_all)  # ONE sync for K tokens
        except BaseException as e:
            if not self._recoverable(e):
                raise
            # quarantine: the chunk's host state never advanced (the
            # sync above is where tokens would have landed), so the
            # chunk's participants replay; an un-synced but dispatched
            # chunk re-runs over the same positions bit-identically
            self._recover_step(e, chunk_slots, "decode_chunk")
            return True
        # TPOT window closes at the chunk's token sync — before the
        # admitted requests' first-token syncs in _admit_integrate, so
        # loaded chunks report decode latency, not admission latency
        # (matches what step() measures)
        t_sync = time.perf_counter()
        emitted = 0
        cost_by_slot: Dict[int, list] = {} if self._cost_enabled \
            else None
        with jax.profiler.TraceAnnotation("pt.engine.emit") as span:
            for k in range(K):
                for slot in range(self.cfg.max_slots):
                    # the slot advances only while its DISPATCH-TIME
                    # occupant still owns it: gone = finished (EOS) at
                    # an earlier k of this same chunk; replaced =
                    # preempted mid-chunk and re-claimed by this tick's
                    # admission — either way the chunk's remaining
                    # tokens are discarded, exactly like cancel's
                    req = chunk_reqs.get(slot)
                    if (req is None or k >= budget[slot]
                            or self._slot_req.get(slot) is not req):
                        continue
                    tok = int(toks_np[k, slot])
                    req.output.append(tok)
                    self.seq_lens[slot] += 1
                    self.last_tok[slot] = tok
                    emitted += 1
                    if adv is not None:
                        adv[req.rid] = adv.get(req.rid, 0) + 1
                    if cost_by_slot is not None:
                        cost_by_slot.setdefault(slot, [req, 0])[1] += 1
                    self._maybe_finish(slot, tok)
            span.set_metadata(tokens=emitted)
        self._tokens_emitted += emitted
        if cost_by_slot:
            self._attribute_cost(
                "decode_chunk",
                p_dec["device_ms"] if p_dec is not None
                else (t_sync - t_disp) * 1e3,
                p_dec is not None,
                [(req, n) for req, n in cost_by_slot.values()])
        if adv is not None:
            # sampled: measured decomposition. Unsampled fallback:
            # same schedule/dispatch windows, plus sync_wall_ms
            # (dispatch-done -> token sync HOST wall) with
            # admit_dispatch_ms reported separately — host admission
            # work OVERLAPPING that window, subtractable when a
            # first-time compile lands in admission
            timing = (dict(p_dec, profiled=True) if p_dec is not None
                      else {"schedule_ms": (t_call - t0) * 1e3,
                            "dispatch_ms": (t_disp - t_call) * 1e3,
                            "sync_wall_ms": (t_sync - t_disp) * 1e3})
            tr.step(seq, "decode_chunk", t0, time.perf_counter(),
                    occupancy=occ, tokens_advanced=emitted,
                    chunk_budget_spent=K, advanced=adv,
                    admit_dispatch_ms=(t_admit - t_admit0) * 1e3,
                    **timing)
        self._integrate_guarded(pending, "chunk_integrate")
        if self._tel is not None:
            self._tel.on_tokens(emitted, (t_sync - t0) * 1e3)
            self._tel.on_state(*self._tel_state())
        return True

    def step_adaptive(self, max_chunk: int = 8,
                      probe_chunk: int = 2) -> bool:
        """``step_chunk`` with load-adaptive granularity.

        The fixed-K chunk is a TTFT/throughput tradeoff: admission
        dispatches behind the in-flight chunk, so a request that arrives
        at a chunk boundary waits ~K decode steps of device time before
        its prefill runs (the round-5 load curve measured that cost at
        ~70 ms p50 at mid-load for K=8, where per-token admission beat
        the chunked loop). This scheduler keeps full chunks only in
        steady-state decode and drops to ``probe_chunk`` whenever
        admission work is queued — short chunks reach the next admission
        point sooner AND notice freed slots sooner, while an empty queue
        costs nothing. K is static to the compiled program, so at most
        two decode programs compile for the engine's lifetime (compile
        both up front by running a short ``max_chunk=probe_chunk``
        request through the engine before serving).

        Short chunks pay off when admission can happen SOON: a free
        slot now, or an active slot whose remaining budget ends inside
        this chunk (the chunk-boundary sync is what detects EOS/budget
        completion — a full chunk makes a queued request wait up to
        K-1 frozen steps behind a slot that finished at step 0). When
        every slot is busy with long remaining budgets, full chunks
        win: each boundary sync costs a host round-trip and buys
        nothing.

        Degradation (throttle level): forced to ``probe_chunk`` — an
        already-compiled program, so shrinking the chunk budget under
        pressure never triggers a new jit specialization."""
        k = max_chunk
        if self._degctl is not None and self._degctl.throttle:
            k = min(probe_chunk, max_chunk)
        elif self._queue:
            if not self.active.all():
                k = min(probe_chunk, max_chunk)
            else:
                budgets = self._slot_budgets()
                soonest = min(
                    (budgets[s] for s in range(self.cfg.max_slots)
                     if self.active[s]), default=max_chunk + 1)
                if soonest <= max_chunk:
                    k = min(probe_chunk, max_chunk)
        return self.step_chunk(k)

    def run(self, prompts: Sequence, max_new_tokens: int = 32,
            eos_token_id: Optional[int] = None,
            max_chunk: int = 8) -> List[Request]:
        """Submit all prompts, drive until completion, return Requests
        in submission order (each carries .output and .ttft_ms).

        Drives ``step_chunk`` so decode syncs the host once per
        ``max_chunk`` tokens; admission (prefill) happens between chunks
        while the previous chunk's tokens are being consumed."""
        rids = [self.add_request(p, max_new_tokens, eos_token_id)
                for p in prompts]
        while self.step_chunk(max_chunk) or self._queue or \
                self.active.any():
            if self._draining and not self.active.any():
                break  # drained mid-run: queued requests stay queued
        return [self._finished[r] for r in rids if r in self._finished]

    # ---------------- telemetry ----------------
    def _tel_state(self):
        """(queue_depth, occupancy, kv_used, kv_total) — all host-side
        scheduler state, no device traffic. Thread-note: also called
        from the /healthz scrape thread; ``pages_of`` has fixed slot
        keys (created once in PagePool.__init__, values replaced whole
        on free), so concurrent iteration never sees a resized dict —
        a scrape racing the scheduler can read a momentarily stale
        count, which is acceptable for a gauge."""
        if self._san is not None:
            self._san.check_read("_tel_state")
        occ = float(self.active.sum()) / self.cfg.max_slots
        if self.cfg.paged:
            used = float(sum(
                len(self.pool.pages_of[s])
                for s in range(self.pool.slots)))
            total = used + self.pool.free_pages
        else:
            used = float(self.seq_lens[self.active].sum())
            total = float(self.cfg.max_slots * self.cfg.max_len)
        return len(self._queue), occ, used, total

    def metrics_snapshot(self) -> dict:
        """ONE unified serving document: registry aggregates (TTFT/TPOT
        percentiles, queue depth, occupancy, KV utilization, counters —
        when telemetry is on) plus the host-side prefix-cache, spec-
        decode and SLO sub-snapshots, which are ALWAYS present (plain
        host counters survive ``PT_FLAGS_telemetry=off``). Bench ledger
        lines and the dump CLI read this one call instead of stitching
        ``prefix_snapshot`` + ``spec_snapshot`` + ``slo_snapshot``."""
        if self._san is not None:
            self._san.check_read("metrics_snapshot")
        if self._tel is None:
            snap = {"telemetry": "off"}
        else:
            # refresh point-in-time gauges so an idle engine still
            # reports its current state
            self._tel.on_state(*self._tel_state())
            snap = self._tel.snapshot()
        snap["slots"] = {
            "active": int(self.active.sum()),
            "max": self.cfg.max_slots,
        }
        snap["prefix_cache"] = self.prefix_snapshot()
        snap["spec_decode"] = self.spec_snapshot()
        snap["slo"] = self.slo_snapshot()
        # multi-tenant accounting + the admission scheduler's policy
        # name and preemption count ride the one unified document
        snap["tenants"] = self.tenant_snapshot()
        snap["resilience"] = self.resilience_snapshot()
        # program-time attribution (PR 12): measured per-program
        # device ms, watchdog state and HBM residency ride the one
        # unified document too. ONE hbm_accounting walk feeds both
        # the gauges and the snapshot sub-doc.
        hbm = observability.hbm_accounting(self)
        if self._tel is not None:
            self._tel.on_hbm(hbm)
        snap["programs"] = self.profile_snapshot()
        snap["recompile"] = self.recompile_snapshot()
        snap["hbm"] = dict(hbm, total=sum(list(hbm.values())))
        # flight data (PR 13): alert-rule states and per-request
        # device-cost attribution ride the one unified document too
        # (the full time-series stays on timeline_snapshot()/
        # /timeline — windows x samples would bloat every scrape)
        snap["alerts"] = self.alerts_snapshot()
        snap["cost"] = self.cost_snapshot()
        # seal-time contract audit (ptaudit): the self-audit verdict
        # rides the one unified document too ({"enabled": False}
        # when PT_FLAGS_audit_on_seal is off)
        snap["audit"] = self.audit_snapshot()
        return snap

    def prefix_snapshot(self) -> dict:
        """Prefix-cache effectiveness counters (plain host counters —
        available even with PT_FLAGS_telemetry=off, which is how the
        bench A/B reads hit rates)."""
        if self._san is not None:
            self._san.check_read("prefix_snapshot")
        st = {k: v for k, v in list(self.prefix_stats.items())}
        st["enabled"] = self._prefix is not None
        st["cached_blocks"] = (self._prefix.cached_pages
                               if self._prefix is not None else 0)
        tot = st["prompt_tokens"]
        st["hit_rate_tokens"] = (st["hit_tokens"] / tot) if tot else 0.0
        return st

    def spec_snapshot(self) -> dict:
        """Speculative-decoding effectiveness counters (plain host
        counters — available even with PT_FLAGS_telemetry=off, which is
        how the bench A/B reads acceptance rates)."""
        if self._san is not None:
            self._san.check_read("spec_snapshot")
        st = {k: v for k, v in list(self.spec_stats.items())}
        st["enabled"] = self._spec_mode != "off"
        st["mode"] = self._spec_mode
        st["k"] = self.cfg.spec_k
        st["acceptance_rate"] = (st["accepted"] / st["proposed"]
                                 if st["proposed"] else 0.0)
        return st

    def slo_snapshot(self) -> dict:
        """SLO attainment per class + overall goodput (plain host
        counters — available even with PT_FLAGS_telemetry=off, which is
        how the bench goodput sweep reads them). ``goodput`` is
        met / (met + violated) over SLO-tracked finishes; cancelled
        requests are counted separately, never as violations."""
        if self._san is not None:
            self._san.check_read("slo_snapshot")
        classes = {}
        met = violated = 0
        # list(): slo_stats grows a key on a class's FIRST finish, and
        # this runs on the /healthz scrape thread too — iterating the
        # live dict would race the scheduler with RuntimeError
        for cls, st in list(self.slo_stats.items()):
            d = {k: v for k, v in list(st.items())}
            # derive ONLY from the copy: mixing d with the live st
            # could report met=5 next to a goodput computed at met=6
            tracked = d["met"] + d["violated"]
            d["goodput"] = d["met"] / tracked if tracked else None
            classes[cls] = d
            met += d["met"]
            violated += d["violated"]
        tracked = met + violated
        return {
            "classes": classes,
            "met": met,
            "violated": violated,
            "goodput": met / tracked if tracked else None,
        }

    def tenant_snapshot(self) -> dict:
        """Per-tenant serving state: cumulative host counters
        (finished/cancelled/timeouts/failed, tokens, attributed
        device-ms, SLO met/violated, preemptions) joined with LIVE
        usage — active slots, held KV pages, queued requests — the
        isolation numbers the multi-tenant scheduler's quotas act on.
        Plain host counters, available with telemetry off; copy-on-
        read like every scrape surface (tenant ``"-"`` is untagged
        traffic)."""
        if self._san is not None:
            self._san.check_read("tenant_snapshot")
        tenants: Dict[str, dict] = {}

        def bucket(key):
            d = tenants.get(key)
            if d is None:
                d = tenants[key] = {
                    "active_slots": 0, "pages": 0, "queued": 0}
            return d

        for key, st in list(self.tenant_stats.items()):
            bucket(key).update({k: v for k, v in list(st.items())})
        for slot, req in list(self._slot_req.items()):
            d = bucket(req.tenant or "-")
            d["active_slots"] += 1
            if self.cfg.paged:
                # pages_of values are replaced whole on free — the
                # same staleness contract as _tel_state's gauge read
                d["pages"] += len(self.pool.pages_of[slot])
        for req in list(self._queue):
            bucket(req.tenant or "-")["queued"] += 1
        return {
            "tenants": tenants,
            "scheduler": {k: v
                          for k, v in list(self.sched_stats.items())},
        }

    def slo_window_reset(self):
        """Zero the host-side SLO counters — one measurement window per
        load step in a goodput sweep (registry counters keep their
        cumulative totals, same contract as metrics_window_reset)."""
        self.slo_stats = {}

    def backpressure(self) -> dict:
        """Honest admission readiness for ``/healthz``: queue depth,
        free slots/pages and whether admission is SATURATED (requests
        waiting with zero free slots) — the state a router drains a
        replica on. Host scheduler state only; safe from the scrape
        thread (same staleness contract as ``_tel_state``)."""
        if self._san is not None:
            self._san.check_read("backpressure")
        qd = len(self._queue)
        free = len(self._free_heap)
        ctl = self._degctl
        out = {
            "queue_depth": qd,
            "free_slots": free,
            "occupancy": float(self.active.sum()) / self.cfg.max_slots,
            # two saturation modes: no free slot, or — the PAGED
            # engine's dominant stall — slots free but the last
            # admission pass blocked on KV-pool pages
            "saturated": qd > 0 and (free == 0 or self._pool_blocked),
            # resilience bits a router steers on: draining (stop
            # sending, we're shutting down) and the degradation ladder
            "draining": self._draining,
            "degraded": ctl.degraded if ctl is not None else False,
            "degradation_level": ctl.level if ctl is not None else 0,
        }
        if self.cfg.paged:
            out["free_pages"] = self.pool.free_pages
            out["pool_blocked"] = self._pool_blocked
        return out

    def metrics_window_reset(self):
        """Reset percentile windows + peak trackers (cumulative
        counters keep running) — one measurement window per benchmark
        sweep."""
        if self._tel is not None:
            self._tel.window_reset()

    # ---------------- per-request device-cost attribution ----------
    def _attribute_cost(self, program: str, device_ms: float,
                        profiled: bool, shares):
        """Split one step's device wall across the requests it
        advanced, proportional to tokens advanced (``shares`` is
        [(req, tokens)]). The split is exact up to float rounding —
        the shares sum to ``device_ms`` — which is the documented
        rounding the reconciliation test allows. ``profiled`` marks a
        MEASURED sample (block_until_ready device wall); the fallback
        is the step's sync-wall estimate, accumulated separately so a
        reader can tell evidence from upper bound."""
        if device_ms <= 0 or not shares:
            return
        total = sum(n for _, n in shares)
        if total <= 0:
            return
        st = self.cost_stats
        st["attributed_ms"][program] = \
            st["attributed_ms"].get(program, 0.0) + device_ms
        st["profiled_ms" if profiled else "estimated_ms"] += device_ms
        for req, n in shares:
            share = device_ms * (n / total)
            req.device_ms += share
            if profiled:
                req.device_ms_profiled += share

    def _record_cost_finish(self, req: Request):
        """Terminal cost bookkeeping for one request (idempotent —
        terminal paths can revisit a request across flush points)."""
        if not self._cost_enabled or req._cost_recorded:
            return
        req._cost_recorded = True
        st = self.cost_stats
        st["requests_finished"] += 1
        st["request_device_ms_total"] += req.device_ms
        key = req.slo or "untracked"
        by = st["by_slo"].get(key)
        if by is None:
            by = st["by_slo"][key] = {"requests": 0,
                                      "device_ms_total": 0.0}
        by["requests"] += 1
        by["device_ms_total"] += req.device_ms
        # per-tenant attributed cost rides the same finish record
        # (cost-gated like cost_stats: off = requests carry 0 anyway)
        self._tenant_bucket(req.tenant)["device_ms"] += req.device_ms
        self._cost_window.append(req.device_ms)
        if self._tel is not None:
            self._tel.on_request_cost(key, req.device_ms,
                                      tenant=req.tenant or "-")

    def _flush_cost(self):
        """Record finish-time costs deferred past the step's
        attribution pass (requests that hit EOS/budget mid-step must
        include the final chunk's share — _maybe_finish runs BEFORE
        the step attributes, so it defers here)."""
        if not self._cost_pending:
            return
        pending, self._cost_pending = self._cost_pending, []
        for req in pending:
            self._record_cost_finish(req)

    def cost_snapshot(self) -> dict:
        """Per-request device-cost attribution totals (plain host
        counters — available with PT_FLAGS_telemetry=off, like every
        other serving stat surface). ``request_device_ms_p50`` is over
        the recent finished-request window."""
        if self._san is not None:
            self._san.check_read("cost_snapshot")
        if not self._cost_enabled:
            return {"enabled": False}
        st = {k: v for k, v in list(self.cost_stats.items())}
        st["attributed_ms"] = {
            k: v for k, v in list(st["attributed_ms"].items())}
        st["by_slo"] = {k: {kk: vv for kk, vv in list(v.items())}
                        for k, v in list(st["by_slo"].items())}
        win = sorted(self._cost_window)
        st["request_device_ms_p50"] = (win[len(win) // 2] if win
                                       else None)
        n = st["requests_finished"]
        st["request_device_ms_mean"] = (
            st["request_device_ms_total"] / n if n else None)
        st["enabled"] = True
        return st

    # ---------------- flight data (time-series + alerts) ----------
    def _flight_tick(self):
        """One scheduler tick for the flight-data layer: advance the
        time-series store (a window closes every cadence-th tick) and,
        on a closed window, run the alert detectors over the series.
        Pure host bookkeeping; the tick count is the only input to
        every decision."""
        ts = self._ts
        if ts is None:
            return
        sample = ts.on_tick(self._flight_collect)
        if sample is not None and self._alerts is not None:
            self._alerts.evaluate(ts)

    def _flight_collect(self) -> dict:
        """Cumulative counters + point gauges for one time-series
        window (scheduler-thread only — the store's readers are the
        scrape-safe surface). Host values the scheduler already holds;
        histogram window-percentiles ride along when telemetry is
        on."""
        st = self.resilience_stats
        counters = {
            "tokens": float(self._tokens_emitted),
            "finished": float(len(self._finished)),
            "prefix_hits": float(self.prefix_stats["hits"]),
            "prefix_misses": float(self.prefix_stats["misses"]),
            "prefix_hit_tokens": float(
                self.prefix_stats["hit_tokens"]),
            "prefix_prompt_tokens": float(
                self.prefix_stats["prompt_tokens"]),
            "prefix_evictions": float(self.prefix_stats["evictions"]),
            "spec_proposed": float(self.spec_stats["proposed"]),
            "spec_accepted": float(self.spec_stats["accepted"]),
            "spec_verify_calls": float(
                self.spec_stats["verify_calls"]),
            "recoveries": float(st["recoveries"]),
            "retries": float(st["retries"]),
            "timeouts": float(st["timeouts"]),
            "failed": float(st["failed"]),
            "recompiles": float(
                sum(self._watchdog.recompiles.values())
                if self._watchdog is not None else 0),
            "device_ms": float(self.cost_stats["profiled_ms"]
                               + self.cost_stats["estimated_ms"]),
        }
        for cls, s in list(self.slo_stats.items()):
            counters[f"slo_met:{cls}"] = float(s["met"])
            counters[f"slo_violated:{cls}"] = float(s["violated"])
        qd, occ, used, total = self._tel_state()
        ctl = self._degctl
        gauges = {
            "queue_depth": float(qd),
            "occupancy": occ,
            "active_slots": float(self.active.sum()),
            "free_slots": float(len(self._free_heap)),
            "kv_used": used,
            "kv_total": total,
            "kv_utilization": used / total if total else 0.0,
            "degradation_level": float(ctl.level
                                       if ctl is not None else 0),
        }
        percentiles = (self._tel.window_percentiles()
                       if self._tel is not None else {})
        return {"counters": counters, "gauges": gauges,
                "percentiles": percentiles}

    def timeline_snapshot(self) -> dict:
        """The retained time-series windows (``{"enabled": False}``
        when PT_FLAGS_timeseries is off). Copy-on-read — the
        /timeline endpoint and `dump --timeline` read this from the
        scrape thread."""
        if self._san is not None:
            self._san.check_read("timeline_snapshot")
        if self._ts is None:
            return {"enabled": False}
        st = self._ts.snapshot()
        st["enabled"] = True
        return st

    def alerts_snapshot(self) -> dict:
        """Alert-rule states + bounded transition log
        (``{"enabled": False}`` when alerts are off). Copy-on-read."""
        if self._san is not None:
            self._san.check_read("alerts_snapshot")
        if self._alerts is None:
            return {"enabled": False}
        st = self._alerts.snapshot()
        st["enabled"] = True
        return st

    def alerts_window_reset(self):
        """Zero the per-rule peak trackers — one measurement window
        per bench sweep step (fire counts, hysteresis state and the
        registry totals keep running)."""
        if self._alerts is not None:
            self._alerts.window_reset()

    # ---------------- program-time attribution ----------------
    def _hbm_update(self):
        """Refresh the HBM residency gauges + watermarks from the
        pools the engine owns (array nbytes metadata — no device
        traffic). Called at init, on profiler-sampled steps and from
        metrics_snapshot; host-side numbers via ``hbm_snapshot``."""
        if self._tel is not None:
            self._tel.on_hbm(observability.hbm_accounting(self))

    def hbm_snapshot(self) -> dict:
        """Live HBM residency by component (kv_pool, kv_scales,
        weights_<dtype>, prefix_store) — plain host metadata,
        available even with PT_FLAGS_telemetry=off."""
        if self._san is not None:
            self._san.check_read("hbm_snapshot")
        st = observability.hbm_accounting(self)
        st["total"] = sum(list(st.values()))
        return st

    def profile_snapshot(self) -> dict:
        """Measured per-program device-time stats (PT_FLAGS_
        profile_programs; ``{"enabled": False}`` when off). Host
        counters — available even with PT_FLAGS_telemetry=off."""
        if self._san is not None:
            self._san.check_read("profile_snapshot")
        if self._prof is None:
            return {"enabled": False}
        st = self._prof.snapshot()
        st["enabled"] = True
        return st

    def recompile_snapshot(self) -> dict:
        """Recompile-watchdog state (sealed bit, per-program post-seal
        recompile counts; ``{"enabled": False}`` when off)."""
        if self._san is not None:
            self._san.check_read("recompile_snapshot")
        if self._watchdog is None:
            return {"enabled": False}
        return self._watchdog.snapshot()

    def profile_window_reset(self):
        """Zero the profiler's host-side stats — one measurement
        window per bench sweep (registry histogram totals keep
        running, like metrics_window_reset)."""
        if self._prof is not None:
            self._prof.window_reset()

    def seal_programs(self):
        """Seal the recompile watchdog's expected program set NOW
        (e.g. right after a bench warmup) instead of waiting out
        PT_FLAGS_recompile_warmup_ticks. No-op when the watchdog is
        off. With ``PT_FLAGS_audit_on_seal`` the sealed program set is
        also contract-audited (ptaudit AL/DQ/TX/DD) at this engine's
        own shapes — trace-only, compile accounting untouched."""
        if self._watchdog is not None:
            self._watchdog.seal()
        if self._audit_on_seal:
            from ..analysis.program_audit import audit_engine

            try:
                self._audit_report = audit_engine(self, arm="seal")
            except Exception as e:
                # the self-audit NEVER takes down a production seal
                # (the recompile watchdog's "never raises" contract):
                # probe/signature drift surfaces as an error verdict
                # on the snapshot instead
                self._audit_report = {
                    "arm": "seal", "programs": {}, "skipped": {},
                    "violations": [], "error": f"{type(e).__name__}: "
                                               f"{e}"}

    def audit_snapshot(self) -> dict:
        """Seal-time contract-audit verdict (``{"enabled": False}``
        when PT_FLAGS_audit_on_seal is off; ``sealed: False`` before
        the first seal). Copy-on-read like every scrape surface —
        the report is immutable after seal, and only copies leave."""
        if self._san is not None:
            self._san.check_read("audit_snapshot")
        if not self._audit_on_seal:
            return {"enabled": False}
        rep = self._audit_report
        if rep is None:
            return {"enabled": True, "sealed": False}
        out = {
            "enabled": True, "sealed": True,
            "programs": len(list(rep["programs"])),
            "skipped": len(list(rep["skipped"])),
            "violations": [
                {"program": v.program, "rule": v.rule,
                 "message": v.message}
                for v in list(rep["violations"])],
        }
        if rep.get("error"):
            out["error"] = rep["error"]
        return out

    def prefix_affinity_tokens(self, hashes: List[bytes]) -> int:
        """Read-only prefix-affinity probe for the multi-engine
        router: how many leading prompt tokens of the rolling
        block-hash chain this engine's prefix store already holds.
        Pure peek — no LRU refresh, no adoption, no device traffic —
        so probing every replica before routing perturbs none of
        them. 0 when the store is off or degradation disabled it
        (min_service: adoption wouldn't happen anyway, so affinity
        must not steer traffic at pages the replica won't share)."""
        if self._prefix is None or self._prefix_disabled():
            return 0
        return self._prefix.match_len(hashes) * self._prefix_block


# ---------------------------------------------------------------------------
# /metrics + /healthz exposition (parity: FastDeploy-style serving
# endpoints; scrape target for Prometheus)
# ---------------------------------------------------------------------------
class MetricsServer:
    """Handle for a running metrics endpoint: ``server_address`` for
    the bound port and a CLEAN ``shutdown()`` — stop ``serve_forever``,
    JOIN the serving thread, CLOSE the listening socket — so chaos
    tests and multi-engine runs don't leak listeners or fds.
    Idempotent; also a context manager."""

    def __init__(self, server, thread):
        self._server = server
        self._thread = thread
        self._closed = False

    @property
    def server_address(self):
        return self._server.server_address

    @property
    def url(self) -> str:
        host, port = self._server.server_address[:2]
        return f"http://{host}:{port}"

    def shutdown(self):
        if self._closed:
            return
        self._closed = True
        self._server.shutdown()
        self._thread.join(timeout=10)
        self._server.server_close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False


def metrics_http_get(engine, path: str):
    """Route one GET against the serving observability surface —
    ``/metrics`` (Prometheus text), ``/healthz`` (JSON readiness, 503
    while saturated/draining), ``/trace`` (Chrome trace JSON,
    ``?fleet=1`` merges a router's fleet), ``/timeline`` (retained
    time-series windows). Returns ``(status, body_bytes, content_type)``
    or ``None`` for an unknown path.

    Factored out of :func:`start_metrics_server` so the streaming API
    front door (``paddle_tpu.serving_api``) serves the SAME
    observability endpoints beside ``/v1/*`` instead of duplicating
    them. ``engine`` may be an engine, an ``EngineRouter``, or None."""
    import json

    bare = path.split("?")[0]
    if bare == "/metrics":
        text = observability.global_registry().prometheus_text()
        return (200, text.encode(),
                "text/plain; version=0.0.4; charset=utf-8")
    if bare == "/healthz":
        payload = {"status": "ok",
                   "telemetry": observability.enabled()}
        code = 200
        if engine is not None:
            bp = engine.backpressure()
            payload["backpressure"] = bp
            payload["engine"] = engine.metrics_snapshot()
            # degraded is NOT a readiness failure: the replica still
            # serves (shed/throttled) — a router reads the bit to
            # deprioritize it, and the numeric RUNG to rank replicas
            # (a shed_batch replica beats a min_service one)
            payload["degraded"] = bool(bp.get("degraded"))
            payload["degradation_level"] = int(
                bp.get("degradation_level", 0))
            if bp.get("draining"):
                # drain() in progress: in-flight requests still
                # complete, but a router must stop sending —
                # readiness fails first
                payload["status"] = "draining"
                code = 503
            elif bp["saturated"]:
                # honest readiness: requests are waiting and no slot
                # can take them — tell the router to drain, don't
                # smile through it
                payload["status"] = "saturated"
                code = 503
        return (code, json.dumps(payload, default=str).encode(),
                "application/json")
    if bare == "/timeline":
        tl = getattr(engine, "timeline_snapshot", None)
        snap = tl() if tl is not None else None
        if snap is None or not snap.get("enabled"):
            return (404, b"timeline disabled (PT_FLAGS_timeseries "
                    b"off)", "text/plain")
        return (200, json.dumps(snap, default=str).encode(),
                "application/json")
    if bare == "/trace":
        from urllib.parse import parse_qs, urlparse

        q = parse_qs(urlparse(path).query)
        want_fleet = q.get("fleet", ["0"])[0] in ("1", "true")
        tracer = getattr(engine, "_tracer", None)
        if want_fleet and hasattr(engine, "_replicas"):
            # /trace?fleet=1 on a router: ONE merged Perfetto
            # document — router + every replica tracer, failed-over
            # rids joined by flow events (tracing.fleet_chrome_trace)
            body = json.dumps(
                observability.tracing.fleet_chrome_trace(engine),
                default=str).encode()
            return (200, body, "application/json")
        if tracer is None:
            return (404, b"tracing disabled (telemetry off or "
                    b"trace_sample=0)", "text/plain")
        body = json.dumps(
            observability.tracing.chrome_trace([tracer]),
            default=str).encode()
        return (200, body, "application/json")
    return None


def start_metrics_server(engine: Optional[ContinuousBatchingEngine] = None,
                         host: str = "127.0.0.1", port: int = 0):
    """Serve ``/metrics`` (Prometheus text exposition of the process
    registry), ``/healthz`` (JSON readiness: liveness + engine snapshot
    + back-pressure state — **503** while admission is saturated or
    the engine is draining, so a router can drain the replica),
    ``/trace`` (the engine's lifecycle tracer as Chrome trace-event
    JSON, Perfetto-loadable; 404 when tracing is off) and
    ``/timeline`` (the engine's/router's retained time-series windows
    as JSON; 404 when ``PT_FLAGS_timeseries`` is off) on a daemon
    thread.

    Also accepts an :class:`~paddle_tpu.inference.router.EngineRouter`
    as ``engine``: the router exposes the same ``backpressure()`` /
    ``metrics_snapshot()`` surface, so ``/healthz`` becomes the
    FLEET-aggregate readiness (503 only when no replica can take
    traffic) and ``/trace`` serves the router's route/failover/breaker
    event stream. Returns a :class:`MetricsServer` handle; read
    ``handle.server_address`` for the bound port (``port=0`` picks a
    free one), call ``handle.shutdown()`` for a clean stop (thread
    joined, socket closed)."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Handler(BaseHTTPRequestHandler):
        def _send(self, code, body: bytes, ctype: str):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            # a scrape must never die on a transient error: the
            # liveness endpoint failing under load defeats its purpose
            try:
                routed = metrics_http_get(engine, self.path)
                if routed is None:
                    self._send(404, b"not found", "text/plain")
                else:
                    self._send(*routed)
            except BrokenPipeError:
                pass
            except Exception as e:  # noqa: BLE001
                try:
                    self._send(500, repr(e).encode(), "text/plain")
                except Exception:
                    pass

        def log_message(self, fmt, *args):  # quiet scrape noise
            pass

    server = ThreadingHTTPServer((host, port), _Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name="pt-metrics-server")
    thread.start()
    return MetricsServer(server, thread)
