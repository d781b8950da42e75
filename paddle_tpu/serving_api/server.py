"""Async streaming HTTP front door for the continuous-batching engine.

The subsystem that turns the engine from a benchmark-driven library
into a served product: a stdlib-only threaded HTTP server exposing
OpenAI-compatible ``/v1/completions`` (server-sent-event token
streaming) and ``/v1/models``, riding the SAME observability surface
as :func:`~paddle_tpu.inference.serving.start_metrics_server`
(``/metrics``, ``/healthz``, ``/trace``, ``/timeline`` — one routing
function, not a copy).

Threading model — the engine's single-scheduler-thread contract is
kept, not worked around:

* ONE **driver thread** owns the engine (or ``EngineRouter``): it
  ticks ``step_chunk`` (chunk length chosen by the scheduler policy),
  applies deferred cancels, and flushes newly-accepted tokens into
  per-request stream queues. It is the only thread that touches
  scheduler state — exactly what the sanitizer's thread-ownership
  invariant enforces.
* HTTP **handler threads** are producers/consumers only: they submit
  via ``add_request`` (the documented producer-safe entry), then block
  on their stream queue. Tokens stream out as the engine ACCEPTS them
  — spec-decode's multi-token commits arrive as multi-token SSE
  deltas, the user-visible form of that latency win.
* A client disconnect mid-stream surfaces as a failed socket write in
  the handler, which defers ``cancel(rid)`` to the driver thread —
  slots, KV pages and prefix refs are provably freed through the
  engine's one teardown path (the chaos lane's disconnect storm pins
  this).

Zero new compiled programs: the front door is transport + policy; the
compile-counter guard pins the program set unchanged.
"""

from __future__ import annotations

import collections
import itertools
import json
import queue
import threading
from typing import Dict, Optional

import jax

from .. import flags
from ..inference.router import EngineRouter
from ..inference.serving import metrics_http_get
from . import protocol
from .scheduler import default_scheduler

# sentinel kinds on a stream queue
_TOKENS, _DONE, _ERROR = "tokens", "done", "error"


class _Stream:
    """Bridge between the driver thread (producer) and one handler
    thread (consumer): a queue of token deltas ending in a terminal
    sentinel. ``sent`` is driver-private (how much of ``req.output``
    has been flushed)."""

    __slots__ = ("q", "sent", "closed")

    def __init__(self):
        self.q: "queue.Queue" = queue.Queue()
        self.sent = 0
        self.closed = False

    def push_tokens(self, toks):
        self.q.put((_TOKENS, toks))

    def finish(self, reason: Optional[str], meta: dict):
        self.q.put((_DONE, reason, meta))

    def error(self, message: str):
        self.q.put((_ERROR, message))


class ServingFrontDoor:
    """Owns the driver thread and the rid→stream registry. Fronts a
    single :class:`ContinuousBatchingEngine` or an
    :class:`~paddle_tpu.inference.router.EngineRouter` fleet — the
    submit/cancel/result surface is shape-compatible."""

    def __init__(self, target, scheduler=None, max_chunk: int = 8,
                 model_id: str = "paddle-tpu"):
        self.target = target
        self.model_id = model_id
        self.max_chunk = int(max_chunk)
        self._is_router = isinstance(target, EngineRouter)
        self._sched = scheduler
        if scheduler is not None:
            if self._is_router:
                # one policy instance across the fleet: the fair-share
                # ledger is fleet-global (tenants span replicas)
                for rep in target._replicas:
                    rep.engine.set_scheduler(scheduler)
            else:
                target.set_scheduler(scheduler)
        self._streams: Dict[int, _Stream] = {}
        self._streams_lock = threading.Lock()
        # distinct tenant ids admitted so far: tenant strings are
        # CLIENT-controlled and each unique value mints permanent
        # per-tenant series/buckets — bounded by PT_FLAGS_api_max_
        # tenants (new tenants past the cap are rejected 429). The
        # lock makes check+reserve atomic across handler threads; a
        # reservation rolls back if the request never admits, so
        # junk requests can't burn the cap
        self._tenants_seen: set = set()
        self._tenant_lock = threading.Lock()
        # cancels deferred to the driver thread (engine.cancel frees
        # slots/pages — scheduler-thread-only, per the engine contract)
        self._cancels: "collections.deque" = collections.deque()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._dead: Optional[str] = None
        self._req_seq = itertools.count()
        self._thread = threading.Thread(
            target=self._drive, daemon=True, name="pt-api-driver")
        self._thread.start()

    # ---------------- handler-thread surface ----------------
    def submit(self, creq: "protocol.CompletionRequest"):
        """Validate+enqueue one completion request; returns
        ``(rid, stream)``. Runs on a handler thread — ``add_request``
        is the documented producer-safe entry; the stream registers
        AFTER submit and catches up from ``output[0]``, so no token
        can be lost in the window."""
        if self._dead is not None:
            raise RuntimeError(f"serving driver died: {self._dead}")
        reserved = False
        if creq.tenant is not None:
            with self._tenant_lock:
                if creq.tenant not in self._tenants_seen:
                    cap = int(flags.flag("api_max_tenants"))
                    if len(self._tenants_seen) >= cap:
                        raise protocol.ProtocolError(
                            429, f"tenant cardinality cap reached "
                            f"({cap} distinct tenants; "
                            "PT_FLAGS_api_max_tenants) — new tenant "
                            "ids are rejected to bound per-tenant "
                            "metric/accounting state")
                    self._tenants_seen.add(creq.tenant)
                    reserved = True
        try:
            rid = self.target.add_request(creq.prompt,
                                          **creq.engine_kwargs())
        except BaseException:
            if reserved:
                # the request never admitted: a junk request must not
                # burn a cap slot (the guard would become the DoS)
                with self._tenant_lock:
                    self._tenants_seen.discard(creq.tenant)
            raise
        stream = _Stream()
        with self._streams_lock:
            self._streams[rid] = stream
        self._wake.set()
        return rid, stream

    def defer_cancel(self, rid: int):
        """Request cancellation from a handler thread (client
        disconnect): applied by the driver at the next tick."""
        self._cancels.append(rid)
        self._wake.set()

    # ---------------- driver thread ----------------
    def _tick(self) -> bool:
        k = self.max_chunk
        if self._sched is not None:
            if self._is_router:
                # the fleet tick drives every replica with ONE chunk
                # length: any replica with urgent admission work (or
                # a router-held request) pulls the whole tick down to
                # the probe chunk — a full chunk anywhere delays that
                # replica's next admission point
                k = min(self._sched.chunk_len(rep.engine,
                                              self.max_chunk)
                        for rep in self.target._replicas)
                if self.target._queue:
                    k = min(k, getattr(self._sched, "probe_chunk", k))
            else:
                k = self._sched.chunk_len(self.target, self.max_chunk)
        if self._is_router:
            return self.target.step(max_chunk=k)
        return self.target.step_chunk(k)

    def _request_index(self) -> Dict[int, object]:
        """rid → live/finished Request, built ONCE per flush — driver
        thread only (the structures are scheduler-owned). One pass
        over queues/slots/finish registries per tick keeps the flush
        O(streams), the same order as the engine's own per-tick queue
        scans; per-stream linear hunts would make the hot loop
        O(streams × queue). Failover moves a rid between replicas;
        rebuilding per tick follows it for free."""
        idx: Dict[int, object] = {}
        if self._is_router:
            engines = [rep.engine for rep in self.target._replicas]
            for req in list(self.target._queue):
                idx[req.rid] = req
            idx.update(self.target._finished)
        else:
            engines = [self.target]
        for eng in engines:
            for req in list(eng._queue):
                idx[req.rid] = req
            for req in list(eng._slot_req.values()):
                idx[req.rid] = req
            idx.update(eng._finished)
        return idx

    def _flush_streams(self):
        with self._streams_lock:
            items = list(self._streams.items())
        if not items:
            return
        index = self._request_index()
        for rid, st in items:
            req = index.get(rid)
            if req is None:
                continue
            out = req.output
            if len(out) > st.sent:
                st.push_tokens([int(t) for t in out[st.sent:]])
                st.sent = len(out)
            if req.done:
                st.finish(req.finish_reason, {
                    "prompt_tokens": int(req.prompt.size),
                    "completion_tokens": len(out),
                    "ttft_ms": req.ttft_ms,
                    "tpot_ms": req.tpot_ms,
                    "slo_met": req.slo_met,
                })
                with self._streams_lock:
                    self._streams.pop(rid, None)
                # REAP: the library path's finish registry assumes a
                # caller harvests results and discards the engine; a
                # long-running server must not retain every served
                # request's prompt/output forever (cumulative
                # tenant/SLO/cost accounting already landed at finish)
                self._reap(rid)

    def _reap(self, rid: int):
        """Drop a delivered request's terminal record (driver thread
        only — the registries are scheduler-owned)."""
        if self._is_router:
            self.target._finished.pop(rid, None)
            ridx = self.target._owner.pop(rid, None)
            if ridx is not None:
                self.target._replicas[ridx].engine._finished.pop(
                    rid, None)
        else:
            self.target._finished.pop(rid, None)

    def _apply_cancels(self):
        while self._cancels:
            try:
                rid = self._cancels.popleft()
            except IndexError:
                break
            self.target.cancel(rid)
            # the cancel path marks req.done — the normal flush
            # delivers the terminal sentinel to any waiting handler

    def _drive(self):
        try:
            while not self._stop.is_set():
                self._apply_cancels()
                busy = self._tick()
                self._flush_streams()
                if not busy and not self._cancels:
                    # idle: sleep until a submit/cancel wakes us (the
                    # timeout keeps deadline expiry ticking for queued
                    # requests even with no new arrivals)
                    with jax.profiler.TraceAnnotation("pt.engine.wait"):
                        self._wake.wait(timeout=0.02)
                    self._wake.clear()
        except BaseException as e:  # noqa: BLE001
            self._dead = f"{type(e).__name__}: {e}"
            with self._streams_lock:
                streams, self._streams = dict(self._streams), {}
            for st in streams.values():
                st.error(self._dead)
            raise

    def shutdown(self):
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=10)
        with self._streams_lock:
            streams, self._streams = dict(self._streams), {}
        for st in streams.values():
            st.error("server shutting down")


class ServingAPIServer:
    """Handle for a running front door: ``url`` for the bound port,
    clean idempotent ``shutdown()`` (driver joined, listener closed) —
    the :class:`~paddle_tpu.inference.serving.MetricsServer` contract,
    so chaos tests and multi-server runs never leak threads or fds."""

    def __init__(self, server, thread, front_door):
        self._server = server
        self._thread = thread
        self.front_door = front_door
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
        self.front_door.shutdown()
        self._server.shutdown()
        self._thread.join(timeout=10)
        self._server.server_close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False


def start_api_server(target, host: str = "127.0.0.1", port: int = 0,
                     scheduler="auto", max_chunk: int = 8,
                     model_id: str = "paddle-tpu"):
    """Serve the OpenAI-compatible streaming API over ``target`` (an
    engine or an :class:`EngineRouter`) on a daemon thread pool.

    Endpoints: ``POST /v1/completions`` (SSE streaming with
    ``"stream": true``, aggregate JSON otherwise), ``GET /v1/models``,
    plus the full observability surface (``/metrics``, ``/healthz``,
    ``/trace``, ``/timeline``) via the same routing the metrics server
    uses.

    ``scheduler``: an admission policy object (installed via
    ``engine.set_scheduler``), ``None`` for engine-native FIFO, or
    ``"auto"`` (default) to build from ``PT_FLAGS_sched_policy``.
    Returns a :class:`ServingAPIServer` handle (``handle.url``,
    ``handle.shutdown()``; also a context manager)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    if scheduler == "auto":
        scheduler = default_scheduler()
    fd = ServingFrontDoor(target, scheduler=scheduler,
                          max_chunk=max_chunk, model_id=model_id)

    class _Handler(BaseHTTPRequestHandler):
        def _send(self, code, body: bytes, ctype: str):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _send_json(self, code, obj):
            self._send(code, json.dumps(obj, default=str).encode(),
                       "application/json")

        def log_message(self, fmt, *args):  # quiet request noise
            pass

        def do_GET(self):
            try:
                if self.path.split("?")[0] == "/v1/models":
                    self._send_json(
                        200, protocol.models_payload(fd.model_id))
                    return
                routed = metrics_http_get(fd.target, self.path)
                if routed is None:
                    self._send(404, protocol.error_body(
                        "not found", "not_found_error"),
                        "application/json")
                else:
                    self._send(*routed)
            except BrokenPipeError:
                pass
            except Exception as e:  # noqa: BLE001
                try:
                    self._send(500, protocol.error_body(
                        repr(e), "internal_error"), "application/json")
                except Exception:
                    pass

        # ---------------- completions ----------------
        def do_POST(self):
            try:
                if self.path.split("?")[0] != "/v1/completions":
                    self._send(404, protocol.error_body(
                        "not found", "not_found_error"),
                        "application/json")
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                except (ValueError, TypeError) as e:
                    self._send(400, protocol.error_body(
                        f"invalid JSON body: {e}"), "application/json")
                    return
                try:
                    creq = protocol.parse_completion_request(body)
                    rid, stream = fd.submit(creq)
                except protocol.ProtocolError as e:
                    self._send(e.status, protocol.error_body(str(e)),
                               "application/json")
                    return
                except ValueError as e:
                    # build_request's validation — the same errors the
                    # library path raises, mapped to 400
                    self._send(400, protocol.error_body(str(e)),
                               "application/json")
                    return
                if creq.stream:
                    self._stream_response(creq, rid, stream)
                else:
                    self._aggregate_response(creq, rid, stream)
            except (BrokenPipeError, ConnectionResetError):
                pass
            except Exception as e:  # noqa: BLE001
                try:
                    self._send(500, protocol.error_body(
                        repr(e), "internal_error"), "application/json")
                except Exception:
                    pass

        def _wait(self, stream):
            """Next stream item; surfaces a driver death instead of
            blocking forever."""
            while True:
                try:
                    return stream.q.get(timeout=30.0)
                except queue.Empty:
                    if fd._dead is not None:
                        return (_ERROR, fd._dead)
                    # otherwise keep waiting: the engine enforces
                    # request deadlines and will close the stream

        def _stream_response(self, creq, rid, stream):
            cid = f"cmpl-{rid}"
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            self.end_headers()
            try:
                if creq.echo:
                    self.wfile.write(protocol.sse_data(
                        protocol.completion_chunk(
                            cid, fd.model_id,
                            [int(t) for t in creq.prompt])))
                    self.wfile.flush()
                while True:
                    item = self._wait(stream)
                    if item[0] == _TOKENS:
                        self.wfile.write(protocol.sse_data(
                            protocol.completion_chunk(
                                cid, fd.model_id, item[1])))
                        self.wfile.flush()
                    elif item[0] == _DONE:
                        self.wfile.write(protocol.sse_data(
                            protocol.completion_chunk(
                                cid, fd.model_id, [],
                                finish_reason=item[1])))
                        self.wfile.write(protocol.SSE_DONE)
                        self.wfile.flush()
                        return
                    else:  # _ERROR
                        self.wfile.write(protocol.sse_data(
                            {"error": {"message": item[1],
                                       "type": "internal_error"}}))
                        self.wfile.flush()
                        return
            except (BrokenPipeError, ConnectionResetError, OSError):
                # CLIENT DISCONNECT mid-stream: the engine must get
                # its slot/pages/prefix refs back — cancel on the
                # driver (scheduler) thread, never from here
                fd.defer_cancel(rid)

        def _aggregate_response(self, creq, rid, stream):
            cid = f"cmpl-{rid}"
            tokens = []
            reason = None
            while True:
                item = self._wait(stream)
                if item[0] == _TOKENS:
                    tokens.extend(item[1])
                elif item[0] == _DONE:
                    reason = item[1]
                    meta = item[2]
                    break
                else:
                    self._send(500, protocol.error_body(
                        item[1], "internal_error"), "application/json")
                    return
            try:
                self._send_json(200, protocol.completion_response(
                    cid, fd.model_id, tokens, reason,
                    meta["prompt_tokens"],
                    echo_tokens=([int(t) for t in creq.prompt]
                                 if creq.echo else None)))
            except (BrokenPipeError, ConnectionResetError, OSError):
                pass  # request already finished engine-side: no leak

    server = ThreadingHTTPServer((host, port), _Handler)
    server.daemon_threads = True
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name="pt-api-server")
    thread.start()
    return ServingAPIServer(server, thread, fd)
