"""Closed-loop streamed completions over HTTP, from parameters alone.

``clients`` workers; each sends a streamed ``POST /v1/completions``,
waits for its last token and sends the next at once. The traffic file
gives the two length distributions::

    "prompt":     {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32, "max": 1024}
    "max_tokens": {"dist": "uniform", "min": 8, "max": 32}

``--seed`` draws every request: its two lengths from those
distributions, clipped to ``min`` and ``max``, and its token ids,
uniform over the vocabulary. No EOS is set, so a request runs to its
``max_tokens``. All of it is made before the window opens.

Times are the client's: ``send`` just before the request is written,
then the arrival of every streamed event. The HTTP client is the
standard library's, as in ``chip_smoke.py:_stream_completion``.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
import urllib.parse

import numpy as np

CONNECT_TRIES = 4


def _lengths(spec: dict, rng, n: int) -> np.ndarray:
    if spec["dist"] == "lognormal":
        raw = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    elif spec["dist"] == "uniform":
        raw = rng.uniform(spec["min"], spec["max"], n)
    else:
        raise ValueError(f"unknown dist {spec['dist']!r}")
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def request_pool(params: dict, vocab: int, seed: int, n: int) -> list:
    """``n`` requests as (prompt ids, max_tokens), all from the seed."""
    rng = np.random.default_rng([seed, 11])
    prompts = _lengths(params["prompt"], rng, n)
    outs = _lengths(params["max_tokens"], rng, n)
    return [(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outs)]


class NoSpan:
    """Stands in for ``jax.profiler.TraceAnnotation`` in untraced runs."""

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def stream_completion(url: str, prompt, max_tokens: int,
                      annotate=NoSpan) -> dict:
    """One streamed request; never raises: a failure is a record."""
    u = urllib.parse.urlparse(url)
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_tokens": int(max_tokens), "stream": True})
    rec = {"prompt": prompt, "max_tokens": max_tokens, "ids": [],
           "arrivals": [], "error": None, "finish": None, "status": None}
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=300)
    try:
        rec["send"] = time.perf_counter()
        with annotate("client.send"):
            for attempt in range(CONNECT_TRIES):
                try:  # a full listen queue resets the connection: a
                    # client tries again, and its clock keeps running
                    conn.request("POST", "/v1/completions", body=body,
                                 headers={"Content-Type":
                                          "application/json"})
                    resp = conn.getresponse()
                    break
                except (ConnectionResetError, ConnectionRefusedError):
                    rec["retries"] = attempt + 1
                    conn.close()
                    if attempt + 1 == CONNECT_TRIES:
                        raise
                    time.sleep(0.05)
        rec["status"] = resp.status
        with annotate("client.receive"):
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                event = json.loads(line[len("data: "):])
                if "error" in event:
                    rec["error"] = str(event["error"])
                    break
                choice = event["choices"][0]
                if choice["token_ids"]:
                    rec["arrivals"].append(
                        (time.perf_counter(), len(choice["token_ids"])))
                    rec["ids"] += choice["token_ids"]
                rec["finish"] = choice["finish_reason"] or rec["finish"]
    except Exception as e:  # noqa: BLE001 - the record carries it
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    rec["end"] = time.perf_counter()
    if rec["error"] is None and (rec["status"] != 200
                                 or len(rec["ids"]) != max_tokens):
        rec["error"] = (f"HTTP {rec['status']}, {len(rec['ids'])} of "
                        f"{max_tokens} tokens, finish {rec['finish']}")
    return rec


def run_closed_loop(url: str, pool: list, clients: int, seconds: float,
                    drain_s: float, annotate=NoSpan, warm_completions=0,
                    stagger_s=0.0, on_open=None) -> dict:
    """Drive the loop. The clients start ``stagger_s`` apart and run
    from the first request on; once ``warm_completions`` requests have
    finished, ``on_open`` is called and the window opens, with the
    clients in full swing at scattered phases. It closes ``seconds``
    later; requests in flight at the close are waited for, up to
    ``drain_s`` more. Returns every record with the window's two ends
    on ``perf_counter`` and the count of requests still open."""
    lock = threading.Lock()
    state = {"next": 0, "t_end": None}
    records = []
    opened = threading.Event()

    def worker(delay):
        time.sleep(delay)
        while True:
            with lock:
                i = state["next"]
                t_end = state["t_end"]
                if i >= len(pool) or (t_end is not None
                                      and time.perf_counter() >= t_end):
                    return
                state["next"] = i + 1
            prompt, out = pool[i]
            rec = stream_completion(url, prompt, out, annotate)
            rec["index"] = i
            with lock:
                records.append(rec)
                if len(records) >= warm_completions:
                    opened.set()

    threads = [threading.Thread(target=worker, args=(k * stagger_s,),
                                daemon=True) for k in range(clients)]
    for t in threads:
        t.start()
    if warm_completions:
        opened.wait(timeout=900)
    if on_open is not None:
        on_open()
    with lock:
        t0 = time.perf_counter()
        state["t_end"] = t_end = t0 + seconds
    deadline = t_end + drain_s
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    with lock:
        done = sorted(records, key=lambda r: r["index"])
        sent = state["next"]
    return {"records": done, "t0": t0, "t_end": t_end,
            "still_open": sent - len(done)}


def percentile(values: list, q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def summarize(records: list, t0: float, t_end: float) -> dict:
    """The window's end-to-end numbers from the clients' records: the
    tokens that arrived inside it, and the latencies of every request
    sent inside it (those that finished in the drain too)."""
    sent = [r for r in records if r["send"] >= t0]
    good = [r for r in sent if r["error"] is None]
    out_tokens = sum(n for r in records for t, n in r["arrivals"]
                     if t0 <= t <= t_end)
    ttft = [(r["arrivals"][0][0] - r["send"]) * 1e3 for r in good]
    tpot = [(r["arrivals"][-1][0] - r["arrivals"][0][0]) * 1e3
            / (len(r["ids"]) - 1) for r in good if len(r["ids"]) > 1]
    return {
        "sent": sent, "out_tokens": out_tokens, "wall_s": t_end - t0,
        "out_tokens_per_s": out_tokens / (t_end - t0),
        "ttft_p95_ms": percentile(ttft, 95), "ttft_p50_ms":
        percentile(ttft, 50), "tpot_p95_ms": percentile(tpot, 95),
        "tpot_p50_ms": percentile(tpot, 50), "samples": len(good)}
