"""Latency arithmetic on a request log. One record per request:
``{"due": s, "sent": s, "first": s|None, "last": s|None, "end": s|None,
"tokens": n, "prompt_tokens": n, "chunks": [(s, n), ...], "ok": bool}``,
all times on one host clock."""

from __future__ import annotations

import math


def percentile(values: list, q: float):
    """Nearest-rank percentile (the smallest value with at least q% of
    the samples at or below it); None of nothing."""
    if not values:
        return None
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[min(rank, len(s)) - 1]


def ttft_s(rec: dict) -> float:
    """From when the request was due (open loop) or sent (closed loop:
    due == sent) to its first streamed token."""
    return rec["first"] - rec["due"]


def tpot_s(rec: dict):
    """Mean gap between output tokens of one request: tokens arrive in
    blocks, so the per-request mean is the honest gap. None for a request
    of one token."""
    if rec["tokens"] < 2:
        return None
    return (rec["last"] - rec["first"]) / (rec["tokens"] - 1)


def lateness_s(rec: dict) -> float:
    """How late the generator sent the request."""
    return rec["sent"] - rec["due"]


def summarize(log: list, t0: float, t1: float) -> dict:
    """End-to-end numbers of the window [t0, t1]. The rate counts every
    token that reached a client inside the window, whichever request it
    belongs to (``chunks`` is a request's list of ``(time, tokens)``
    arrivals). Latencies are over ALL requests that ended inside the
    window. A failed or refused request counts in ``failed`` and has no
    latency."""
    ended = [r for r in log if r["end"] is not None and t0 <= r["end"] <= t1]
    ok = [r for r in ended if r["ok"]]
    ttft = [ttft_s(r) * 1e3 for r in ok]
    tpot = [t * 1e3 for t in map(tpot_s, ok) if t is not None]
    tokens = sum(n for r in log for t, n in r["chunks"] if t0 <= t <= t1)
    late = [lateness_s(r) * 1e3 for r in log if t0 <= r["sent"] <= t1]
    return {
        "attempted": len(ended), "failed": len(ended) - len(ok),
        "serve_tok_s": tokens / (t1 - t0),
        "ttft_p95_ms": percentile(ttft, 95), "ttft_p50_ms": percentile(ttft, 50),
        "tpot_p95_ms": percentile(tpot, 95), "tpot_p50_ms": percentile(tpot, 50),
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "lateness_p95_ms": percentile(late, 95),
        "prompt_tokens": sum(r["prompt_tokens"] for r in ok),
    }
