"""Arithmetic of the end-to-end metrics: one percentile definition and the
per-request reductions.  Pure functions of client rows; no clock, no I/O."""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def nearest_rank(samples: Sequence[float], q: float) -> float:
    """The ``ceil(q*n)``-th smallest of ``samples`` (1-indexed), the one
    percentile used everywhere here.  An empty sample is an error: a
    metric of nothing is not 0."""
    vs = sorted(samples)
    if not vs:
        raise ValueError("percentile of an empty sample")
    return vs[min(len(vs) - 1, max(0, math.ceil(q * len(vs)) - 1))]


def ttft_s(row: dict) -> Optional[float]:
    """Due time to first streamed token: the wait a late generator or a full
    server imposes counts, so it is not taken from the send time."""
    return None if row.get("t_first") is None else row["t_first"] - row["t_due"]


def tpot_s(row: dict) -> Optional[float]:
    """(last token time - first token time) / (tokens - 1), per request.
    Tokens arrive in bursts of ``decode_chunk``, so single gaps are either 0
    or a whole dispatch; the request's mean is the steady number."""
    if row.get("t_first") is None or row["tokens"] < 2:
        return None
    return (row["t_last"] - row["t_first"]) / (row["tokens"] - 1)


def tokens_in_window(rows: List[dict], w0: float, w1: float) -> int:
    """Output tokens that reached a client inside [w0, w1), whichever request
    they belong to: all the work of the window, over all of its time."""
    return sum(n for r in rows for t, n in r["events"] if w0 <= t < w1)


def end_to_end(rows: List[dict], w0: float, w1: float) -> Dict[str, float]:
    """Every end-to-end metric the measured rows support.  ``rows`` are the
    measured requests (due, or completed, inside the window); a metric whose
    sample is empty is left out, and the caller fails the run if the cell
    lists it."""
    ok = [r for r in rows if r["ok"]]
    out: Dict[str, float] = {}
    ttfts = [ttft_s(r) for r in ok if ttft_s(r) is not None]
    tpots = [tpot_s(r) for r in ok if tpot_s(r) is not None]
    if ttfts:
        out["ttft_mean_ms"] = sum(ttfts) / len(ttfts) * 1e3
        out["ttft_p50_ms"] = nearest_rank(ttfts, 0.50) * 1e3
        out["ttft_p90_ms"] = nearest_rank(ttfts, 0.90) * 1e3
    if tpots:
        out["tpot_p50_ms"] = nearest_rank(tpots, 0.50) * 1e3
        out["tpot_p90_ms"] = nearest_rank(tpots, 0.90) * 1e3
    if w1 > w0:
        out["out_tok_per_s"] = tokens_in_window(rows, w0, w1) / (w1 - w0)
    return out


def pair_diff(a: dict, b: dict) -> Dict[str, float]:
    """Two answers to one prompt ({"ids": [chosen], "top": [{id: logprob} per
    position]}): the largest difference between the two log-probabilities of
    a token both list, and how many listed tokens the other answer lacks.
    Positions after the first at which the chosen tokens differ have other
    contexts and are not compared; that position's lists still are."""
    diffs, unmatched = [], 0
    for pos, (ta, tb) in enumerate(zip(a["top"], b["top"])):
        diffs += [abs(float(ta[t]) - float(tb[t])) for t in ta if t in tb]
        unmatched += len(set(ta) ^ set(tb))
        if int(a["ids"][pos]) != int(b["ids"][pos]):
            unmatched += sum(len(t) for t in a["top"][pos + 1:])
            break
    return {"n_values": len(diffs), "max_abs": max(diffs, default=0.0),
            "unmatched": unmatched}


def rms(values: Sequence[float]) -> float:
    if not values:
        raise ValueError("rms of nothing")
    return math.sqrt(sum(v * v for v in values) / len(values))
