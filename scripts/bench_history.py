#!/usr/bin/env python3
"""Bench-trajectory trend table: join the per-round ``BENCH_r*.json``
driver records (and ``BASELINE.json``'s published numbers, when any)
into one table and flag regressions.

The perf trajectory exists only as loose JSON files nobody reads; this
script is the reader.  Per tracked metric it prints one row across
rounds and compares the LATEST round against the best prior round,
flagging anything that moved the wrong way by more than ``--tolerance``
(default 5%).  Direction-aware: bandwidth up is good, latency/overhead
down is good.  TPU-leg values captured from a stale snapshot
(``tpu_stale``) are annotated ``*`` and never flagged — a stale copy of
an old number is not a fresh regression.

    python scripts/bench_history.py            # table + flags
    python scripts/bench_history.py --json     # machine-readable
    python scripts/bench_history.py --strict   # exit 1 on regressions

Round records are the driver's shape: ``{n, cmd, rc, tail, parsed}``
where ``parsed`` (and/or the last JSON line of ``tail``) carries the
bench.py output; newer rounds add ``shm_*``, latency percentiles, and
``tpu_*`` keys.  Unknown keys are ignored, so the table grows as the
bench does.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# metric -> (direction, label); direction "up" = bigger is better
METRICS = {
    "value": ("up", "shm put/get harmonic GB/s"),
    "shm_put_gbps": ("up", "shm put GB/s"),
    "shm_get_gbps": ("up", "shm get GB/s"),
    "vs_baseline": ("up", "vs single-stream TCP"),
    "p50_read_latency_us": ("down", "p50 64KiB read us"),
    "p99_read_latency_us": ("down", "p99 64KiB read us"),
    "alloc_ms": ("down", "alloc p50 ms"),
    # the HBM->pool push path (the alloc-first zero-copy tentpole): live
    # captures emit these unprefixed; stale-snapshot copies ride the
    # tpu_-prefixed rows below with the usual staleness annotation
    "hbm_put_gbps": ("up", "HBM->store GB/s (live)"),
    "hbm_get_gbps": ("up", "store->HBM GB/s (live)"),
    "prefill_store_overhead": ("down", "store prefill x (live)"),
    "tpu_hbm_put_gbps": ("up", "HBM->store GB/s"),
    "tpu_hbm_get_gbps": ("up", "store->HBM GB/s"),
    "tpu_prefill_store_overhead": ("down", "store-attached prefill x"),
    "tpu_serving_ttft_p50_ms": ("down", "serving TTFT p50 ms"),
    "tpu_serving_ttft_p99_ms": ("down", "serving TTFT p99 ms"),
    "tpu_spec_speedup": ("up", "speculation speedup"),
    "goodput_rps": ("up", "serve goodput req/s"),
    "slo_attainment": ("up", "serve SLO attainment"),
    # the step profiler's serving-leg attribution (engine/stepprof.py):
    # device-drain share of step wall time and retrace pressure — a
    # round that turns the step loop host-bound or shape-polymorphic
    # is flagged here, not argued about
    "host_stall_frac": ("down", "serving host-stall frac"),
    "retraces_per_100_steps": ("down", "retraces / 100 steps"),
    # dispatch economy (the single-sync speculation work): compiled
    # programs launched per decoded token, and accepted draft tokens
    # per fused spec dispatch — the two numbers that turn "spec is
    # 0.53x at 0.938 acceptance" into an attributable regression
    "dispatches_per_token": ("down", "dispatches / decoded token"),
    "spec_accept_per_dispatch": ("up", "spec accepted / dispatch"),
    # the disaggregation verdict (bench_serve.py `disagg` block): fleet
    # TTFT/TPOT p99 over the same-decode-budget monolith's at the top
    # offered rate — < 1.0 means prefill/decode separation is paying;
    # the PD acceptance is ttft_ratio <= 1.0 with tpot_burst_ratio
    # measurably below it under a prefill-heavy mix
    "ttft_ratio": ("down", "disagg/monolith TTFT p99"),
    "tpot_burst_ratio": ("down", "disagg/monolith TPOT p99"),
    # the health plane's verdict on the serving run (bench_serve.py
    # `health` block): watchdog firing transitions during the sweep —
    # a round that starts paging under the same load is a regression
    # even when the raw latency rows stay green
    "alerts_fired": ("down", "serve alerts fired"),
    # the admission plane's verdict (bench_serve.py `admission` block):
    # 1 = goodput at the highest offered rate held ≥50% of the curve's
    # peak (graceful degradation), 0 = collapse — a round that loses
    # the plateau regressed the control loop itself
    "goodput_plateau": ("up", "goodput plateau under overload"),
    # usage-attribution plane (PR 15): fleet-wide share of prompt tokens
    # served from the store per bench_serve's /debug/usage join — the
    # cache paying for itself, trended
    "usage_reuse_ratio": ("up", "store-served prompt-token share"),
    # the multi-node cluster leg (bench.py --endpoints N): aggregate
    # fleet bandwidth through the consistent-hash router
    "cluster_put_gbps": ("up", "cluster put GB/s (aggregate)"),
    "cluster_get_gbps": ("up", "cluster get GB/s (aggregate)"),
    # the reshape plane (same leg): descriptor-batched membership
    # migration throughput, with the per-key fallback's number kept as
    # the comparison row — a round where the two converge means the
    # batched path silently degraded to per-key copies
    "migrate_gbps": ("up", "reshape migrate GB/s (batched)"),
    "migrate_gbps_per_key": ("up", "reshape migrate GB/s (per-key)"),
    # the session plane (bench_serve.py --conversation `sessions`
    # block): fraction of computed prompt tokens that were re-prefill
    # waste — context a prior turn already paid for — and the
    # session-affinity hit rate among re-visits.  A round where waste
    # climbs or stickiness drops broke the cross-turn KV-persistence
    # contract, not just a latency number
    "reprefill_waste_frac": ("down", "session re-prefill waste frac"),
    "affinity_hit_rate": ("up", "session affinity hit rate"),
    # the resumption plane (bench_serve.py `resumption` block): streams
    # that crossed at least one mid-stream splice during the sweep, and
    # the worst client-visible stall the splices cost — both down-good:
    # a healthy fleet resumes nothing, and when chaos rounds DO splice,
    # the stall ceiling is the client-experience number to hold
    "stream_resumes": ("down", "streams resumed mid-sweep"),
    "max_stall_ms": ("down", "worst client stall ms"),
    # the stage ledger's TTFT decomposition (bench_serve.py `critpath`
    # block, infinistore_tpu/critpath.py): per-stage p99 at sweep end —
    # a round where one stage's p99 climbs is a NAMED regression
    # (scripts/trace_diff.py diffs two captures the same way); absent
    # keys (no /debug/critpath on older rounds) skip silently
    "stage_p99_admission_wait_ms": ("down", "p99 admission_wait ms"),
    "stage_p99_queue_wait_ms": ("down", "p99 queue_wait ms"),
    "stage_p99_prefill_compute_ms": ("down", "p99 prefill_compute ms"),
    "stage_p99_kv_flush_ms": ("down", "p99 kv_flush ms"),
    "stage_p99_store_transfer_ms": ("down", "p99 store_transfer ms"),
    "stage_p99_decode_queue_ms": ("down", "p99 decode_queue ms"),
    "stage_p99_first_token_ms": ("down", "p99 first_token ms"),
    "stage_p99_per_token_decode_ms": ("down", "p99 per_token_decode ms"),
    "stage_p99_unattributed_ms": ("down", "p99 unattributed ms"),
}


def _last_json_line(text: str):
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            return json.loads(line)
        except ValueError:
            continue
    return None


_PAIR = re.compile(r'"([a-z0-9_]+)":\s*(-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|'
                   r'true|false)(?=[,}\s])')


def _salvage_pairs(text: str):
    """Flat key/number pairs regex-scanned out of a TRUNCATED JSON
    fragment — the driver caps ``tail``, and a round whose record lost
    its opening brace (r05) would otherwise vanish from the trend."""
    out = {}
    for k, v in _PAIR.findall(text):
        if v in ("true", "false"):
            out[k] = v == "true"
        else:
            out[k] = float(v)
    return out


def load_round(path: Path):
    """One round's flat metric dict (numbers only) + its round number
    and staleness marker."""
    rec = json.loads(path.read_text())
    m = re.search(r"r(\d+)", path.stem)
    n = rec.get("n", int(m.group(1)) if m else 0)
    flat = {}
    parsed = rec.get("parsed") or {}
    tail = _last_json_line(rec.get("tail", ""))
    if tail is None:  # truncated fragment: salvage what scans
        tail = _salvage_pairs(rec.get("tail", ""))
    for src in (parsed, tail):  # tail is richer; parsed wins nothing new
        for k, v in src.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            flat.setdefault(k, float(v))
    stale = bool(parsed.get("tpu_stale") or tail.get("tpu_stale")
                 or tail.get("stale"))
    return n, flat, stale


def load_baseline():
    """Published reference numbers from BASELINE.json, when any are
    numeric (the seed repo ships an empty ``published`` section)."""
    path = REPO / "BASELINE.json"
    if not path.exists():
        return {}
    try:
        pub = json.loads(path.read_text()).get("published") or {}
    except ValueError:
        return {}
    return {k: float(v) for k, v in pub.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def collect(repo: Path = REPO):
    rounds = []
    for path in sorted(repo.glob("BENCH_r*.json")):
        try:
            rounds.append(load_round(path))
        except (ValueError, OSError) as e:
            print(f"# skipping {path.name}: {e}", file=sys.stderr)
    rounds.sort(key=lambda r: r[0])
    return rounds


def regressions(rounds, tolerance: float):
    """Latest round vs the best prior round, per tracked metric.
    Returns ``{metric: {latest, best_prior, best_round, ratio}}`` for
    metrics that regressed past the tolerance.  Stale-TPU rounds are
    excluded on BOTH sides for tpu_* metrics."""
    if len(rounds) < 2:
        return {}
    latest_n, latest, latest_stale = rounds[-1]
    out = {}
    for key, (direction, _label) in METRICS.items():
        if key not in latest:
            continue
        if key.startswith("tpu_") and latest_stale:
            continue  # a stale snapshot is not a fresh measurement
        prior = [
            (n, flat[key]) for n, flat, stale in rounds[:-1]
            if key in flat and not (key.startswith("tpu_") and stale)
        ]
        if not prior:
            continue
        best_n, best = (max if direction == "up" else min)(
            prior, key=lambda p: p[1]
        )
        cur = latest[key]
        if best == 0:
            continue
        ratio = cur / best
        worse = ratio < (1 - tolerance) if direction == "up" \
            else ratio > (1 + tolerance)
        if worse:
            out[key] = {
                "latest": cur, "best_prior": best,
                "best_round": best_n, "latest_round": latest_n,
                "ratio": round(ratio, 3),
            }
    return out


def render(rounds, baseline, flagged):
    cols = [n for n, _f, _s in rounds]
    width = max((len(lbl) for _d, lbl in METRICS.values()), default=20) + 2
    head = f"{'metric':{width}s}" + "".join(f"{'r%02d' % n:>10s}" for n in cols)
    if baseline:
        head += f"{'baseline':>10s}"
    lines = [head, "-" * len(head)]
    for key, (_direction, label) in METRICS.items():
        if not any(key in flat for _n, flat, _s in rounds) \
                and key not in baseline:
            continue
        row = f"{label:{width}s}"
        for _n, flat, stale in rounds:
            v = flat.get(key)
            if v is None:
                row += f"{'-':>10s}"
            else:
                mark = "*" if key.startswith("tpu_") and stale else ""
                row += f"{_fmt(v) + mark:>10s}"
        if baseline:
            row += f"{_fmt(baseline[key]) if key in baseline else '-':>10s}"
        if key in flagged:
            f = flagged[key]
            row += (f"  REGRESSED vs r{f['best_round']:02d} "
                    f"({f['ratio']:.2f}x)")
        lines.append(row)
    if any(s for _n, _f, s in rounds):
        lines.append("* tpu leg served from a stale committed snapshot "
                     "(no chip at bench time) — not flagged")
    return "\n".join(lines)


def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e6:
        return str(int(v))
    return f"{v:.3g}" if abs(v) >= 100 else f"{v:.3f}".rstrip("0").rstrip(".")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("bench_history.py")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="relative slack before a move counts as a "
                         "regression (default 5%%)")
    ap.add_argument("--json", action="store_true",
                    help="emit the joined rounds + flags as JSON")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any metric regressed")
    args = ap.parse_args(argv)
    rounds = collect()
    if not rounds:
        print("no BENCH_r*.json records found", file=sys.stderr)
        return 0
    baseline = load_baseline()
    flagged = regressions(rounds, args.tolerance)
    if args.json:
        print(json.dumps({
            "rounds": [
                {"round": n, "stale_tpu": s, "metrics": f}
                for n, f, s in rounds
            ],
            "baseline": baseline,
            "regressions": flagged,
        }, indent=2))
    else:
        print(render(rounds, baseline, flagged))
        if flagged:
            print(f"\n{len(flagged)} metric(s) regressed vs the best "
                  "prior round (see rows above)")
        else:
            print("\nno regressions vs best prior round "
                  f"(tolerance {args.tolerance:.0%})")
    return 1 if (args.strict and flagged) else 0


if __name__ == "__main__":
    sys.exit(main())
