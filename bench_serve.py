"""Serving SLO benchmark: open-loop goodput-vs-rate curve.

Sweeps arrival rates against a live serving front-end (or a self-hosted
tiny-model server with ``--self-serve``) through the open-loop harness
(`infinistore_tpu/loadgen.py`): Poisson/deterministic arrivals,
concurrent streaming sessions, a shared-prefix request population, and
per-lane TTFT/TPOT percentiles.  The headline output is **goodput** —
requests/s that complete AND meet the TTFT+TPOT SLOs — per offered
rate, the curve ROADMAP item 4's admission/QoS work will be judged
against.

    # against a running server
    python bench_serve.py --url http://127.0.0.1:8000 --rates 2,4,8 \
        --n 64 --slo-ttft 2.0 --slo-tpot 0.25 --json-out serve_load.json

    # zero-setup smoke (in-process tiny model; CI uses this)
    JAX_PLATFORMS=cpu python bench_serve.py --self-serve --rates 8,16 --n 24

``--json-out`` writes one JSON object joining the bench-schema family
(``run_id`` + stable keys; docs/observability.md): ``{run_id, kind:
"serve_load", slo: {...}, config: {...}, curve: [per-rate summaries],
stepprof: {...}, health: {...}}`` — ``stepprof`` is the server's
step-profiler summary (``GET /debug/engine``): host-stall share,
retrace pressure, dispatch counts for the whole sweep; ``health`` is
the health plane's verdict (``GET /debug/health``): alert firing
transitions and the peak burn rate observed, with ``alerts_fired``
mirrored top-level for the trend table (both absent against servers
without the endpoints); ``admission`` is the overload-control verdict —
client-observed 429 shed counts per lane, the server's
``GET /debug/admission`` shed/quota tallies, and the ``plateau`` flag
(goodput at the highest offered rate held ≥50% of the curve's peak
instead of collapsing), with ``goodput_plateau`` mirrored top-level.

``--conversation`` switches the sweep to multi-turn session traffic
(``SessionConfig`` in loadgen.py): rates become session arrivals/s and
the record gains a ``sessions`` block — ``reprefill_waste_frac`` and
``affinity_hit_rate`` (both mirrored top-level for the trend table)
plus the client-observed per-turn TTFT slope, the three numbers of the
cross-turn KV-persistence contract.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import uuid

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def parse_rates(s: str):
    rates = [float(x) for x in s.split(",") if x.strip()]
    if not rates:
        raise argparse.ArgumentTypeError("need at least one rate")
    return rates


def parse_mix(s: str):
    """``weight:prompt:max_tokens`` triples, comma-separated — e.g.
    ``3:24:8,1:96:32`` = 3/4 short chat turns, 1/4 long generations."""
    mix = []
    for part in s.split(","):
        w, p, m = part.split(":")
        mix.append((float(w), int(p), int(m)))
    return mix


def parse_weighted_ints(s: str):
    """``weight:value`` pairs, comma-separated — the turn-count and
    turn-token mixes of ``--conversation`` (e.g. ``3:4,1:8`` = 3/4 of
    sessions run 4 turns, 1/4 run 8)."""
    out = []
    for part in s.split(","):
        w, v = part.split(":")
        out.append((float(w), int(v)))
    return out


def parse_think(s: str):
    """``lo:hi`` uniform think-time range in seconds (``0:0`` =
    agent-loop speed)."""
    lo, hi = s.split(":")
    return (float(lo), float(hi))


def parse_lanes(s: str):
    """``lane:weight`` pairs, comma-separated — e.g. ``10:1,0:4`` = 1
    in 5 requests rides the high-priority lane.  A lane may be an int
    priority or a STRING tenant id (``acme:3,bulk:1``): named tenants
    carry through as the lane label everywhere (metrics, quotas, the
    usage ledger)."""
    lanes = []
    for part in s.split(","):
        lane, w = part.rsplit(":", 1)
        lane = lane.strip()
        lanes.append((int(lane) if lane.lstrip("-").isdigit() else lane,
                      float(w)))
    return lanes


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_store():
    """A python-backend store node in a subprocess (the disagg fleet's
    KV transport).  Returns ``(proc, service_port)``; caller SIGINTs."""
    import socket
    import subprocess

    port, mport = _free_port(), _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 25
    while True:
        if proc.poll() is not None:
            raise RuntimeError("store server failed to start")
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=0.5).close()
            return proc, port
        except OSError:
            if time.time() >= deadline:
                proc.kill()
                raise RuntimeError("store server did not come up")
            time.sleep(0.1)


def self_disagg(args):
    """The zero-setup disaggregated fleet: one store node (subprocess)
    + N in-process prefill workers + M decode workers behind a
    ``FrontDoor`` — the target the ``disagg`` block is measured
    against.  Returns ``(close, url, vocab, fleet_workers)``."""
    import signal

    import jax.numpy as jnp

    from infinistore_tpu.frontdoor import local_fleet
    from infinistore_tpu.models import TINY, scaled

    proc, store_port = _spawn_store()
    try:
        fd, workers, close_fleet = local_fleet(
            store_port, args.prefill_workers, args.decode_workers,
            n_blocks=args.self_serve_blocks,
            max_batch=args.self_serve_batch,
            n_routers=max(1, args.routers),
        )
    except BaseException:
        proc.send_signal(signal.SIGINT)
        proc.wait(timeout=10)
        raise

    def close():
        close_fleet()
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=10)
        except Exception:  # noqa: BLE001
            proc.kill()

    cfg = scaled(TINY, dtype=jnp.float32)
    return close, f"http://127.0.0.1:{fd.port}", cfg.vocab_size, workers


def self_serve(args):
    """An in-process tiny-model ServingServer on a free port: the
    zero-setup target for smokes — real HTTP, real scheduler, no
    checkpoint or separate process needed."""
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.kv import PagedCacheConfig
    from infinistore_tpu.models import TINY, init_params, scaled
    from infinistore_tpu.serve import ServingServer

    cfg = scaled(TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=args.self_serve_blocks,
        block_tokens=4, dtype=cfg.dtype,
    )
    eng = InferenceEngine(params, cfg, pc)
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=args.self_serve_batch,
                        model_id="tiny-bench",
                        slo_ttft_s=args.slo_ttft, slo_tpot_s=args.slo_tpot,
                        quotas=args.quotas or None)
    srv.start()
    return srv, f"http://127.0.0.1:{srv.port}", cfg.vocab_size


def _lane_pct(point, which, key):
    """Completed-weighted mean of one lane percentile across a point's
    lanes — the cross-lane headline the disagg ratios compare on."""
    tot = n = 0.0
    for v in point["lanes"].values():
        stats = v.get(which) or {}
        if stats.get(key) is not None and v.get("completed"):
            tot += stats[key] * v["completed"]
            n += v["completed"]
    return (tot / n) if n else None


def _gather_disagg(url, workers, args):
    """The ``disagg`` block's fleet-side half: the front door's
    /debug/fleet (handoff percentiles, per-role counts) plus the decode
    workers' ledgers (per-request adoption provenance — the store/local
    split is process-global in-process, the ledger is per-worker)."""
    import urllib.request

    fleet = None
    try:
        with urllib.request.urlopen(url + "/debug/fleet", timeout=5) as r:
            fleet = json.loads(r.read())
    except Exception:  # noqa: BLE001 — observability, not the bench
        pass
    adopted = total = 0
    for s in (workers or {}).get("decode", ()):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{s.port}/debug/requests",
                    timeout=5) as r:
                recs = json.loads(r.read()).get("records") or []
        except Exception:  # noqa: BLE001
            continue
        for rec in recs:
            st = rec.get("store") or {}
            total += 1
            if (st.get("reused_chunks") or 0) > 0:
                adopted += 1
    out = {
        "prefill_workers": args.prefill_workers,
        "decode_workers": args.decode_workers,
        "adoption": {
            "requests": total, "adopted": adopted,
            "hit_rate": round(adopted / total, 4) if total else None,
        },
    }
    if fleet and fleet.get("enabled"):
        out["handoff_ms"] = fleet.get("handoff")
        out["fleet_adoption_tokens"] = fleet.get("adoption")
        out["router_requests"] = fleet.get("requests")
    return out


def main(argv=None) -> int:
    from infinistore_tpu.loadgen import LoadConfig, sweep

    ap = argparse.ArgumentParser("bench_serve.py")
    ap.add_argument("--url", default=None,
                    help="serving front-end base URL (http://host:8000)")
    ap.add_argument("--target", dest="url",
                    help="alias of --url: point it at a disaggregated "
                         "front door (istpu-frontdoor) to drive a fleet")
    ap.add_argument("--self-serve", action="store_true",
                    help="spin up an in-process tiny-model server to "
                         "load instead of --url (CI smoke mode)")
    ap.add_argument("--self-disagg", action="store_true",
                    help="spin up a whole in-process disaggregated "
                         "fleet (store node + prefill + decode workers "
                         "+ front door), sweep it, then sweep a "
                         "same-decode-budget monolith and report the "
                         "TTFT/TPOT ratios in a `disagg` block")
    ap.add_argument("--prefill-workers", type=int, default=1,
                    help="--self-disagg: prefill pool size")
    ap.add_argument("--decode-workers", type=int, default=1,
                    help="--self-disagg: decode pool size")
    ap.add_argument("--routers", type=int, default=1,
                    help="--self-disagg: router replicas over the same "
                         "pools (each names the others as --peers); the "
                         "load generator spreads clients across all of "
                         "them and fails over on connect errors")
    ap.add_argument("--pacer", choices=["auto", "thread", "async"],
                    default="auto",
                    help="arrival pacer: 'async' drives every request "
                         "from one asyncio event loop (the 10k-session "
                         "path), 'thread' keeps one thread per in-flight "
                         "request; 'auto' picks async for live targets")
    ap.add_argument("--no-monolith-baseline", action="store_true",
                    help="--self-disagg: skip the monolith comparison "
                         "sweep (faster; no ratio in the output)")
    ap.add_argument("--self-serve-blocks", type=int, default=512)
    ap.add_argument("--self-serve-batch", type=int, default=8)
    ap.add_argument("--rates", type=parse_rates, default=[2.0, 4.0, 8.0],
                    help="comma-separated arrival rates (req/s) to sweep")
    ap.add_argument("--n", type=int, default=32,
                    help="requests per rate point")
    ap.add_argument("--process", choices=["poisson", "deterministic"],
                    default="poisson")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mix", type=parse_mix, default=[(1.0, 24, 8)],
                    help="weight:prompt_tokens:max_tokens triples, "
                         "comma-separated (default 1:24:8)")
    ap.add_argument("--lanes", type=parse_lanes, default=[(0, 1.0)],
                    help="lane:weight pairs, comma-separated (default "
                         "0:1 — one lane).  Lanes are int priorities OR "
                         "string tenant ids: '--lanes acme:3,bulk:1' "
                         "names tenants end to end (metrics, --quota, "
                         "the usage ledger)")
    ap.add_argument("--prefixes", type=int, default=4,
                    help="shared-prefix population size (0 disables)")
    ap.add_argument("--prefix-len", type=int, default=16)
    ap.add_argument("--prefix-frac", type=float, default=0.5,
                    help="fraction of requests that prepend a shared "
                         "prefix (tenant system-prompt traffic shape)")
    ap.add_argument("--vocab", type=int, default=256,
                    help="token ids drawn in [0, vocab) — keep within "
                         "the served model's vocab")
    ap.add_argument("--no-stream", action="store_true",
                    help="non-streaming requests (TTFT == e2e)")
    ap.add_argument("--honor-retry-after", action="store_true",
                    help="a 429-shed request sleeps the server's "
                         "Retry-After (capped 10 s) and re-attempts "
                         "once; default off — the raw shed behavior is "
                         "the measurement")
    ap.add_argument("--quota", action="append", default=[],
                    dest="quotas", metavar="TENANT:TOKS_PER_S[:BURST_S]",
                    help="--self-serve only: per-tenant token quotas "
                         "passed through to the in-process server")
    ap.add_argument("--conversation", action="store_true",
                    help="conversation mode: --rates become SESSION "
                         "arrivals/s, each session runs its turns "
                         "sequentially with per-turn context growth and "
                         "a 'session' id end to end; the record gains a "
                         "`sessions` block (reprefill_waste_frac, "
                         "affinity_hit_rate, per-turn TTFT slope)")
    ap.add_argument("--sessions", type=int, default=16,
                    help="--conversation: sessions per rate point")
    ap.add_argument("--turns", type=parse_weighted_ints,
                    default=[(1.0, 4)],
                    help="--conversation: weight:n_turns mix "
                         "(default 1:4)")
    ap.add_argument("--turn-tokens", type=parse_weighted_ints,
                    default=[(1.0, 16)],
                    help="--conversation: weight:new_user_tokens mix "
                         "per turn (default 1:16)")
    ap.add_argument("--system-prompt-len", type=int, default=32,
                    help="--conversation: shared system-prompt tokens "
                         "every session opens on")
    ap.add_argument("--think", type=parse_think, default=(0.0, 0.0),
                    help="--conversation: lo:hi uniform think-time "
                         "seconds between turns (default 0:0)")
    ap.add_argument("--conv-max-tokens", type=int, default=8,
                    help="--conversation: max_tokens per turn")
    ap.add_argument("--timeout", type=float, default=120.0)
    ap.add_argument("--slo-ttft", type=float,
                    default=float(os.environ.get("ISTPU_SLO_TTFT_S", 2.0)),
                    help="TTFT SLO in seconds (goodput threshold)")
    ap.add_argument("--slo-tpot", type=float,
                    default=float(os.environ.get("ISTPU_SLO_TPOT_S", 0.25)),
                    help="TPOT SLO in seconds (goodput threshold)")
    ap.add_argument("--cooldown", type=float, default=0.5,
                    help="seconds between rate points (stragglers drain)")
    ap.add_argument("--warmup", type=int, default=2,
                    help="sequential requests before the sweep so jit "
                         "compilation doesn't pollute the first rate "
                         "point (0 disables)")
    ap.add_argument("--json-out", default=None, metavar="FILE",
                    help="write the run record (run_id + goodput curve; "
                         "docs/observability.md schema)")
    args = ap.parse_args(argv)

    modes = sum(map(bool, (args.url, args.self_serve, args.self_disagg)))
    if modes != 1:
        ap.error("pass exactly one of --url/--target, --self-serve, "
                 "or --self-disagg")
    srv = None
    fleet_close = None
    fleet_workers = None
    url = args.url
    vocab = args.vocab
    if args.self_serve:
        srv, url, model_vocab = self_serve(args)
        vocab = min(vocab, model_vocab)
    elif args.self_disagg:
        fleet_close, url, model_vocab, fleet_workers = self_disagg(args)
        vocab = min(vocab, model_vocab)
    pacer = None if args.pacer == "auto" else args.pacer
    # the load generator's target: every router replica when the fleet
    # has more than one (clients spread across them round-robin and
    # fail over on connect errors); `url` stays the primary replica for
    # the debug-endpoint gathering below
    urls = url
    if fleet_workers is not None and len(fleet_workers.get("router", ())) > 1:
        urls = [f"http://127.0.0.1:{r.port}"
                for r in fleet_workers["router"]]
    base = LoadConfig(
        rate=args.rates[0], n_requests=args.n, process=args.process,
        seed=args.seed, mix=args.mix, lanes=args.lanes,
        n_prefixes=args.prefixes, prefix_len=args.prefix_len,
        prefix_frac=args.prefix_frac, vocab=vocab,
        stream=not args.no_stream, timeout_s=args.timeout,
        honor_retry_after=args.honor_retry_after,
    )

    def show(point):
        lanes = "  ".join(
            f"lane {k}: ttft p50/p99 "
            f"{(v['ttft'] or {}).get('p50_ms', '-')}/"
            f"{(v['ttft'] or {}).get('p99_ms', '-')} ms"
            for k, v in point["lanes"].items()
        )
        print(
            f"# rate {point['offered_rate_rps']:>6.2f} rps  "
            f"completed {point['completed']}/{point['n']}  "
            f"rejected {point.get('rejected', 0)}  "
            f"goodput {point['goodput_rps']:.2f} rps  "
            f"attainment {point['slo_attainment']:.0%}  {lanes}",
            file=sys.stderr,
        )

    t0 = time.time()
    disagg = None
    try:
        if args.warmup:
            from dataclasses import replace

            from infinistore_tpu.loadgen import _http_post, make_requests

            for body in make_requests(
                replace(base, n_requests=args.warmup, seed=base.seed - 1)
            ):
                r = _http_post(url, body, args.timeout)
                if not r["ok"]:
                    print(f"# warmup request failed: {r['error']}",
                          file=sys.stderr)
        if args.conversation:
            # conversation sweep: open-loop SESSION arrivals per rate
            # point, each point summarized like a load point (same
            # lanes/goodput math over the per-turn results) PLUS the
            # per-turn contract numbers from session_summary
            from infinistore_tpu.loadgen import (SessionConfig,
                                                 run_sessions,
                                                 session_summary,
                                                 summarize)

            curve = []
            for i, rate in enumerate(args.rates):
                scfg = SessionConfig(
                    rate=float(rate), n_sessions=args.sessions,
                    process=args.process, seed=args.seed + i,
                    turns=args.turns, think_s=args.think,
                    system_prompt_len=args.system_prompt_len,
                    turn_tokens=args.turn_tokens,
                    max_tokens=args.conv_max_tokens, lanes=args.lanes,
                    vocab=vocab, stream=not args.no_stream,
                    timeout_s=args.timeout,
                )
                results, makespan = run_sessions(urls, scfg, pacer=pacer)
                point = summarize(results, makespan, args.slo_ttft,
                                  args.slo_tpot, rate=float(rate))
                point["sessions"] = session_summary(results)
                curve.append(point)
                show(point)
                if args.cooldown and rate != args.rates[-1]:
                    time.sleep(args.cooldown)
        else:
            curve = sweep(urls, base, args.rates, args.slo_ttft,
                          args.slo_tpot, cooldown_s=args.cooldown,
                          on_point=show, pacer=pacer)
        # the step profiler's summary for the whole sweep (best-effort:
        # older servers have no /debug/engine) — host-stall share,
        # retrace pressure, dispatch counts next to the goodput curve
        stepprof = None
        try:
            import urllib.request

            with urllib.request.urlopen(url + "/debug/engine?limit=0",
                                        timeout=5) as r:
                payload = json.loads(r.read())
            if payload.get("enabled"):
                stepprof = payload.get("summary")
        except Exception:  # noqa: BLE001 — observability, not the bench
            pass
        # the health plane's verdict on the run (best-effort, same
        # contract): alert firing transitions observed during the sweep
        # and the peak burn rate the watchdogs saw — a load point that
        # pages is a different result than one that merely misses SLO
        health = None
        try:
            import urllib.request

            with urllib.request.urlopen(url + "/debug/health",
                                        timeout=5) as r:
                payload = json.loads(r.read())
            if payload.get("enabled"):
                alerts = payload.get("alerts") or {}
                burn_peaks = [
                    a.get("peak") or 0.0 for name, a in alerts.items()
                    if name.endswith("_burn")
                ]
                health = {
                    "alerts_fired": payload.get("alerts_fired", 0),
                    "firing": payload.get("firing", []),
                    "burn_rate_peak": round(max(burn_peaks, default=0.0),
                                            3),
                    "alerts": {
                        name: {"fired": a.get("fired", 0),
                               "peak": a.get("peak")}
                        for name, a in alerts.items() if a.get("fired")
                    },
                }
        except Exception:  # noqa: BLE001 — observability, not the bench
            pass
        # the admission plane's verdict (best-effort, same contract):
        # server-side shed/quota tallies next to the client-observed
        # rejection counts below
        admission_dbg = None
        try:
            import urllib.request

            with urllib.request.urlopen(url + "/debug/admission",
                                        timeout=5) as r:
                payload = json.loads(r.read())
            if payload.get("enabled"):
                admission_dbg = payload
        except Exception:  # noqa: BLE001 — observability, not the bench
            pass
        # the usage ledger's verdict (best-effort, same contract):
        # per-tenant occupancy vs tokens-saved as /debug/usage joins it
        usage_dbg = None
        try:
            import urllib.request

            with urllib.request.urlopen(url + "/debug/usage",
                                        timeout=5) as r:
                payload = json.loads(r.read())
            if payload.get("enabled"):
                usage_dbg = payload
        except Exception:  # noqa: BLE001 — observability, not the bench
            pass
        # the session ledger's verdict (best-effort, same contract):
        # lifetime waste/computed totals from /debug/sessions — against
        # a fleet the decode workers hold the ledgers, so aggregate
        # their endpoints too; the front door itself answers the
        # affinity tallies via /debug/fleet
        sessions_dbg = []
        sess_targets = [url]
        for s in (fleet_workers or {}).get("decode", ()):
            sess_targets.append(f"http://127.0.0.1:{s.port}")
        for tgt in sess_targets:
            try:
                import urllib.request

                with urllib.request.urlopen(tgt + "/debug/sessions",
                                            timeout=5) as r:
                    payload = json.loads(r.read())
                if payload.get("enabled"):
                    sessions_dbg.append(payload)
            except Exception:  # noqa: BLE001 — observability, not the bench
                pass
        fleet_sessions = None
        try:
            import urllib.request

            with urllib.request.urlopen(url + "/debug/fleet",
                                        timeout=5) as r:
                payload = json.loads(r.read())
            if payload.get("enabled"):
                fleet_sessions = payload.get("sessions")
        except Exception:  # noqa: BLE001 — observability, not the bench
            pass
        # the reshape plane's verdict (best-effort, same contract):
        # if the store ring behind the server migrated during the run,
        # /debug/cluster carries the last migration's throughput
        cluster_dbg = None
        try:
            import urllib.request

            with urllib.request.urlopen(url + "/debug/cluster",
                                        timeout=5) as r:
                payload = json.loads(r.read())
            if payload.get("enabled"):
                cluster_dbg = payload
        except Exception:  # noqa: BLE001 — observability, not the bench
            pass
        # the stage ledger's verdict (best-effort, same contract): the
        # canonical TTFT decomposition at sweep end — /debug/critpath
        # answers worker-grain on a monolith and router-grain against a
        # fleet with the same shape, so two captures are diffable by
        # scripts/trace_diff.py either way
        critpath_dbg = None
        try:
            import urllib.request

            with urllib.request.urlopen(url + "/debug/critpath?limit=0",
                                        timeout=5) as r:
                payload = json.loads(r.read())
            if payload.get("enabled"):
                critpath_dbg = payload
        except Exception:  # noqa: BLE001 — observability, not the bench
            pass
        # the resumption plane's fleet-side half (best-effort, same
        # contract): the router-merged stream ledger ("did any stream
        # die?" — aborts + resumes summed across replicas) and the
        # decode workers' checkpoint-overhead counters
        fleet_merged = None
        try:
            import urllib.request

            with urllib.request.urlopen(url + "/debug/fleet?merged=1",
                                        timeout=5) as r:
                payload = json.loads(r.read())
            if payload.get("enabled"):
                fleet_merged = payload
        except Exception:  # noqa: BLE001 — observability, not the bench
            pass
        ckpt_writes = ckpt_tokens = 0.0
        ckpt_seen = False
        for s in (fleet_workers or {}).get("decode", ()):
            try:
                import urllib.request

                from infinistore_tpu.utils.metrics import \
                    parse_prometheus_text

                with urllib.request.urlopen(
                        f"http://127.0.0.1:{s.port}/metrics",
                        timeout=5) as r:
                    fams = parse_prometheus_text(r.read().decode())
            except Exception:  # noqa: BLE001
                continue
            for (name, _labels), v in fams.items():
                if name == "istpu_serve_resume_ckpt_writes_total":
                    ckpt_writes += v
                    ckpt_seen = True
                elif name == "istpu_serve_resume_ckpt_tokens_total":
                    ckpt_tokens += v
                    ckpt_seen = True
        disagg = None
        if args.self_disagg:
            disagg = _gather_disagg(url, fleet_workers, args)
    finally:
        if srv is not None:
            srv.close()
        if fleet_close is not None:
            fleet_close()
    # the same-budget monolith comparison: one server whose max_batch
    # equals the decode pool's total (equal decode throughput), swept on
    # the SAME schedule AFTER the fleet is torn down (fresh server, no
    # CPU contention between the two measurements)
    if disagg is not None and not args.no_monolith_baseline:
        import argparse as _argparse
        from dataclasses import replace

        from infinistore_tpu.loadgen import _http_post, make_requests

        mono_args = _argparse.Namespace(**{
            **vars(args),
            "self_serve_batch":
                args.self_serve_batch * max(1, args.decode_workers),
            "quotas": [],
        })
        msrv, murl, _mv = self_serve(mono_args)
        try:
            if args.warmup:
                for body in make_requests(
                    replace(base, n_requests=args.warmup,
                            seed=base.seed - 1)
                ):
                    _http_post(murl, body, args.timeout)
            mono_curve = sweep(murl, base, args.rates, args.slo_ttft,
                               args.slo_tpot, cooldown_s=args.cooldown)
        finally:
            msrv.close()
        top, mtop = curve[-1], mono_curve[-1]
        d_ttft = _lane_pct(top, "ttft", "p99_ms")
        m_ttft = _lane_pct(mtop, "ttft", "p99_ms")
        d_tpot = _lane_pct(top, "tpot", "p99_ms")
        m_tpot = _lane_pct(mtop, "tpot", "p99_ms")
        disagg["ttft_p99_ms"] = {"disagg": d_ttft, "monolith": m_ttft}
        disagg["tpot_p99_ms"] = {"disagg": d_tpot, "monolith": m_tpot}
        disagg["monolith_curve"] = mono_curve
        if d_ttft and m_ttft:
            disagg["ttft_ratio"] = round(d_ttft / m_ttft, 4)
        if d_tpot and m_tpot:
            disagg["tpot_burst_ratio"] = round(d_tpot / m_tpot, 4)
    record = {
        "run_id": uuid.uuid4().hex[:8],
        "kind": "serve_load",
        "slo": {"ttft_s": args.slo_ttft, "tpot_s": args.slo_tpot},
        "config": {
            "n_per_rate": args.n, "process": args.process,
            "mix": [list(m) for m in args.mix],
            "lanes": [list(p) for p in args.lanes],
            "prefixes": args.prefixes, "prefix_len": args.prefix_len,
            "prefix_frac": args.prefix_frac, "stream": not args.no_stream,
        },
        "wall_s": round(time.time() - t0, 1),
        "curve": curve,
    }
    if stepprof is not None:
        # profiler summary block (engine/stepprof.py): joins the schema
        # the same way `slo`/`config` do — stable keys, documented in
        # docs/observability.md §engine-attribution
        record["stepprof"] = stepprof
        # dispatch-economy mirrors, top-level: compiled programs per decoded
        # token over the whole sweep (down is good) and accepted spec
        # tokens per fused dispatch (up is good; absent when the server
        # never speculated)
        if stepprof.get("dispatches_per_token") is not None:
            record["dispatches_per_token"] = \
                stepprof["dispatches_per_token"]
        if stepprof.get("spec_accept_per_dispatch") is not None:
            record["spec_accept_per_dispatch"] = \
                stepprof["spec_accept_per_dispatch"]
    # admission block (docs/observability.md): shed counts per lane as
    # the CLIENT saw them (429s per priority lane), the server-side
    # shed/quota tallies when /debug/admission answered, and the
    # plateau flag — did goodput at the highest offered rate hold ≥50%
    # of the curve's peak (a plateau) instead of collapsing?
    per_lane_shed: dict = {}
    for pt in curve:
        for lane, v in pt["lanes"].items():
            per_lane_shed[lane] = (per_lane_shed.get(lane, 0)
                                   + (v.get("rejected") or 0))
    goodputs = [p["goodput_rps"] for p in curve]
    plateau = bool(len(goodputs) >= 2 and max(goodputs) > 0
                   and goodputs[-1] >= 0.5 * max(goodputs))
    record["admission"] = {
        "rejected_total": sum(p.get("rejected", 0) for p in curve),
        "per_lane_shed": per_lane_shed,
        "plateau": plateau,
    }
    if admission_dbg is not None:
        record["admission"]["server"] = {
            "mode": admission_dbg.get("mode"),
            "shed_total": admission_dbg.get("shed_total"),
            "shed_by_reason": admission_dbg.get("shed_by_reason"),
            "quota_throttled": (admission_dbg.get("quota")
                                or {}).get("throttled_total"),
        }
    # mirrored top-level (0/1): an overload round whose plateau flag
    # drops to 0 regressed
    record["goodput_plateau"] = int(plateau)
    # resumption block (docs/observability.md §Resumption): the
    # client-observed splice ledger over the whole sweep (resumed =
    # streams that crossed at least one splice, stalled = the same
    # requests as the client's stall accounting sees them, max_stall_ms
    # = the worst client-visible gap), the router-merged server-side
    # view when a fleet answered /debug/fleet?merged=1, and the decode
    # pool's checkpoint-overhead counters.  stream_resumes mirrors
    # top-level (direction: down — a quiet fleet resumes nothing)
    resumption = {
        "resumed": sum(p.get("resumed") or 0 for p in curve),
        "stalled": sum(p.get("stalled") or 0 for p in curve),
        "max_stall_ms": max(
            (p.get("max_stall_ms") for p in curve
             if p.get("max_stall_ms") is not None), default=None),
        "routers": args.routers if args.self_disagg else None,
    }
    if fleet_merged is not None:
        resumption["fleet"] = {
            "replicas": fleet_merged.get("replicas"),
            "reachable": fleet_merged.get("reachable"),
            "stream": fleet_merged.get("stream"),
        }
    if ckpt_seen:
        resumption["checkpoint"] = {
            "writes": ckpt_writes, "tokens": ckpt_tokens,
        }
    record["resumption"] = resumption
    record["stream_resumes"] = resumption["resumed"]
    if resumption["max_stall_ms"] is not None:
        record["max_stall_ms"] = resumption["max_stall_ms"]
    if args.conversation:
        # sessions block (docs/observability.md §Session attribution):
        # the persistence-contract numbers for the run — the fraction of
        # computed prompt tokens that were re-prefill waste (down is
        # good; a warm store holds it ~0), the session-affinity hit rate
        # among RE-visits (up is good; fallback is every session's first
        # placement, not a miss), and the client-observed per-turn TTFT
        # slope at the top offered rate — with the first two mirrored
        # top-level
        record["config"]["conversation"] = {
            "sessions_per_rate": args.sessions,
            "turns": [list(t) for t in args.turns],
            "turn_tokens": [list(t) for t in args.turn_tokens],
            "system_prompt_len": args.system_prompt_len,
            "think_s": list(args.think),
            "max_tokens": args.conv_max_tokens,
        }
        sess_block = {
            "per_turn": (curve[-1].get("sessions") or {}).get("per_turn"),
            "ttft_slope_ms_per_turn":
                (curve[-1].get("sessions") or {})
                .get("ttft_slope_ms_per_turn"),
        }
        if sessions_dbg:
            waste = sum((p.get("totals") or {}).get("waste_tokens", 0)
                        for p in sessions_dbg)
            computed = sum(
                (p.get("totals") or {}).get("computed_tokens", 0)
                for p in sessions_dbg)
            sess_block["waste_tokens"] = waste
            sess_block["computed_tokens"] = computed
            sess_block["reprefill_waste_frac"] = (
                round(waste / computed, 4) if computed else 0.0)
            record["reprefill_waste_frac"] = \
                sess_block["reprefill_waste_frac"]
        if fleet_sessions is not None:
            aff = fleet_sessions.get("affinity") or {}
            sess_block["affinity"] = aff
            revisits = (aff.get("hit") or 0) + (aff.get("miss") or 0)
            if revisits:
                sess_block["affinity_hit_rate"] = round(
                    (aff.get("hit") or 0) / revisits, 4)
                record["affinity_hit_rate"] = \
                    sess_block["affinity_hit_rate"]
        record["sessions"] = sess_block
    if disagg is not None:
        # disaggregation block (docs/observability.md): per-role worker
        # counts, handoff leg percentiles, decode-pool adoption hit
        # rate, and the TTFT/TPOT-vs-monolith ratios at the top offered
        # rate — the headline ratios mirror top-level (direction: down;
        # < 1.0 means the fleet beat the same-decode-budget monolith)
        record["disagg"] = disagg
        if disagg.get("ttft_ratio") is not None:
            record["ttft_ratio"] = disagg["ttft_ratio"]
        if disagg.get("tpot_burst_ratio") is not None:
            record["tpot_burst_ratio"] = disagg["tpot_burst_ratio"]
    if usage_dbg is not None:
        # usage block (docs/observability.md §Usage attribution): the
        # per-tenant ledger at sweep end — occupancy byte·seconds, token
        # provenance, economics — with the fleet-wide reuse ratio
        # mirrored top-level (up is good: more prompt tokens served from
        # the store per byte held)
        tenants = usage_dbg.get("tenants") or {}
        tok_store = sum((t.get("tokens") or {}).get("store", 0.0)
                       for t in tenants.values())
        tok_all = sum(sum((t.get("tokens") or {}).values())
                      for t in tenants.values())
        record["usage"] = {
            "tenants": tenants,
            "top_occupants": usage_dbg.get("top_occupants"),
            "top_savers": usage_dbg.get("top_savers"),
            "doa_offenders": usage_dbg.get("doa_offenders"),
            "nodes": usage_dbg.get("nodes"),
        }
        if tok_all:
            record["usage_reuse_ratio"] = round(tok_store / tok_all, 4)
    if health is not None:
        # health-plane block (infinistore_tpu/health.py): alert
        # transitions + burn-rate peak during the run.  alerts_fired is
        # ALSO mirrored top-level (direction: down), so that a reader
        # need not dig into nested blocks
        record["health"] = health
        record["alerts_fired"] = health["alerts_fired"]
        record["burn_rate_peak"] = health["burn_rate_peak"]
    if cluster_dbg is not None:
        # reshape throughput mirrored top-level (up is good) — only when
        # a migration actually ran: a sweep with no membership change
        # emits no row
        mig = cluster_dbg.get("migration") or {}
        if mig.get("migrate_gbps") is not None:
            record["migrate_gbps"] = mig["migrate_gbps"]
    if critpath_dbg is not None:
        # critpath block (docs/observability.md §Latency attribution):
        # the per-stage TTFT decomposition at sweep end, row tail
        # dropped (the aggregates are the diffable artifact).  Each
        # stage's p99 mirrors top-level as stage_p99_<stage>_ms so
        # scripts/trace_diff.py names a regressed stage from two of
        # these captures
        overall = critpath_dbg.get("overall") or {}
        record["critpath"] = {
            "role": critpath_dbg.get("role"),
            "stages": critpath_dbg.get("stages"),
            "overall": overall,
            "lanes": critpath_dbg.get("lanes"),
        }
        for s, v in (overall.get("stage_p99_ms") or {}).items():
            record[f"stage_p99_{s}_ms"] = v
    print(json.dumps(record))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
