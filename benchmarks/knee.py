#!/usr/bin/env python3
"""The knee of an open-loop cell, found once when the cell is defined:

    python3 benchmarks/knee.py --workload <cell> --seed <n> --seconds 25 --rates 1,2,3,4,5

One set-up (run.py's own phases), then a window at each rate.  Each line
gives the tails and whether the backlog grew: requests in flight at the
window's middle and at its end.  The knee is the highest rate at which it did
not grow; the cell's file then offers 0.8 of it.  Prints no result line: a run
never searches for a rate.
"""

from __future__ import annotations

import argparse
import asyncio
import sys

import run
from run import client, say, stats


async def sweep(port: int, c: dict, plan: dict, seed: int, seconds: float, rates: list):
    await run.set_up_traffic(port, plan, c["cell"])
    say("sweep: rate sent done_in_window inflight_mid inflight_end "
        "ttft_p50_ms ttft_p90_ms tpot_p90_ms late_max_ms failed")
    for i, rate in enumerate(rates):
        c["cell"]["rate"] = rate
        sched = c["generate"](seed, seconds, schedule_salt=f"sweep{i}")["schedule"]
        t0 = client.clock() + 0.25
        w0, w1 = t0 + plan["ramp_s"], t0 + plan["ramp_s"] + seconds
        rows = await client.open_loop(port, sched, t0)
        inflight = lambda t: sum(1 for r in rows if r["t_due"] <= t < r["t_done"])
        m = [r for r in rows if r["t_due"] >= w0]
        e = stats.end_to_end(m, w0, w1)
        say(f"sweep: {rate:g} {len(rows)} "
            f"{sum(1 for r in m if r['t_done'] < w1)} {inflight((w0 + w1) / 2)} "
            f"{inflight(w1)} {e.get('ttft_p50_ms', 0):.0f} {e.get('ttft_p90_ms', 0):.0f} "
            f"{e.get('tpot_p90_ms', 0):.1f} {max(r['late_s'] for r in rows) * 1e3:.1f} "
            f"{sum(1 for r in rows if not r['ok'])}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="req/s, comma separated")
    ap.add_argument("--rehearse", type=int, choices=[0, 1], default=0)
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    c = run.load_cell(args.workload, args.rehearse)
    run_dir = run.make_run_dir(c, f"s{args.seed}.knee")
    c["cell"]["rate"] = max(rates)            # the pool holds the fastest window
    plan = c["generate"](args.seed, args.seconds)
    pool = run.pool_gib(c, [plan] * len(rates))
    try:
        with run.servers(c, run_dir, args.seed, pool) as up:
            asyncio.run(sweep(up["port"], c, plan, args.seed, args.seconds, rates))
    except run.RunFailure as e:
        say(f"FAILED: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
