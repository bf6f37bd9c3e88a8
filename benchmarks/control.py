#!/usr/bin/env python3
"""The output check's controls: what `correct` must refuse (PERF.md section 2).

    python3 benchmarks/control.py --workload <cell> --kind kv-int8 --seeds 1,2,3
    python3 benchmarks/control.py --workload <cell> --kind ref-int8 --seeds 1

``kv-int8`` is the program's own lower-precision path: the server started
with ``--kv-quant int8``, so pages go to the store and come back in int8.  One
server answers the paired probes of every seed in ``--seeds``: each prompt
computed, asked again at once (pages in HBM), and asked again after a fill has
pushed it out of HBM (pages from the store).  The statistic run.py holds to
the configuration's ``pair_logprob_max_abs_limit`` is printed per seed.
``--kind none`` reads the same statistic from a sound server.

``ref-int8`` puts the configuration's plain reference, recomputed in int8
(W8A8), in the program's place and holds it to the float32 reference as run.py
holds the server: the RMS that ``logprob_rms_limit`` must refuse.  A family
whose reference names another precision below the one its configuration states
asks for it by that name (``ref-<precision>``).

No timed window, no result line.  ``--rehearse 1`` walks it on the CPU with
the configuration's toy (kept as a test under ``tests/``).  The reference and
the counts are found as run.py finds them (``harness/family.py``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

import run
from run import client, say


async def pairs(port: int, c: dict, plans: list) -> list:
    local = [await run.pair_first_asks(port, p) for p in plans]
    run.must_ok(await client.gather_posts(
        port, plans[0]["fill"], min(8, c["cell"]["max_batch"])), "fill")
    out = []
    for plan, loc in zip(plans, local):
        rows, ledger = await run.ask_probes(port, [p["body"] for p in plan["probes"]])
        out.append(run.pair_check(plan, {"pair_local": loc, "probe_rows": rows,
                                         "probe_ledger": ledger}))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kind", required=True,
                    help="kv-int8, none, or ref-<precision> (ref-int8 for the dense reference)")
    ap.add_argument("--seeds", required=True, help="comma separated")
    ap.add_argument("--rehearse", type=int, choices=[0, 1], default=0)
    args = ap.parse_args()
    if args.kind not in ("kv-int8", "none") and not args.kind.startswith("ref-"):
        ap.error(f"--kind {args.kind}: kv-int8, none or ref-<precision>")
    seeds = [int(s) for s in args.seeds.split(",")]
    c = run.load_cell(args.workload, args.rehearse)
    run_dir = run.make_run_dir(c, f"s{seeds[0]}.control-{args.kind}")
    plans = [c["generate"](s, 1.0) for s in seeds]
    for p in plans:
        p["schedule"] = []
    for p in plans[1:]:                 # one fill and no warm-up serve them all
        p["fill"], p["warm_decode"] = [], []
    try:
        if args.kind.startswith("ref-"):
            low = args.kind[len("ref-"):]
            with run.servers(c, run_dir, seeds[0], run.pool_gib(c, plans[:1]),
                             control=args.kind) as up:
                rows, _ = asyncio.run(run.ask_probes(
                    up["port"], [p["body"] for p in plans[0]["probes"]]))
                chk = run.stop_and_check(up, run_dir, run.answers(plans[0]["probes"], rows))
            say(f"seed {seeds[0]}: sound RMS {chk['f32']['rms']}; control ({low} reference "
                f"{chk['reference']} in the program's place) RMS "
                f"{chk[f'control_ref_{low}']['rms']} "
                f"max_abs {chk[f'control_ref_{low}']['max_abs']}")
            return 0
        kv = "int8" if args.kind == "kv-int8" else "none"
        with run.servers(c, run_dir, seeds[0], run.pool_gib(c, plans), kv_quant=kv) as up:
            got = asyncio.run(pairs(up["port"], c, plans))
        for seed, g in zip(seeds, got):
            say(f"kv-quant {kv} seed {seed}: {json.dumps(g)}")
    except run.RunFailure as e:
        say(f"FAILED: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
