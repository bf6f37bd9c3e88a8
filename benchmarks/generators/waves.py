"""``mix.py``'s traffic with a decode warm-up that does not lean on timing.

``mix.generate`` warms each (batch bucket, table width) with a phase of k
requests that run.py sends 0.15 s behind a short blocker, so that all k are
pending when the blocker's one decode dispatch ends and are admitted as one
wave.  That holds where a dispatch outlasts the lead (350 ms a dispatch in
the dense cells).  Where a 32-step dispatch takes 85 ms the blocker is gone
before the phase arrives, the k requests are admitted as they trickle in,
and which batch buckets the warm-up compiles varies from run to run: the
missing program is then compiled inside the window (PERF.md section 6, PR
30: 5 of 13 runs, `tpot_p50_ms` 3.22 against 2.62).

Here a phase is ONE request with ``"n": k`` (not streamed): the server
submits its k choices back to back from one handler, so they are pending
together whatever the clock says, share the prompt's pages and decode
together at batch k.  Everything else is ``mix.generate``'s, unchanged: the
same sizes at the same times, the same probes, fill and schedule.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mix  # noqa: E402


def generate(traffic: dict, cell: dict, config: dict, seed: int,
             seconds: float, schedule_salt: str = "") -> dict:
    plan = mix.generate(traffic, cell, config, seed, seconds, schedule_salt)
    for phase in plan["warm_decode"]:
        k = len(phase["requests"])
        phase["requests"] = [dict(phase["requests"][0], n=k, stream=False)]
    return plan
