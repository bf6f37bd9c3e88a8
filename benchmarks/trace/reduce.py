"""From a profiler trace to numbers: device busy and idle time, time per
jitted program, the device operations that took most time, and the longest
idle gaps by what the host was doing.

Two steps, so that the second can be tested on a small recorded trace:
``load_xplane`` turns an ``.xplane.pb`` into plain lists (needs JAX's
``ProfileData``), ``reduce`` turns those into numbers (pure Python).

What a v5e trace holds (looked at by hand, PR 24): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Modules`` has one event per executed
program, named ``jit_<function>(<fingerprint>)``, and whose line ``XLA Ops``
has one event per device operation; host threads are lines of the plane
``/host:CPU``.  ``programs.json`` maps program names to the classes the
readers ask for; a family whose programs have other names adds a file of the
same form under ``programs.d/`` (``load_table``).  A program in flight when the profiler starts or stops is
recorded cut: it begins at the trace's first instant or ends at its last, and
is shorter than it ran.  Such events count as busy time but not as
executions of a program: a time per execution divides whole runs only.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
MODULES, OPS = "XLA Modules", "XLA Ops"
MIN_GAP_S = 20e-6     # shorter gaps are launch latency, not host work
EDGE_NS = 100_000     # an event this close to the trace's edge was cut by it


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load_xplane(path: str, max_events_per_line: int = 0) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]} for the device planes and the host plane."""
    from jax.profiler import ProfileData

    out = {"planes": []}
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith("/device:") or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
            if max_events_per_line:
                evs = evs[:max_events_per_line]
            if evs:
                lines.append({"name": line.name, "events": evs})
        out["planes"].append({"name": plane.name, "lines": lines})
    return out


def union_s(intervals: List[Tuple[int, int]]) -> Tuple[float, List[Tuple[int, int]]]:
    """Length in seconds of the union of [start, end) ns intervals, and the
    merged intervals."""
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged) / 1e9, [tuple(m) for m in merged]


def program_class(names: List[str], i: int, table: dict) -> str:
    """The class of the ``i``-th program of a device's line.  A pattern is a
    regular expression on the name, or {"match", "followed_by", "within"}: the
    name matches and one of the next ``within`` programs matches
    ``followed_by`` (the engine's jitted partials are all called
    ``jit__unknown``; what runs next tells them apart)."""
    for cls, patterns in table.items():
        for p in patterns:
            if isinstance(p, str):
                if re.search(p, names[i]):
                    return cls
            elif re.search(p["match"], names[i]) and any(
                    re.search(p["followed_by"], n)
                    for n in names[i + 1:i + 1 + p["within"]]):
                return cls
    return "other"


WRAPPERS = ("%while", "%conditional", "%call")


def op_label(name: str) -> str:
    """'%fusion.12 = bf16[8,128]{...} fusion(...)' -> '%fusion.12 bf16[8,128]'."""
    m = re.match(r"(%[\w.\-]+) = \(?(\w+\[[\d,]*\])", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def strip_id(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def load_table(here: str = HERE) -> dict:
    """``programs.json``, then every ``programs.d/*.json`` in the order of
    their names: each adds patterns to the classes (or a class); none takes a
    pattern away."""
    table: Dict[str, list] = {}
    for path in [os.path.join(here, "programs.json")] + sorted(
            glob.glob(os.path.join(here, "programs.d", "*.json"))):
        with open(path) as f:
            for cls, patterns in json.load(f).items():
                table.setdefault(cls, []).extend(patterns)
    return table


def reduce(trace: dict, table: dict = None) -> dict:
    if table is None:
        table = load_table()
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:TPU")]
    if not devices:
        raise ValueError("the trace has no /device:TPU plane: nothing ran on "
                         "a chip while it was taken")
    hosts = [p for p in trace["planes"] if p["name"].startswith("/host:")]
    every = [(s, s + d) for p in trace["planes"] for ln in p["lines"]
             for _, s, d in ln["events"]]
    t0, t1 = min(a for a, _ in every), max(b for _, b in every)
    window_s = (t1 - t0) / 1e9

    busy, programs, ops = [], defaultdict(lambda: [0, 0.0]), defaultdict(float)
    classes, cut = defaultdict(lambda: [0, 0.0]), [0, 0.0]
    gaps_by_host: Dict[str, float] = defaultdict(float)
    host_events = sorted((s, s + d, n) for p in hosts for ln in p["lines"]
                         for n, s, d in ln["events"] if d > 0)
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = lines.get(OPS) or lines.get(MODULES) or []
        b, _ = union_s([(s, s + d) for _, s, d in op_events])
        busy.append(b)
        for n, _, d in lines.get(OPS, []):
            if not n.startswith(WRAPPERS):      # a loop's own event spans its body
                ops[op_label(n)] += d / 1e9
        mods = lines.get(MODULES, [])
        names = [n for n, _, _ in mods]
        for i, (n, s, d) in enumerate(mods):
            whole = s - t0 > EDGE_NS and t1 - (s + d) > EDGE_NS
            for c in ((programs[strip_id(n)], classes[program_class(names, i, table)])
                      if whole else (cut,)):
                c[0] += 1
                c[1] += d / 1e9
        if plane is devices[0]:
            _, merged = union_s([(s, s + d) for _, s, d in lines.get(MODULES, op_events)])
            edges = [(t0, t0)] + merged + [(t1, t1)]
            for (_, a), (b_, _) in zip(edges, edges[1:]):
                if (b_ - a) / 1e9 >= MIN_GAP_S:
                    gaps_by_host[_host_doing(host_events, a, b_)] += (b_ - a) / 1e9
    busy_s = sum(busy) / len(busy)
    if busy_s <= 0:
        raise ValueError("no operation ran on the device inside the trace")
    top = lambda d, n=10: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:n]
    return {
        "window_s": window_s, "busy_s": busy_s, "n_devices": len(devices),
        "idle_share_pct": 100.0 * (1.0 - busy_s / window_s),
        "programs": {k: {"count": c, "dur_s": s} for k, (c, s) in programs.items()},
        "classes": {k: {"count": c, "dur_s": s} for k, (c, s) in classes.items()},
        "cut_by_the_edges": {"count": cut[0], "dur_s": cut[1]},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps_by_host)},
    }


def _host_doing(host_events, a: int, b: int) -> str:
    """The host event that covers most of the device's idle gap [a, b)."""
    best, best_ov = "nothing traced on the host", 0
    for s, e, n in host_events:
        if s >= b:
            break
        ov = min(e, b) - max(s, a)
        if ov > best_ov:
            best, best_ov = strip_id(n), ov
    return best


def describe(trace: dict, n: int = 12) -> str:
    """What a person looks at first: planes, lines, event counts, top names."""
    out = []
    for p in trace["planes"]:
        out.append(f"plane {p['name']}")
        for ln in p["lines"]:
            names = defaultdict(float)
            for nm, _, d in ln["events"]:
                names[strip_id(nm)] += d / 1e9
            topn = sorted(names.items(), key=lambda kv: -kv[1])[:n]
            out.append(f"  line {ln['name']!r}: {len(ln['events'])} events; "
                       + "; ".join(f"{k}={v:.4f}s" for k, v in topn))
    return "\n".join(out)
