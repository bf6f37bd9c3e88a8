#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process every time.  This parent never imports JAX (a chip belongs to
one process): it starts the store and ``benchmarks/serve_proc.py`` (the
serving server under a launcher), generates the cell's traffic from the seed,
warms the cell's own shapes, fills the store, sends the probes, measures for
``--seconds`` at the client over HTTP/SSE, stops the server, lets the launcher
check the probes against the plain reference, and prints one JSON object as
the last line of its output.  Everything else it learns goes on earlier lines
and into ``chiprun_out/bench/<run>/``.

Which files it reads is decided by names in ``BENCHMARK.json`` alone: the
cell's file under ``workloads/``, its configuration under ``configs/``, its
mix under ``traffic/`` (read by the generator the mix names, under
``generators/``), and each per-layer metric's file under ``metrics/`` with its
reader under ``readers/``.  What depends on the model's family (the reference,
the counts, the server's model file, the rehearsal's toy) is named by the
configuration's own file and found by ``harness/family.py``.  A new cell, mix,
metric or family is new files.

``--rehearse 1`` walks the same sequence on the CPU with the toy configuration
the cell's configuration names (``rehearse``; the tiny preset by default), to
debug the harness off the chip.  It says ``platform: cpu``, prints no result
line and exits 3: a CPU number is never written under a device metric's name.
The knee sweep and the check's controls are tools of their own beside this
file (``knee.py``, ``control.py``) that borrow its phases.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import glob
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(HERE, "harness")]

import client  # noqa: E402
import costs  # noqa: E402
import family  # noqa: E402
import promtext  # noqa: E402
import stats  # noqa: E402

T0 = time.perf_counter()
CHILDREN: list = []
TRACE_SECONDS = 10.0    # eight or so decode dispatches; the raw trace is deleted
BLOCKER_LEAD_S = 0.15   # the blocker's step is in flight before its phase is sent
HEARTBEAT_S = 0.05


class RunFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T0:7.2f}s] {msg}", flush=True)


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start(name: str, argv: list, env: dict, run_dir: str) -> subprocess.Popen:
    with open(os.path.join(run_dir, f"{name}.argv.json"), "w") as f:
        json.dump(argv, f)
    with open(os.path.join(run_dir, f"{name}.log"), "w") as f:
        proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=f,
                                stderr=subprocess.STDOUT)
    CHILDREN.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace_s: float) -> None:
    """SIGTERM and wait until the process is gone (the chip is held until
    then); SIGKILL past the grace period."""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def log_tail(run_dir: str, name: str, n: int = 25) -> str:
    with open(os.path.join(run_dir, f"{name}.log"), errors="replace") as f:
        return "".join([ln for ln in f if not ln.startswith("DEBUG:")][-n:])


def wait_for(what: str, ready, procs: dict, run_dir: str, timeout_s: float):
    """Poll ``ready()`` until it returns something; a watched child that
    exits first fails the run with its log."""
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        for name, p in procs.items():
            if p.poll() is not None:
                raise RunFailure(f"{name} exited with {p.returncode} while "
                                 f"waiting for {what}:\n{log_tail(run_dir, name)}")
        got = ready()
        if got:
            return got
        time.sleep(0.25)
    raise RunFailure(f"{what}: not within {timeout_s:.0f}s")


def rehearsal_scale(traffic: dict, cell: dict) -> None:
    """Lengths / 8, at most 8 in flight: a toy configuration's sizes."""
    def grid(g):
        return {str(max(8, int(k) // 8)): w for k, w in g.items()}
    traffic["tails"] = grid(traffic["tails"])
    if traffic.get("documents"):
        traffic["documents"]["lengths"] = grid(traffic["documents"]["lengths"])
    for p in traffic["probes"]:
        for k in ("doc", "tail"):
            if k in p:
                p[k] = max(8, p[k] // 8)
    cell["max_batch"] = min(cell["max_batch"], 8)
    if "clients" in cell:
        cell["clients"] = min(cell["clients"], 8)


# -- a cell's files, its traffic, its two processes ------------------------------------

def load_cell(workload: str, rehearse: int) -> dict:
    manifest = load_json(ROOT, "BENCHMARK.json")
    wl = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == wl["config"])
    cell = load_json(HERE, "workloads", f"{wl['name']}.json")
    traffic = load_json(HERE, "traffic", f"{wl['traffic']}.json")
    config_file = os.path.join(ROOT, cfg_entry["file"])
    config = load_json(config_file)
    if rehearse:
        config_file = family.rehearsal_file(config)
        config = load_json(config_file)
        rehearsal_scale(traffic, cell)
    gen = family.load_module(os.path.join(HERE, "generators", f"{traffic['generator']}.py"))
    return {"manifest": manifest, "wl": wl, "cell": cell, "traffic": traffic,
            "config": config, "config_file": config_file, "rehearse": rehearse,
            "counts": family.counts(config),
            "generate": lambda seed, seconds, **kw: gen.generate(
                traffic, cell, config, seed, seconds, **kw)}


def make_run_dir(c: dict, tag: str) -> str:
    run_dir = os.path.join(ROOT, "chiprun_out", "bench", f"{c['wl']['name']}.{tag}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    return run_dir


def pool_gib(c: dict, plans: list) -> int:
    """The store pool: every token the run pushes, plus the store's overhead."""
    config = c["config"]
    block = config["serve"]["block_tokens"]
    kv_tok = (c["counts"].store_page_bytes(config, block)
              * config["num_hidden_layers"] // block)
    pushed = 0
    for plan in plans:
        pushed += sum(len(b["prompt"]) for b in plan["fill"]) + sum(
            len(p["body"]["prompt"]) for p in plan["probes"]) + sum(
            len(ph["requests"][0]["prompt"]) for ph in plan["warm_decode"])
        if not plan["closed"]:     # a closed loop's requests are unshared: capped below
            pushed += sum(len(s["body"]["prompt"]) if s["kind"] != "reask" else 256
                          for s in plan["schedule"])
    gib = math.ceil(pushed * kv_tok * 1.15 / 2**30) + 1
    if not c["traffic"].get("documents"):
        gib = min(gib, 8)      # nothing is read back: old pages may go
    say(f"store pool {gib} GiB for about {pushed} pushed tokens ({kv_tok} B each)")
    return gib


@contextlib.contextmanager
def servers(c: dict, run_dir: str, seed: int, pool: int, *, kv_quant: str = "none",
            control: str = "none"):
    """The store and the serving server, up and healthy; both gone on the way
    out.  Yields {"port", "serve", "store", "device"}."""
    config, cell, wl = c["config"], c["cell"], c["wl"]
    serve_cfg = config["serve"]
    weight_seed = seed % (2**31 - 1)
    model_file = os.path.join(run_dir, "model.json")
    with open(model_file, "w") as f:
        json.dump(family.model_file(config, weight_seed), f)
    env = dict(os.environ, PYTHONUNBUFFERED="1", ISTPU_CLIENT="python",
               # every program into the persistent cache, not only those that
               # took over a second to compile (PR 21: 123 of 140 did not)
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               TPU_LOG_DIR=os.environ.get("TPU_LOG_DIR", "disabled"))
    if c["rehearse"]:
        env["JAX_PLATFORMS"] = "cpu"
    shm_free = shutil.disk_usage("/dev/shm").free
    if shm_free < (pool + 1) << 30:
        raise RunFailure(f"/dev/shm has {shm_free} bytes free, the store pool needs "
                         f"{pool} GiB: not shrinking the population")
    page_kb = max(16, c["counts"].store_page_bytes(
        config, serve_cfg["block_tokens"]) // 1024)
    svc, mng, port = free_port(), free_port(), free_port()
    shm_prefix = f"istpu_bench_{os.getpid()}"
    try:
        # the python backend: no ignored binary decides the path
        store = start("store", [
            sys.executable, "-m", "infinistore_tpu.server", "--backend", "python",
            "--host", "127.0.0.1", "--service-port", str(svc),
            "--manage-port", str(mng), "--prealloc-size", str(pool),
            "--minimal-allocate-size", str(page_kb),
            "--shm-prefix", shm_prefix], env, run_dir)
        serve_args = list(serve_cfg["args"])
        serve_args[serve_args.index("--kv-quant") + 1] = kv_quant
        serve = start("serve", [
            sys.executable, os.path.join(HERE, "serve_proc.py"),
            "--run-dir", run_dir, "--config", c["config_file"],
            "--seed", str(weight_seed), "--chips", str(wl["chips"]),
            "--rehearse", str(c["rehearse"]), "--control", control, "--",
            "--model", model_file, "--port", str(port),
            "--n-blocks", str(serve_cfg["n_blocks"]),
            "--block-tokens", str(serve_cfg["block_tokens"]),
            "--max-batch", str(cell["max_batch"]), "--ledger-ring", "8192",
            "--store-host", "127.0.0.1", "--store-service-port", str(svc),
            *serve_args], env, run_dir)
        dev_f = os.path.join(run_dir, "device.json")
        device = wait_for("the device", lambda: os.path.exists(dev_f)
                          and load_json(dev_f), {"serve": serve}, run_dir, 300)
        say(f"platform: {device['platform']} device_kind: {device['kind']} "
            f"count: {device['count']} bytes_limit: {device['bytes_limit']} "
            f"fill: {device.get('fill')}")

        def healthy():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=2) as r:
                    return json.loads(r.read())
            except (OSError, ValueError):
                return None

        wait_for("serve /healthz", healthy, {"serve": serve, "store": store},
                 run_dir, 900)
        say("server healthy")
        yield {"port": port, "serve": serve, "store": store, "device": device}
    finally:
        for proc in reversed(CHILDREN):
            stop(proc, 15)
        for seg in glob.glob(f"/dev/shm/{shm_prefix}*"):
            os.unlink(seg)


def stop_and_check(up: dict, run_dir: str, probes: list) -> dict:
    """Hand the probes to the launcher, stop the server, let it compare them
    with the reference (and reduce the trace); returns check.json."""
    with open(os.path.join(run_dir, "probes.json"), "w") as f:
        json.dump(probes, f)
    t_stop = time.perf_counter()
    up["serve"].send_signal(signal.SIGTERM)
    try:
        up["serve"].wait(timeout=300)
    except subprocess.TimeoutExpired:
        raise RunFailure(f"serve_proc did not finish its checks:\n"
                         f"{log_tail(run_dir, 'serve')}")
    if up["serve"].returncode != 0:
        raise RunFailure(f"serve_proc exited with {up['serve'].returncode}:\n"
                         f"{log_tail(run_dir, 'serve')}")
    say(f"server stopped and outputs checked in {time.perf_counter() - t_stop:.1f}s")
    stop(up["store"], 20)
    return load_json(run_dir, "check.json")


# -- the phases ----------------------------------------------------------------

def must_ok(rows: list, what: str) -> list:
    bad = [r for r in rows if not r["ok"]]
    if bad:
        raise RunFailure(f"{what}: {len(bad)} of {len(rows)} requests failed, "
                         f"first: status {bad[0]['status']} tokens "
                         f"{bad[0]['tokens']}/{bad[0]['asked']} {bad[0]['error']}")
    return rows


async def scrape(port: int) -> dict:
    m = promtext.parse((await client.get(port, "/metrics")).decode())
    eng = json.loads(await client.get(port, "/debug/engine?limit=0"))["summary"]
    return {"prefix": promtext.family(m, "istpu_engine_prefix_tokens_total"),
            "dropped": promtext.family(m, "istpu_store_push_dropped_total"),
            "degraded": promtext.family(m, "istpu_store_degraded_ops_total"),
            "engine": eng, "t": client.clock(), "wall": time.time()}


async def ask_probes(port: int, bodies: list) -> tuple:
    """One at a time: batch one, the same arithmetic in every run.  Returns
    the client rows and the server's ledger rows of the same requests."""
    rows = []
    if not bodies:
        return [], []
    for b in bodies:
        rows += must_ok([await client.post(port, b)], "probe")
    ledger = json.loads(await client.get(port, "/debug/requests?limit=256"))["records"]
    return rows, [r for r in ledger if r.get("max_new_tokens") == 4][-len(rows):]


async def pair_first_asks(port: int, plan: dict) -> dict:
    """Each re-ask probe's prompt asked twice in a row: computed (and pushed
    to the store), then served from the pages still in HBM."""
    idx = [i for i, p in enumerate(plan["probes"]) if p["reask"]]
    bodies = [plan["probes"][i]["body"] for i in idx for _ in (0, 1)]
    rows, ledger = await ask_probes(port, bodies)
    return {"index": idx, "rows": rows[1::2], "ledger": ledger[1::2]}


async def set_up_traffic(port: int, plan: dict, cell: dict) -> dict:
    out: dict = {}
    # 1. decode warm-up: a blocker holds the engine for one dispatch while
    #    the phase's k requests queue; the batch empties, they are admitted
    #    as one wave and decode together at batch k
    for ph in plan["warm_decode"]:
        blk = asyncio.ensure_future(client.post(port, plan["blocker"]))
        await asyncio.sleep(BLOCKER_LEAD_S)
        rows = await asyncio.gather(*(client.post(port, b) for b in ph["requests"]))
        must_ok(list(rows) + [await blk], ph["name"])
    s = await scrape(port)
    say(f"decode warm-up: {len(plan['warm_decode'])} phases, compiles so far "
        f"{s['engine']['compiles']} ({s['engine']['compile_s']:.1f}s)")
    # 2. the paired probes' first asks, while their pages are in HBM
    out["pair_local"] = await pair_first_asks(port, plan)
    # 3. fill: every other document asked once / one prompt of each length
    must_ok(await client.gather_posts(port, plan["fill"], min(8, cell["max_batch"])),
            "fill")
    say(f"fill: {len(plan['fill'])} requests after "
        f"{len(out['pair_local']['index'])} paired probes")
    # 4. the probes; the paired ones now come back from the store
    out["probe_rows"], out["probe_ledger"] = await ask_probes(
        port, [p["body"] for p in plan["probes"]])
    # 5. re-ask shapes
    if plan["warm_reask"]:
        must_ok(await client.gather_posts(port, plan["warm_reask"], 4), "re-ask warm-up")
    out["before_load"] = s_a = await scrape(port)
    say(f"set-up traffic done: compiles {s_a['engine']['compiles']} "
        f"({s_a['engine']['compile_s']:.1f}s), prefix {s_a['prefix']}")
    return out


async def heartbeat(until: float, out: dict) -> None:
    """The longest the client's own loop went unserved.  Seconds here mean the
    machine froze (PR 24 saw 6.9 s with the server's stall of 10.1 s), not
    that the server was slow."""
    last = client.clock()
    while last < until:
        await asyncio.sleep(HEARTBEAT_S)
        now = client.clock()
        out["max_gap_s"] = max(out.get("max_gap_s", 0.0), now - last - HEARTBEAT_S)
        last = now


async def window(port: int, plan: dict, cell: dict, seconds: float, trace: int,
                 run_dir: str) -> dict:
    """Ramp + the measured window."""
    ramp = plan["ramp_s"]
    t0 = client.clock() + 0.25
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    marks: dict = {}
    beat: dict = {}

    async def at(t: float, name: str, fn):
        await asyncio.sleep(max(0.0, t - client.clock()))
        marks[name] = await fn()

    async def touch(name: str):
        open(os.path.join(run_dir, name), "w").close()
        return client.clock()

    side = [at(w0, "before", lambda: scrape(port)), heartbeat(w1, beat)]
    if trace:
        t_tr = w0 + 0.4 * seconds
        side += [at(t_tr, "trace_on", lambda: touch("ctl_trace_start")),
                 at(t_tr + min(TRACE_SECONDS, seconds / 3), "trace_off",
                    lambda: touch("ctl_trace_stop"))]
    side_task = asyncio.gather(*side)
    await asyncio.sleep(max(0.0, t0 - client.clock()))
    if plan["closed"]:
        rows = await client.closed_loop(port, plan["schedule"], cell["clients"], w1)
    else:
        rows = await client.open_loop(port, plan["schedule"], t0)
    await side_task
    s_c = await scrape(port)
    ledger = json.loads(await client.get(port, "/debug/requests?limit=100000"))
    health = json.loads(await client.get(port, "/healthz"))
    return dict(rows=rows, w0=w0, w1=w1, t0=t0, before=marks["before"], after=s_c,
                health=health, client_gap_s=beat.get("max_gap_s", 0.0),
                trace_span=((marks["trace_on"], marks["trace_off"]) if trace else None),
                server_rows=[r for r in ledger["records"]
                             if r.get("wall_done", 0) >= marks["before"]["wall"]])


def answers(plan_probes: list, rows: list) -> list:
    out = []
    for p, row in zip(plan_probes, rows):
        ch = row["payload"]["choices"][0]
        out.append({"prompt": p["body"]["prompt"], "ids": ch["token_ids"],
                    "top": ch["logprobs"]["top_logprobs"]})
    return out


def pair_check(plan: dict, res: dict) -> dict:
    """The paired probes: the answer from pages in HBM against the answer from
    the same pages come back from the store.  Returns the statistic and how
    many pairs were formed as meant (the first wholly local, the second
    reading the store, both reusing the same number of pages)."""
    local, formed, diffs = res["pair_local"], 0, []
    for i, row_l, rec_l in zip(local["index"], local["rows"], local["ledger"]):
        led_l, led_s = rec_l["store"], res["probe_ledger"][i]["store"]
        a = answers([plan["probes"][i]] * 2, [row_l, res["probe_rows"][i]])
        diffs.append(stats.pair_diff(a[0], a[1]))
        formed += (led_l["store_chunks"] == 0 and led_s["store_chunks"] > 0
                   and led_l["local_chunks"]
                   == led_s["local_chunks"] + led_s["store_chunks"])
    return {"pairs": len(diffs), "formed": formed,
            "n_values": sum(d["n_values"] for d in diffs),
            "max_abs": max((d["max_abs"] for d in diffs), default=0.0),
            "unmatched": sum(d["unmatched"] for d in diffs),
            "per_pair_max_abs": [d["max_abs"] for d in diffs]}


# -- main --------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", type=int, choices=[0, 1], default=0)
    args = ap.parse_args()

    c = load_cell(args.workload, args.rehearse)
    run_dir = make_run_dir(c, f"s{args.seed}.t{args.trace}")
    plan = c["generate"](args.seed, args.seconds)     # a pure function of the seed
    say(f"{c['wl']['name']} seed {args.seed}: {plan['meta']}, "
        + ("a closed loop" if plan["closed"] else f"{len(plan['schedule'])} requests"))
    try:
        with servers(c, run_dir, args.seed, pool_gib(c, [plan])) as up:
            async def drive():
                res = await set_up_traffic(up["port"], plan, c["cell"])
                res.update(await window(up["port"], plan, c["cell"], args.seconds,
                                        args.trace, run_dir))
                return res

            res = asyncio.run(drive())
            setup_s = res["w0"] - T0
            chk = stop_and_check(up, run_dir, answers(plan["probes"], res["probe_rows"]))
            return report(args, c, up["device"], plan, res, chk, setup_s, run_dir)
    except RunFailure as e:
        say(f"FAILED: {e}")
        serve = CHILDREN[1] if len(CHILDREN) > 1 else None
        # no TPU (2) / chip not filled as a deployment (4): the launcher's code
        return serve.returncode if serve and serve.returncode in (2, 4) else 1


def in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def read_layer_metrics(manifest: dict, cell_name: str, ctx: dict) -> dict:
    """One reader each.  A metric is left out of the cell where its reader
    finds nothing to read, and where the configuration's count module does
    not count what it needs (``costs.NotCounted``)."""
    reported = {m["name"] for m in manifest["end_to_end"] if in_cell(m, cell_name)}
    layer = {}
    for m in manifest["per_layer"]:
        # with no list of its own a metric belongs to every cell that reports
        # the end-to-end metric it moves, those that later PRs add too
        if not in_cell(m, cell_name) or m["moves"] not in reported:
            continue
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        try:
            value = ctx["reader"](spec["reader"]).read(ctx)
        except costs.NotCounted as e:
            say(f"{m['name']} left out: {e}")
            continue
        if value is not None:
            layer[m["name"]] = {"value": value, "unit": m["unit"]}
    return layer


def report(args, c, device, plan, res, chk, setup_s, run_dir) -> int:
    manifest, wl, cell = c["manifest"], c["wl"], c["cell"]
    traffic, config = c["traffic"], c["config"]
    serve_args = config["serve"]["args"]
    prefill_chunk = int(serve_args[serve_args.index("--prefill-chunk") + 1])
    rows, w0, w1 = res["rows"], res["w0"], res["w1"]
    closed = plan["closed"]
    measured = ([r for r in rows if w0 <= r["t_done"] < w1] if closed
                else [r for r in rows if r["t_due"] >= w0])
    dropped = [r for r in rows if r.get("cancelled")]
    rows = [r for r in rows if not r.get("cancelled")]
    failed = [r for r in rows if not r["ok"]]
    late = sorted(r["late_s"] for r in rows)
    say(f"load: {len(rows)} requests done, {len(dropped)} dropped at the window's "
        f"end, {len(measured)} measured, {len(failed)} failed; generator lateness p50 "
        f"{stats.nearest_rank(late, 0.5) * 1e3:.2f} ms max {late[-1] * 1e3:.2f} ms; "
        f"client loop's longest gap {res['client_gap_s'] * 1e3:.1f} ms")

    e2e = stats.end_to_end(measured if not closed else rows + dropped, w0, w1)
    if closed:       # TPOT over requests completed in the window
        e2e.update({k: v for k, v in stats.end_to_end(measured, w0, w1).items()
                    if k.startswith("tpot_")})
    e2e["setup_s"] = setup_s

    # -- correct: exact things inside the window, the probes outside it -----------
    checks = []   # (name, what, value, limit, ok)

    def check(name, what, value, limit, ok):
        checks.append((name, what, value, limit, bool(ok)))
        say(f"check {'ok  ' if ok else 'FAIL'} {what}: {value} (limit {limit})")

    check("requests_failed", "requests failed (non-200, short stream, error)",
          len(failed), 0, not failed)
    for fam in ("dropped", "degraded"):
        bad = {k: v for k, v in res["after"][fam].items() if v}
        check(f"store_{fam}", f"store {fam} counters", bad or 0, 0, not bad)
    check("store_circuit", "store circuit", res["health"].get("store_circuit"), "closed",
          res["health"].get("store_circuit") == "closed")
    load_delta = promtext.delta(res["after"]["prefix"], res["before_load"]["prefix"])
    sent = sum(r["prompt_tokens"] for r in rows)
    if closed:
        # requests dropped at the window's end may or may not have been
        # admitted, so the sum is held between two exact ends; unshared
        # prompts reuse nothing, exactly
        top = sent + sum(r["prompt_tokens"] for r in dropped)
        got = sum(load_delta.values())
        check("prompt_tokens", "local + store + computed prompt tokens", got,
              f"{sent}..{top}", sent <= got <= top)
        reused = load_delta.get("local", 0) + load_delta.get("store", 0)
        check("unshared_reused", "prompt tokens reused by unshared prompts", reused, 0,
              reused == 0)
    else:
        check("prompt_tokens", "local + store + computed prompt tokens",
              sum(load_delta.values()), sent, sum(load_delta.values()) == sent)
    # a rehearsal prints no result line whatever it finds; its `correct` says
    # whether every other comparison held on the CPU
    want = "cpu" if args.rehearse else "tpu"
    check("platform", "platform", device["platform"], want, device["platform"] == want)
    limit = config["check"]["logprob_rms_limit"]
    f32 = chk["f32"]
    check("probe_values", "probe log-probabilities compared", f32["n_values"], ">= 160",
          f32["n_values"] >= 160)
    check("logprob_rms", f"RMS(server logprob - f32 reference logprob), reference "
          f"{chk['reference']}", f32["rms"], limit,
          limit is not None and f32["rms"] <= limit)
    # not part of `correct`: with seeded weights the top logits lie within
    # bf16 rounding of each other, and a sound run read 1 of 32 (PR 24)
    say(f"info: chosen tokens outside the reference's top 5: "
        f"{f32['chosen_not_in_ref_top5']} of {f32['n_values'] // 5}")
    say(f"probe RMS per probe: {f32['per_probe_rms']} max_abs {f32['max_abs']} "
        f"(reference took {chk['seconds']:.1f}s)")
    want_store = traffic.get("min_store_probes", 0)
    if want_store:
        pairs = pair_check(plan, res)
        check("pairs_formed", "re-ask probes paired (first ask from HBM, second from "
              "the store)", pairs["formed"], f">= {want_store}",
              pairs["formed"] >= want_store)
        lim = config["check"]["pair_logprob_max_abs_limit"]
        check("pair_logprob_max_abs", "max |logprob from store pages - logprob from "
              f"HBM pages|, {pairs['n_values']} values", pairs["max_abs"], lim,
              lim is not None and pairs["max_abs"] <= lim)
        check("pair_unmatched", "top-5 tokens of one answer missing from the other",
              pairs["unmatched"], 0, pairs["unmatched"] == 0)
        say(f"pair max_abs per pair: {pairs['per_pair_max_abs']}")
    correct = all(ok for *_, ok in checks)
    say(f"correct: {correct}")

    # -- per-layer metrics: one reader each, from counters, rows and the trace ----
    post = load_json(run_dir, "post.json")
    trace = None
    if args.trace:
        trace = load_json(run_dir, "trace.json")
        if "error" in trace:
            if not args.rehearse:
                raise RunFailure(f"trace: {trace['error']}")
            say(f"trace (rehearsal): {trace['error']}")
            trace = None
    ctx = {"cell": cell, "traffic": traffic, "config": config, "stats": stats,
           "costs": c["counts"], "peaks": costs.peaks(device["kind"]) if not args.rehearse else {},
           "rows": measured, "all_rows": rows, "window": (w0, w1),
           "prefix_delta": promtext.delta(res["after"]["prefix"], res["before"]["prefix"]),
           "engine_before": res["before"]["engine"], "engine_after": res["after"]["engine"],
           "server_rows": res["server_rows"], "trace": trace,
           "trace_span": res["trace_span"], "prefill_chunk": prefill_chunk,
           "reader": lambda name: family.load_module(
               os.path.join(HERE, "readers", f"{name}.py"))}
    layer = read_layer_metrics(manifest, wl["name"], ctx)
    say(f"end to end: {json.dumps(e2e)}")
    say(f"per layer: {json.dumps({k: v['value'] for k, v in layer.items()})}")
    with open(os.path.join(run_dir, "rows.json"), "w") as f:
        json.dump({"rows": [{k: v for k, v in r.items() if k != "payload"}
                            for r in rows], "w0": w0, "w1": w1,
                   "server_rows": res["server_rows"], "checks": checks, "correct": correct,
                   "e2e": e2e, "layer": layer, "trace": trace,
                   "trace_span": res["trace_span"],
                   "engine_after": res["after"]["engine"]}, f)

    if args.rehearse:
        say("REHEARSAL on platform: cpu -- no result line: these are not device numbers")
        return 3
    if args.trace:
        metrics = layer
    else:
        metrics = {}
        for m in manifest["end_to_end"]:
            if not in_cell(m, wl["name"]):
                continue
            if m["name"] not in e2e:
                raise RunFailure(f"nothing to compute {m['name']} from")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": post["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": len(rows),
              "failed": len(failed), "metrics": metrics, "device": dev}
    if args.trace:
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    # every number compared beside its limit: last in the line, and the last
    # lines on standard error (what the driver keeps of a run that is not correct)
    result["compared"] = {name: {"value": value, "limit": limit, "ok": ok}
                          for name, _, value, limit, ok in checks}
    sys.stdout.flush()
    for name, _, value, limit, ok in checks:
        print(f"compared {name}: {value} limit {limit} {'ok' if ok else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main())
