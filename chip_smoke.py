#!/usr/bin/env python3
"""chip_smoke.py — does the store-attached serving path still start on the chip?

Drives the system's main path once, through the entry points a user calls:

    python -m infinistore_tpu.server   (the KV store, python backend)
    python -m infinistore_tpu.serve    (the serving server, --store-host ...)
    HTTP clients                       (/v1/completions, one of them SSE)

at the full width of one model the repo supports (configs/qwen3_8b_l12.json:
every Qwen3-8B width as published, depth cut to fit one 16 GB chip, weights
from a seed), with the KV cache sized like a deployment.  It then restarts the
serving server on the same store and checks that the second process computed
only the sub-chunk remainders of the same prompts: the KV tier, which is the
product, worked — not merely that HTTP answered 200.

This process never imports JAX: a chip belongs to one process at a time, and
the servers it starts need it.  Everything it learns about the device comes
from short-lived children and from the serving server's /healthz.

Without an accelerator it exits non-zero before serving anything.
``--dry-run`` runs the same sequence with the tiny preset under
JAX_PLATFORMS=cpu, to debug this script off the chip; it says ``platform:
cpu`` and proves nothing about the chip.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import glob
import http.client
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

from infinistore_tpu.utils.metrics import parse_prometheus_text  # jax-free

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

SEED = 20260926
BLOCK_TOKENS = 16
PREFILL_CHUNK = 512     # unchunked, a 3k prompt's [S, vocab] logits and
#                         [heads, S, S] scores alone are gigabytes
PREFIX_TOKENS = 2048    # shared by the four requests of a group
TAIL_TOKENS = (256, 1024)
MAX_TOKENS = 64
IN_FLIGHT = 4
STREAMED = 3            # index of the one request sent over SSE
# Weights + cache should fill the chip like a deployment does (memory bugs
# only show when the cache is large).  The cache is sized so that they reach
# FILL of the device's bytes_limit; the smoke fails below MIN_FILL.
FILL, MIN_FILL = 0.78, 0.75
# The second server loads from the store the very KV the first one computed
# (--kv-quant none: bit-identical pages), so the first generated token's
# distribution may differ only by bf16 rounding along a different reduction
# order (a 16-token tail forward instead of a 512-token chunk).  The token the
# first run chose must be among the second run's top 5 with a log-probability
# within 0.2: a bf16 logit near 5 (the largest of ~150k unit-variance logits)
# is spaced 2^-5 = 0.03 apart, and a few such steps through the stack stay well
# inside 0.2, while KV from the wrong pages puts a different token on top and
# fails the top-5 test outright.  Sampled tokens are NOT compared: with random
# weights the argmax flips on rounding.
FIRST_LOGPROB_TOL = 0.2
START_TIMEOUT_S = 600
REQUEST_TIMEOUT_S = 900


class SmokeFailure(Exception):
    pass


T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- children -----------------------------------------------------------------

CHILDREN: list = []   # every Popen started, for the final reaping


def log_path(name: str) -> str:
    return os.path.join(OUT_DIR, f"{name}.log")


def start(name: str, argv: list, env: dict) -> subprocess.Popen:
    with open(log_path(name), "w") as f:
        proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=f,
                                stderr=subprocess.STDOUT)
    CHILDREN.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace_s: float = 60.0) -> float:
    """SIGTERM, wait until the process is GONE (the chip is held until it
    is), SIGKILL past the grace period.  Returns seconds to exit."""
    t0 = time.monotonic()
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    return time.monotonic() - t0


def log_tail(name: str, n: int = 30) -> str:
    with open(log_path(name), errors="replace") as f:  # minus JAX's chatter
        lines = [ln for ln in f if not ln.startswith("DEBUG:")]
    return "".join(lines[-n:])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- http ---------------------------------------------------------------------

def get(port: int, path: str, timeout: float = 30.0) -> bytes:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return r.read()


def get_json(port: int, path: str, timeout: float = 30.0):
    return json.loads(get(port, path, timeout))


def get_metrics(port: int) -> dict:
    return parse_prometheus_text(get(port, "/metrics").decode())


def family(metrics: dict, name: str) -> dict:
    """{label value: sample} for a one-label family (absent labels are 0)."""
    return {labels[0][1]: v for (n, labels), v in metrics.items()
            if n == name and labels}


def wait_healthy(name: str, proc: subprocess.Popen, port: int) -> dict:
    deadline = time.monotonic() + START_TIMEOUT_S
    while time.monotonic() < deadline:
        check(proc.poll() is None,
              f"{name} exited with {proc.returncode} before /healthz "
              f"answered:\n{log_tail(name)}")
        try:
            return get_json(port, "/healthz", timeout=2)
        except (OSError, ValueError):
            time.sleep(1.0)
    raise SmokeFailure(f"{name} did not answer /healthz in "
                       f"{START_TIMEOUT_S}s:\n{log_tail(name)}")


def sse_events(resp):
    """The JSON events of a Server-Sent-Events body, up to its [DONE]."""
    for raw in resp:
        line = raw.decode().strip()
        if line == "data: [DONE]":
            return
        if line.startswith("data: "):
            yield json.loads(line[len("data: "):])
    raise SmokeFailure("SSE stream ended without [DONE]")


def complete(port: int, prompt: list, stream: bool) -> dict:
    """One /v1/completions call, SSE when ``stream``; it must answer 200 with
    MAX_TOKENS tokens.  Returns the generated ids and the first token's
    (id, logprob, top-5)."""
    body = json.dumps({"prompt": prompt, "max_tokens": MAX_TOKENS,
                       "temperature": 0, "logprobs": 5,
                       "stream": stream}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=REQUEST_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"request answered {resp.status}: "
                               f"{resp.read()[:2000]!r}")
        ids, lps, tops = [], [], []
        for ev in sse_events(resp) if stream else [json.loads(resp.read())]:
            check("error" not in ev, f"error event: {ev}")
            choice = ev["choices"][0]
            ids += choice["token_ids"]
            lps += choice["logprobs"]["token_logprobs"]
            tops += choice["logprobs"]["top_logprobs"]
        check(len(ids) == MAX_TOKENS, f"request returned {len(ids)} tokens")
        return {"ids": ids, "first": (ids[0], lps[0], tops[0])}
    finally:
        conn.close()


def run_requests(port: int, prompts: list) -> list:
    with ThreadPoolExecutor(IN_FLIGHT) as pool:
        futs = [pool.submit(complete, port, p, i == STREAMED)
                for i, p in enumerate(prompts)]
        return [f.result() for f in futs]


# -- the workload ---------------------------------------------------------------

def make_prompts(vocab: int) -> list:
    """8 prompts from SEED: two groups of four sharing a PREFIX_TOKENS
    prefix, unshared tails of TAIL_TOKENS.  Lengths are never a whole number
    of pages (redrawn otherwise): the engine always recomputes at least one
    token, so only then is "computed" exactly the sub-page remainders.
    Groups alternate in send order, so with four in flight the second wave
    of each group can meet pages the first wave registered."""
    rng = random.Random(SEED)
    groups = []
    for _ in range(2):
        prefix = [rng.randrange(1, vocab) for _ in range(PREFIX_TOKENS)]
        group = []
        for _ in range(4):
            n = rng.randint(*TAIL_TOKENS)
            while (PREFIX_TOKENS + n) % BLOCK_TOKENS == 0:
                n = rng.randint(*TAIL_TOKENS)
            group.append(prefix + [rng.randrange(1, vocab) for _ in range(n)])
        groups.append(group)
    return [groups[i % 2][i // 2] for i in range(8)]


def weight_bytes_per_device(m: dict, n_layers: int, tp: int) -> int:
    """bf16 weights one device holds under the engine's tensor-parallel
    specs: matrices split tp ways, the embedding replicated (norms are
    noise at this scale and left out)."""
    qkv = m["dim"] * m["head_dim"] * (m["n_heads"] + 2 * m["n_kv_heads"])
    layer = (qkv + m["n_heads"] * m["head_dim"] * m["dim"]
             + 3 * m["dim"] * m["ffn_dim"])
    embed = m["vocab_size"] * m["dim"]
    return 2 * (n_layers * layer // tp + embed + embed // tp)


# -- phases -----------------------------------------------------------------------

def probe_device(env: dict) -> dict:
    """What JAX sees, from a child that exits (and releases the chip) before
    any server starts."""
    code = ("import json, jax; d = jax.devices(); "
            "s = d[0].memory_stats() or {}; "
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d), "
            "'bytes_limit': int(s.get('bytes_limit', 0))}))")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    check(out.returncode == 0, f"device probe failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def serve_once(tag: str, argv: list, env: dict, port: int, prompts: list,
               dry_run: bool) -> dict:
    """Start a serving server, send the prompts, read every counter the
    assertions need, stop it and wait for it to be gone."""
    t_start = time.monotonic()
    proc = start(tag, argv, env)
    health = wait_healthy(tag, proc, port)
    dev = health.get("device") or {}
    say(f"{tag}: healthy after {time.monotonic() - t_start:.1f}s on "
        f"platform: {dev.get('platform')} device_kind: "
        f"{dev.get('device_kind')} count: {dev.get('count')}")
    check(dev.get("platform") == ("cpu" if dry_run else "tpu"),
          f"{tag} runs on {dev!r}, not on the chip")
    t_req = time.monotonic()
    results = run_requests(port, prompts)
    req_s = time.monotonic() - t_req
    say(f"{tag}: 8/8 requests 200 with {MAX_TOKENS} tokens in {req_s:.1f}s")
    metrics = get_metrics(port)
    engine = get_json(port, "/debug/engine?limit=0")["summary"]
    out = {
        "health": get_json(port, "/healthz"),
        "device": dev,
        "first": [r["first"] for r in results],
        "prefix": family(metrics, "istpu_engine_prefix_tokens_total"),
        "dropped": family(metrics, "istpu_store_push_dropped_total"),
        "degraded": family(metrics, "istpu_store_degraded_ops_total"),
        "rows": get_json(port, "/debug/requests")["records"],
        "compiles": engine["compiles"], "compile_s": engine["compile_s"],
        "mem": engine["mem"], "start_s": round(t_req - t_start, 1),
        "requests_s": round(req_s, 1),
    }
    out["exit_s"] = round(stop(proc), 1)
    with open(log_path(tag), errors="replace") as f:
        out["cache_hits"] = f.read().count("Persistent compilation cache hit")
    mem = out["mem"] or {}
    say(f"{tag}: compiles {out['compiles']} compile_s {out['compile_s']:.1f} "
        f"cache_hits {out['cache_hits']} peak_bytes {mem.get('peak_bytes')} "
        f"limit_bytes {mem.get('limit_bytes')} prefix {out['prefix']} "
        f"gone {out['exit_s']}s after SIGTERM")
    for d in mem.get("devices", ()):
        say(f"{tag}: device {d['id']} bytes_in_use {d['live_bytes']} "
            f"peak {d['peak_bytes']} limit {d['limit_bytes']}")
    return out


def check_tier_clean(tag: str, run: dict) -> None:
    check(run["health"].get("store_circuit") == "closed",
          f"{tag}: store_circuit is {run['health'].get('store_circuit')!r}")
    for fam in ("dropped", "degraded"):
        bad = {k: v for k, v in run[fam].items() if v}
        check(not bad, f"{tag}: store {fam} counters are not 0: {bad}")


def run(args) -> dict:
    dry = args.dry_run
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    if dry:
        env["JAX_PLATFORMS"] = "cpu"
        if args.tp > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count="
                                f"{args.tp}").strip()
    model_file = os.path.join("configs", "tiny.json") if dry else args.model
    with open(os.path.join(REPO, model_file)) as f:
        spec = json.load(f)
    m = spec["published"]
    n_layers = spec.get("reduced", {}).get("n_layers", m["n_layers"])

    # -- 0. the device, before anything is served ---------------------------
    probe = probe_device(env)
    say(f"probe: platform: {probe['platform']} device_kind: {probe['kind']} "
        f"count: {probe['count']} bytes_limit: {probe['bytes_limit']}")
    check(probe["platform"] == ("cpu" if dry else "tpu"),
          f"JAX found no accelerator (platform {probe['platform']!r}); "
          f"this smoke does not serve from the CPU")
    check(probe["count"] >= args.tp, f"--tp {args.tp} needs {args.tp} devices")

    prompts = make_prompts(m["vocab_size"])
    lens = [len(p) for p in prompts]
    chunks = [n // BLOCK_TOKENS for n in lens]
    prefix_chunks = PREFIX_TOKENS // BLOCK_TOKENS
    unique_chunks = 2 * prefix_chunks + sum(c - prefix_chunks for c in chunks)
    page_bytes = 2 * BLOCK_TOKENS * m["n_kv_heads"] * m["head_dim"] * 2
    block_bytes = n_layers * page_bytes
    say(f"model {model_file}: {n_layers} of {m['n_layers']} layers, prompt "
        f"lengths {lens}, {unique_chunks} unique complete chunks")

    # -- cache sized like a deployment ---------------------------------------
    w_dev = weight_bytes_per_device(m, n_layers, args.tp)
    if probe["bytes_limit"]:
        n_blocks = int((FILL * probe["bytes_limit"] - w_dev) * args.tp
                       // block_bytes) // 256 * 256
        fill = (w_dev + n_blocks * block_bytes / args.tp) / probe["bytes_limit"]
        say(f"weights {w_dev / 1e9:.2f} GB/device + cache {n_blocks} blocks "
            f"({n_blocks * block_bytes / args.tp / 1e9:.2f} GB/device, "
            f"{n_blocks * BLOCK_TOKENS} tokens) = {fill:.1%} of bytes_limit")
        check(fill >= MIN_FILL, f"weights + cache fill only {fill:.1%}")
    else:
        check(dry, "the device reports no bytes_limit to size the cache from")
        n_blocks = 2048
    need = sum(-(-(n + MAX_TOKENS) // BLOCK_TOKENS) for n in lens)
    check(n_blocks >= need, f"{n_blocks} blocks cannot hold the workload's "
                            f"{need} pages")

    # -- 1. the store (python backend: no ignored binary, no toolchain) --------
    store_bytes = unique_chunks * n_layers * max(page_bytes, 64 << 10)
    prealloc_gb = max(1, -(-2 * store_bytes >> 30))
    shm_free = shutil.disk_usage("/dev/shm").free
    say(f"/dev/shm free {shm_free / 2**30:.1f} GiB; store pool "
        f"{prealloc_gb} GiB for {store_bytes / 2**30:.2f} GiB of pages")
    check(shm_free > (prealloc_gb << 30) + (1 << 30),
          f"/dev/shm has {shm_free} bytes free, the store pool needs "
          f"{prealloc_gb << 30}")
    svc, mng = free_port(), free_port()
    store = start("store", [
        sys.executable, "-m", "infinistore_tpu.server", "--backend", "python",
        "--host", "127.0.0.1", "--service-port", str(svc),
        "--manage-port", str(mng), "--prealloc-size", str(prealloc_gb),
        "--minimal-allocate-size", "64",
        "--shm-prefix", f"istpu_smoke_{os.getpid()}"], env)
    wait_healthy("store", store, mng)

    # -- 2..7. two serving processes on the same store ---------------------------
    serve_env = dict(env, ISTPU_CLIENT="python", ISTPU_STEPPROF_SAMPLE="4",
                     JAX_DEBUG_LOG_MODULES="jax._src.compiler")

    def serve_argv(port: int) -> list:
        return [sys.executable, "-m", "infinistore_tpu.serve",
                "--model", model_file, "--port", str(port),
                "--n-blocks", str(n_blocks),
                "--block-tokens", str(BLOCK_TOKENS),
                "--prefill-chunk", str(PREFILL_CHUNK), "--tp", str(args.tp),
                "--kv-quant", "none", "--store-durability", "strict",
                # a cold server compiles on the request path; against the
                # stock 2 s / 0.25 s targets that burns the SLO budget and
                # admission control answers 429 by the second wave
                "--slo-ttft", str(REQUEST_TIMEOUT_S), "--slo-tpot", "60",
                "--store-host", "127.0.0.1",
                "--store-service-port", str(svc)]

    port1 = free_port()
    first = serve_once("serve1", serve_argv(port1), serve_env, port1, prompts,
                       dry)
    check_tier_clean("serve1", first)
    # (the first server may itself adopt from the store: chunks are pushed as
    # they are computed, before their pages are registered locally, so a later
    # request of a group can find a prefix there that a neighbour is still
    # prefilling)
    check(sum(first["prefix"].values()) == sum(lens),
          f"serve1 prefix provenance {first['prefix']} does not add up to "
          f"{sum(lens)} prompt tokens")
    # every complete chunk of every layer is one key; the one SSE stream
    # also leaves its resume checkpoint (an inline blob) in the same store
    kvmap = get_json(mng, "/kvmap_len")["len"]
    check(kvmap == unique_chunks * n_layers + 1,
          f"store holds {kvmap} keys, expected {unique_chunks} chunks x "
          f"{n_layers} layers + 1 stream checkpoint")
    say(f"store kvmap_len {kvmap} = {unique_chunks} chunks x {n_layers} "
        f"layers + 1 stream checkpoint")

    # A FRESH process: in one process repeated prompts hit the HBM prefix
    # cache and never reach the store.
    port2 = free_port()
    second = serve_once("serve2", serve_argv(port2), serve_env, port2, prompts,
                        dry)
    check_tier_clean("serve2", second)
    px = second["prefix"]
    complete_toks = sum(chunks) * BLOCK_TOKENS
    check(px.get("store", 0) + px.get("local", 0) == complete_toks
          and px.get("computed", 0) == sum(lens) - complete_toks
          and px.get("store", 0) >= unique_chunks * BLOCK_TOKENS,
          f"serve2 prefix provenance {px}: expected store + local = "
          f"{complete_toks}, computed = {sum(lens) - complete_toks} (the "
          f"sub-page remainders), store >= {unique_chunks * BLOCK_TOKENS}")
    check(len(second["rows"]) == 8 and all(
        r["store"]["hit"] or r["store"]["local_chunks"] > 0
        for r in second["rows"]),
        f"serve2 /debug/requests has a row that adopted nothing: "
        f"{[r['store'] for r in second['rows']]}")
    check(get_json(mng, "/kvmap_len")["len"] == kvmap + 1,
          "serve2 pushed pages the store already held")
    worst = 0.0
    for i, (a, b) in enumerate(zip(first["first"], second["first"])):
        tok, lp, _ = a
        check(str(tok) in b[2], f"request {i}: first token {tok} of serve1 is "
                                f"not in serve2's top 5 {b[2]}")
        worst = max(worst, abs(b[2][str(tok)] - lp))
    check(worst <= FIRST_LOGPROB_TOL, f"first-token logprob moved by {worst}")
    say(f"first-token logprobs agree within {worst:.4f} "
        f"(tolerance {FIRST_LOGPROB_TOL})")
    if not dry:
        # the persistent compile cache (infinistore_tpu/jaxcfg.py) is what
        # makes a restart cheap; on the CPU it is off by design
        check(second["cache_hits"] > 0, "serve2 logged no compile-cache hit")
        if first["cache_hits"]:
            # a checkout (or a placed cache) that an earlier run warmed:
            # both servers mostly hit, and their ratio says nothing
            say(f"serve1 itself hit the compile cache {first['cache_hits']} "
                f"times: compile_s ratio not asserted")
        else:
            check(second["compile_s"] < 0.5 * first["compile_s"],
                  f"serve2 compile_s {second['compile_s']} is not under "
                  f"half of serve1's {first['compile_s']}")
        for tag, r in (("serve1", first), ("serve2", second)):
            check(r["mem"] and r["mem"].get("limit_bytes"),
                  f"{tag} reported no device memory: {r['mem']}")
    stop(store)

    strip = ("first", "rows", "health")
    return {"device": {"platform": first["device"]["platform"],
                       "kind": first["device"]["device_kind"],
                       "count": first["device"]["count"]},
            "model": model_file, "tp": args.tp, "n_blocks": n_blocks,
            "kvmap_len": kvmap, "first_logprob_worst": worst,
            "serve1": {k: v for k, v in first.items() if k not in strip},
            "serve2": {k: v for k, v in second.items() if k not in strip}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="the same sequence with the tiny preset on the CPU "
                         "(debugs this script; proves nothing about the chip)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree passed through to serve")
    ap.add_argument("--model",
                    default=os.path.join("configs", "qwen3_8b_l12.json"),
                    help="model config file to serve")
    args = ap.parse_args()
    os.makedirs(OUT_DIR, exist_ok=True)
    # a supervisor's SIGTERM must still reap the children
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        summary = run(args)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        for proc in reversed(CHILDREN):
            stop(proc, grace_s=10.0)
        for seg in glob.glob(f"/dev/shm/istpu_smoke_{os.getpid()}*"):
            os.unlink(seg)
    summary["wall_s"] = round(time.monotonic() - T0, 1)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    say(f"all phases passed in {summary['wall_s']}s")
    print(json.dumps({"ok": True, "device": summary["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
