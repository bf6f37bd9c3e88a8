"""Driver benchmark: Llama-3-8B-shaped KV block put/get bandwidth.

Workload (SURVEY.md §6 config 2): pages of Llama-3-8B KV cache — 32 layers,
8 KV heads, 128 head dim, bf16, 16-token chunks → 64 KiB per (layer, chunk)
page — moved between a client buffer and a live infinistore-tpu server on the
same host (the TPU-VM serving topology).

Measured path: the zero-copy SHM transport (our RDMA analog).
Baseline path:  single-stream loopback TCP inline transfer — the proxy for
the reference's TCP transport measured on identical hardware (BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from infinistore_tpu import ClientConfig, InfinityConnection  # noqa: E402
from infinistore_tpu.config import TYPE_SHM, TYPE_TCP  # noqa: E402

PAGE_BYTES = 2 * 16 * 8 * 128 * 2  # K+V, 16 tok, 8 kv-heads, 128 dim, bf16 = 64 KiB
N_LAYERS = 32
CHUNKS = 64  # pages per layer per round -> 128 MiB per round
ROUND_BYTES = PAGE_BYTES * N_LAYERS * CHUNKS


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def start_server(backend=None):
    service, manage = _free_port(), _free_port()
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "infinistore_tpu.server",
            "--service-port", str(service), "--manage-port", str(manage),
            "--prealloc-size", "2", "--minimal-allocate-size", "64",
            "--log-level", "warning", "--auto-increase",
        ]
        + (["--backend", backend] if backend else []),
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", service), timeout=1).close()
            return proc, service
        except OSError:
            time.sleep(0.2)
    proc.kill()
    raise RuntimeError("server did not come up")


def bench_conn(conn_type: str, port: int, rounds: int, tag: str,
               force_python: bool = False):
    cfg = ClientConfig(host_addr="127.0.0.1", service_port=port,
                       connection_type=conn_type, log_level="warning",
                       # the baseline proxy is the reference's single TCP
                       # stream; the measured path uses the striped default
                       num_streams=1 if force_python else 4)
    if force_python:
        # the baseline leg is a stable proxy for the reference's single-stream
        # loopback TCP (BASELINE.md); pin it to the Python client so it does
        # not drift with native-client optimizations
        prev = os.environ.get("ISTPU_CLIENT")
        os.environ["ISTPU_CLIENT"] = "python"
        try:
            conn = InfinityConnection(cfg)
        finally:
            if prev is None:
                os.environ.pop("ISTPU_CLIENT", None)
            else:
                os.environ["ISTPU_CLIENT"] = prev
    else:
        conn = InfinityConnection(cfg)
    conn.connect()
    buf = np.random.randint(0, 256, size=ROUND_BYTES, dtype=np.uint8)
    conn.register_mr(buf)
    ptr = buf.ctypes.data

    put_t = get_t = 0.0
    for r in range(rounds):
        blocks = [
            (f"{tag}-r{r}-L{layer}-c{c}", (layer * CHUNKS + c) * PAGE_BYTES)
            for layer in range(N_LAYERS)
            for c in range(CHUNKS)
        ]
        t0 = time.perf_counter()
        conn.write_cache(blocks, PAGE_BYTES, ptr)
        put_t += time.perf_counter() - t0
        t0 = time.perf_counter()
        conn.read_cache(blocks, PAGE_BYTES, ptr)
        get_t += time.perf_counter() - t0
        conn.delete_keys([k for k, _ in blocks])
    stages = conn.latency_stats()
    conn.close()
    gb = rounds * ROUND_BYTES / 1e9
    return gb / put_t, gb / get_t, stages


def bench_tpu_leg(timeout_s: int = 1800) -> dict:
    """Run the TPU-in-the-loop leg (bench_tpu.py) in a subprocess (this
    process stays off JAX: the leg needs the chip) with a hard timeout.

    Returns the leg's JSON dict on success, ``{"disabled": True}`` under
    ``ISTPU_BENCH_TPU=0``, and ``{"failed": <why>}`` otherwise — no chip,
    a leg that raised, a timeout — which makes ``main`` exit non-zero:
    a host-only number is never passed off as a full run."""
    if os.environ.get("ISTPU_BENCH_TPU") == "0":
        return {"disabled": True}
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_tpu.py")
    try:
        # own process group: on timeout we must also kill the server
        # subprocess bench_tpu spawns (SIGKILL to the leg alone would orphan
        # it, leaking its shm pool)
        leg = subprocess.Popen(
            [sys.executable, script],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            start_new_session=True,
        )
        stdout, stderr = leg.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        import signal

        os.killpg(leg.pid, signal.SIGKILL)
        leg.communicate()
        return {"failed": f"timed out after {timeout_s}s"}
    rec: dict = {}
    for line in reversed(stdout.decode(errors="replace").strip().splitlines()):
        try:
            rec = json.loads(line)
            break
        except ValueError:
            continue
    if leg.returncode != 0 or not rec:
        # bench_tpu prints its cumulative JSON (with the ``*_error`` keys
        # of the legs that raised) before exiting non-zero: keep it, marked
        stderr_tail = stderr.decode(errors="replace")[-1200:]
        print(f"# tpu leg: FAILED rc={leg.returncode} "
              f"({stderr_tail[-300:].replace(chr(10), ' | ')})",
              file=sys.stderr)
        return {**rec, "failed": f"exit code {leg.returncode}"}
    return rec


def bench_cluster(n_nodes: int, rounds: int = 4) -> dict:
    """Cluster leg: N local store instances driven through the
    consistent-hash router (``infinistore_tpu.cluster``), one writer
    thread per node per round — the aggregate number says what the
    fleet sustains when one host's NIC/DRAM stops being the cap, and
    the per-node split shows ring balance."""
    import concurrent.futures as cf

    from infinistore_tpu.cluster import RoutedStorePool

    procs = []
    try:
        for _ in range(n_nodes):
            procs.append(start_server())
        pool = RoutedStorePool(
            [f"127.0.0.1:{port}" for _, port in procs],
            connection_type=TYPE_SHM,
        )
        bufs = {}
        for node in pool.nodes():
            buf = np.random.randint(0, 256, size=ROUND_BYTES, dtype=np.uint8)
            node.conn.register_mr(buf)
            bufs[node.endpoint] = buf
        per_node = {ep: {"put_s": 0.0, "get_s": 0.0, "bytes": 0}
                    for ep in pool.endpoints}
        put_t = get_t = 0.0
        with cf.ThreadPoolExecutor(max_workers=n_nodes) as pool_exec:
            for r in range(rounds):
                keys = [f"cl-r{r}-L{layer}-c{c}"
                        for layer in range(N_LAYERS) for c in range(CHUNKS)]
                groups = pool.partition(keys)

                def one(ep_idxs, op):
                    ep, idxs = ep_idxs
                    blocks = [(keys[i], j * PAGE_BYTES)
                              for j, i in enumerate(idxs)]
                    conn = pool.node(ep).conn
                    t0 = time.perf_counter()
                    getattr(conn, op)(blocks, PAGE_BYTES,
                                      bufs[ep].ctypes.data)
                    dt = time.perf_counter() - t0
                    per_node[ep]["put_s" if op == "write_cache"
                                 else "get_s"] += dt
                    if op == "write_cache":
                        per_node[ep]["bytes"] += PAGE_BYTES * len(blocks)
                    return dt

                t0 = time.perf_counter()
                list(pool_exec.map(lambda g: one(g, "write_cache"),
                                   groups.items()))
                put_t += time.perf_counter() - t0
                t0 = time.perf_counter()
                list(pool_exec.map(lambda g: one(g, "read_cache"),
                                   groups.items()))
                get_t += time.perf_counter() - t0
                for ep, idxs in groups.items():
                    pool.node(ep).conn.delete_keys(
                        [keys[i] for i in idxs])

        pool.close()
        # the native fleet is done — free its CPU before the reshape
        # leg so the two migration passes aren't measured under the
        # native servers' polling load
        for proc, _ in procs:
            proc.terminate()
        for proc, _ in procs:
            proc.wait(timeout=10)
        procs.clear()

        # -- reshape leg: join one spare node into the loaded fleet,
        # once over the pre-PR-16 per-key path (``_copy_batch``
        # disabled) and once over the descriptor-batched path — same
        # key population, same node, so the two ``migrate_gbps``
        # numbers are directly comparable.  The leg runs its own
        # python-backend mini-fleet with a python-client pool
        # (``op_timeout_s``): migration needs the key-listing surface,
        # which neither the native server nor the native client speaks
        for _ in range(n_nodes):
            procs.append(start_server(backend="python"))
        rpool = RoutedStorePool(
            [f"127.0.0.1:{port}" for _, port in procs[-n_nodes:]],
            connection_type=TYPE_SHM, op_timeout_s=30.0, replicas=1,
        )
        for node in rpool.nodes():
            buf = np.random.randint(0, 256, size=ROUND_BYTES,
                                    dtype=np.uint8)
            node.conn.register_mr(buf)
            bufs[node.endpoint] = buf
        mig_keys = [f"mig-L{layer}-c{c}"
                    for layer in range(N_LAYERS) for c in range(CHUNKS)]
        for ep, idxs in rpool.partition(mig_keys).items():
            blocks = [(mig_keys[i], j * PAGE_BYTES)
                      for j, i in enumerate(idxs)]
            rpool.node(ep).conn.write_cache(blocks, PAGE_BYTES,
                                            bufs[ep].ctypes.data)
        spare = start_server(backend="python")
        procs.append(spare)
        spare_ep = f"127.0.0.1:{spare[1]}"

        def _join_and_measure(per_key_only):
            if per_key_only:  # the old path, for the comparison row
                rpool._copy_batch = lambda *a, **kw: None
            try:
                rpool.join_node(spare_ep)
                while not rpool.migration_idle():
                    time.sleep(0.02)
                return rpool.migration_report()
            finally:
                rpool.__dict__.pop("_copy_batch", None)

        rep_new = _join_and_measure(per_key_only=False)
        rpool.drain_node(spare_ep)
        while not rpool.migration_idle():
            time.sleep(0.02)
        # the drained spare still holds the copied bytes — purge so the
        # second join moves real bytes instead of skipping everything
        cfg = ClientConfig(host_addr="127.0.0.1", service_port=spare[1],
                           connection_type=TYPE_SHM, log_level="warning")
        spare_conn = InfinityConnection(cfg)
        spare_conn.connect()
        spare_conn.purge()
        spare_conn.close()
        rep_old = _join_and_measure(per_key_only=True)
        rpool.close()
    finally:
        for proc, _ in procs:
            proc.terminate()
        for proc, _ in procs:
            proc.wait(timeout=10)
    gb = rounds * ROUND_BYTES / 1e9
    return {
        "cluster_nodes": n_nodes,
        "cluster_put_gbps": round(gb / put_t, 3),
        "cluster_get_gbps": round(gb / get_t, 3),
        "migrate_gbps": rep_new.get("migrate_gbps", 0.0),
        "migrate_gbps_per_key": rep_old.get("migrate_gbps", 0.0),
        "migrate_bytes": rep_new.get("bytes", 0),
        "cluster_per_node": {
            ep: {
                "put_gbps": round(s["bytes"] / 1e9 / s["put_s"], 3)
                if s["put_s"] else 0.0,
                "get_gbps": round(s["bytes"] / 1e9 / s["get_s"], 3)
                if s["get_s"] else 0.0,
                "bytes": s["bytes"],
            }
            for ep, s in per_node.items()
        },
    }


def bench_read_latency(port: int, n: int = 400) -> dict:
    """Single-page (64 KiB) read latency percentiles on the zero-copy path —
    the latency half of the driver metric (BASELINE.json: "p50 read
    latency"; VERDICT r2 missing #5)."""
    cfg = ClientConfig(host_addr="127.0.0.1", service_port=port,
                       connection_type=TYPE_SHM, log_level="warning")
    conn = InfinityConnection(cfg)
    conn.connect()
    buf = np.random.randint(0, 256, size=PAGE_BYTES, dtype=np.uint8)
    conn.register_mr(buf)
    ptr = buf.ctypes.data
    conn.write_cache([("lat-page", 0)], PAGE_BYTES, ptr)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        conn.read_cache([("lat-page", 0)], PAGE_BYTES, ptr)
        ts.append(time.perf_counter() - t0)
    conn.delete_keys(["lat-page"])
    conn.close()
    ts.sort()
    return {
        "p50_read_latency_us": round(ts[n // 2] * 1e6, 1),
        "p99_read_latency_us": round(ts[min(int(n * 0.99), n - 1)] * 1e6, 1),
    }


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser("bench.py")
    ap.add_argument("--json-out", default=None, metavar="FILE",
                    help="also write the stable perf-trajectory record "
                         "({run_id, gbps_put, gbps_get, alloc_ms, "
                         "stages:{...}} — docs/observability.md) for the "
                         "measured SHM leg")
    ap.add_argument("--endpoints", type=int, default=0, metavar="N",
                    help="also run the CLUSTER leg: N local store "
                         "instances driven through the consistent-hash "
                         "router, reporting aggregate and per-node GB/s "
                         "(cluster_put_gbps / cluster_get_gbps)")
    args = ap.parse_args(argv)

    proc, port = start_server()
    try:
        # warmup (compilation-free path, but page in the pools)
        bench_conn(TYPE_SHM, port, 1, "warm")
        shm_put, shm_get, shm_stages = bench_conn(TYPE_SHM, port, 6, "shm")
        tcp_put, tcp_get, _ = bench_conn(TYPE_TCP, port, 2, "tcp", force_python=True)
        lat = bench_read_latency(port)
    finally:
        proc.terminate()
        proc.wait(timeout=10)

    cluster = {}
    if args.endpoints:
        cluster = bench_cluster(args.endpoints)
        print(
            "# cluster x{}: put {} get {} GB/s | per-node {}".format(
                cluster["cluster_nodes"], cluster["cluster_put_gbps"],
                cluster["cluster_get_gbps"],
                {ep: f"{s['put_gbps']}/{s['get_gbps']}"
                 for ep, s in cluster["cluster_per_node"].items()},
            ),
            file=sys.stderr,
        )
        print(
            "# reshape: migrate {} GB/s batched vs {} GB/s per-key "
            "({} bytes moved)".format(
                cluster["migrate_gbps"], cluster["migrate_gbps_per_key"],
                cluster["migrate_bytes"],
            ),
            file=sys.stderr,
        )

    tpu = bench_tpu_leg()

    shm_bw = 2 / (1 / shm_put + 1 / shm_get)  # harmonic mean put/get
    tcp_bw = 2 / (1 / tcp_put + 1 / tcp_get)
    print(
        f"# shm put {shm_put:.2f} get {shm_get:.2f} GB/s | "
        f"tcp put {tcp_put:.2f} get {tcp_get:.2f} GB/s",
        file=sys.stderr,
    )
    if tpu:
        print(f"# tpu leg: {json.dumps(tpu)}", file=sys.stderr)
    result = {
        "metric": "llama8b_kv_put_get_bandwidth_shm",
        "value": round(shm_bw, 3),
        "unit": "GB/s",
        "vs_baseline": round(shm_bw / tcp_bw, 2),
        "shm_put_gbps": round(shm_put, 2),
        "shm_get_gbps": round(shm_get, 2),
        **lat,
        **cluster,
    }
    # extra keys: the TPU-in-the-loop numbers (HBM<->store hop, engine
    # tokens/s) when a TPU answered
    result.update({f"tpu_{k}": v for k, v in tpu.items()})
    print(json.dumps(result))
    if args.json_out:
        import uuid

        from infinistore_tpu.benchmark import bench_json

        rec = bench_json(uuid.uuid4().hex[:8], shm_put, shm_get, shm_stages)
        rec.update(lat)  # the latency half rides along (extra keys allowed)
        rec.update(cluster)  # cluster aggregate + per-node, when run
        with open(args.json_out, "w") as f:
            json.dump(rec, f, indent=2)
    if "failed" in tpu:
        # the host numbers above stand, but the run is not a clean one
        sys.exit(1)


if __name__ == "__main__":
    main()
