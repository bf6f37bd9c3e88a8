"""Continuous-batching serving with store-backed prefix reuse.

Boots an engine on the TINY Llama config (swap in models/hf.py
``params_from_hf`` + a real checkpoint for production shapes), submits a mix
of greedy and sampled requests to the scheduler, and — when a store server
is reachable — shows a second engine reusing the first one's prefilled KV
through the store (the reference's LMCache prefix-reuse deployment,
reference docs/source/design.rst).

Usage:
    python examples/serving.py [--service-port 22345]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

import infinistore_tpu as ist
from infinistore_tpu.engine import InferenceEngine, Scheduler
from infinistore_tpu.kv import PagedCacheConfig
from infinistore_tpu.models import TINY, init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--service-port", type=int, default=0,
                    help="store server data port (0 = run without a store)")
    ap.add_argument("--host", default="127.0.0.1")
    args = ap.parse_args()

    conn = None
    if args.service_port:
        conn = ist.InfinityConnection(ist.ClientConfig(
            host_addr=args.host, service_port=args.service_port,
            connection_type=ist.TYPE_SHM))
        conn.connect()

    cfg = TINY
    params = init_params(cfg, jax.random.PRNGKey(0))
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, block_tokens=16, n_blocks=256,
    )
    engine = InferenceEngine(params, cfg, pc, conn=conn, prefill_chunk=64)
    sched = Scheduler(engine, max_batch=4)

    prompts = {
        "a": list(range(1, 40)),
        "b": list(range(1, 12)),
        "c": [7, 99, 404, 42],
    }
    ids = {}
    for name, p in prompts.items():
        ids[name] = sched.submit(p, 32)
    ids["sampled"] = sched.submit(
        prompts["a"], 32, sample="categorical", temperature=0.8, top_k=40)
    # streamed request: tokens arrive at every decode-chunk boundary
    streamed: list = []
    ids["streamed"] = sched.submit(
        prompts["b"], 16,
        on_token=lambda toks, done: streamed.append((len(toks), done)))

    t0 = time.time()
    out = sched.run()
    dt = time.time() - t0
    n_tok = sum(len(v) for v in out.values())
    print(f"{len(out)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s aggregate)")
    for name, rid in ids.items():
        print(f"  {name:8s} -> {out[rid][:8]}...")
    print(f"  streamed deliveries (n_tokens, done): {streamed}")

    if conn is not None:
        eng2 = InferenceEngine(params, cfg, pc, conn=conn)
        st = eng2.prefill(prompts["a"])
        print(f"second engine reused {st.reused_chunks} stored chunks "
              f"of prompt 'a' from the store")
    return 0


if __name__ == "__main__":
    sys.exit(main())
