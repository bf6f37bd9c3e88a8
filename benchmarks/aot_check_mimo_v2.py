#!/usr/bin/env python3
"""The compile check of the window/full family with pages of two shapes and an
expert share (``mimo_v2_flash``): do a cell's widest decode program, its
largest prefill programs and the program that draws the weights fit one v5e
chip beside the weights and the cache, does each pool lie on the device in
the bytes the count gives it (a row of 1,280 or 2,560 values is whole
128-lane tiles: nothing padded), and does a served program COPY a pool?
Asked of the TPU compiler here, without a chip (on-chip-measurement guide,
section 2), before chip time is spent:

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check_mimo_v2.py \\
        --config mimo-v2-flash-l7-e16 --batch 8 --width 2048 --prefix 16384

``aot_check.py`` compiles the dense programs and refuses a configuration
with a ``model`` block; this file compiles the program's own
``mimo_v2_decode_forward`` in a 32-step scan at batch B and block-table
width W over the cache's two pools (five layers gather their window's 9
pages a row out of the window pool, two their pool's whole table, in XLA's
gathered form), its ``mimo_v2_prefill_forward`` on a chunk over the largest
prefix buffers (chunked: the full layers' padded buffer with a traced length
and the window layers' 128 rows; re-ask: a tail over an exact prefix; the
attention one key/value head at a time), and ``init_mimo_v2_params``.  It
prints the compiler's memory analysis, each pool's bytes as the device lays
it out beside the count, the copies of a pool's shape it finds in the
compiled programs, and measures no time.  Exit code 1 where something does
not fit, a pool is padded or a program copies one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(HERE, "harness")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--width", type=int, required=True, help="block-table width, pages")
    ap.add_argument("--prefix", type=int, default=16384, help="prefix buffer, tokens")
    ap.add_argument("--tail", type=int, default=256, help="a re-ask's tail, tokens")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import family
    from infinistore_tpu import models
    from infinistore_tpu.kv import PagedCacheConfig, init_cache

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        spec = json.load(f)
    counts = family.counts(spec)
    model_file = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                              f"aot_{args.config}.{os.getpid()}.json")
    with open(model_file, "w") as f:
        json.dump(family.model_file(spec, 0), f)
    _, cfg, _ = models.load_config_file(model_file)
    os.unlink(model_file)
    fam = models.family_of(cfg)
    sv = spec["serve"]
    chunk = int(sv["args"][sv["args"].index("--prefill-chunk") + 1])
    pc = PagedCacheConfig.for_model(cfg, sv["n_blocks"], sv["block_tokens"],
                                    window_blocks=counts.pool_blocks(spec)[1])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                           sharding=chip), tree)

    params = on_chip(jax.eval_shape(lambda: fam["init"](cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(pc)))
    B, W, T = args.batch, args.width, sv["block_tokens"]
    decode, prefill = fam["fns"]["decode_fn"], fam["fns"]["prefill_fn"]

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jax.tree.map(lambda t: jnp.take_along_axis(
                t, (p // T)[:, None], axis=1)[:, 0], table)
            logits2, cache, _ = decode(
                params, cfg, tokens=tok, positions=p, cache=cache,
                block_table=table, seq_lens=p + 1, slot_block_ids=blocks,
                slot_ids=p % T)
            return (logits2, cache), tok
        (logits, cache), toks = jax.lax.scan(step, (logits, cache), jnp.arange(32))
        return toks, logits, cache

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    window_rows = -(-cfg.sliding_window // T) * T

    def prefix(n, exact=False):
        """One buffer a pool: the full layers' over ``n`` rows, the window
        layers' over their window's rows."""
        rows = (n, min(n, window_rows) if exact else window_rows)
        return tuple(sds((len(layers), 1, 1, r, 1, pc.page_shape_of(p)[-1]),
                         cfg.dtype)
                     for p, ((layers, _), r) in enumerate(zip(pc.pools, rows)))

    import re

    pool_shapes = ["bf16[" + ",".join(map(str, c.shape)) + "]" for c in cache]

    def pool_copies(text):
        """Instructions of the compiled program that produce an array of a
        whole pool's shape by a copy (not the donated pool updated in place)."""
        return [line.strip()[:160] for line in text.splitlines()
                if re.search(r"= (bf16\[[0-9,]+\])[^ ]* copy\(", line)
                and any(s in line.split(" copy(")[0] for s in pool_shapes)]
    weights = counts.weight_bytes(spec)
    cache_b = sv["n_blocks"] * T * counts.cache_bytes_per_token(spec)
    limit = 15.75 * 2**30        # what XLA:TPU reported as usable on a v5e (PR 21)
    print(f"weights {weights / 1e9:.2f} GB + cache {cache_b / 1e9:.2f} GB by the count; "
          f"compiler's HBM limit {limit / 1e9:.2f} GB")
    worst, faults = 0, []
    for name, fn, a, donate in (
        ("init_mimo_v2_params", lambda k: fam["init"](cfg, k),
         (sds((2,), jnp.uint32),), ()),
        (f"decode scan B={B} width={W}", decode_scan,
         (params, sds((B, cfg.vocab_size), cfg.dtype), sds((B,), jnp.int32), cache,
          (sds((B, W), jnp.int32), sds((B, W), jnp.int32))), (3,)),
        (f"prefill chunk {chunk} over a {args.prefix}-token prefix buffer",
         lambda p, t, kv, n: prefill(p, cfg, t, prefix_kv=kv, prefix_len=n),
         (params, sds((1, chunk), jnp.int32), prefix(args.prefix), sds((), jnp.int32)), ()),
        (f"re-ask tail {args.tail} over an exact {args.prefix}-token prefix",
         lambda p, t, kv: prefill(p, cfg, t, prefix_kv=kv),
         (params, sds((1, args.tail), jnp.int32), prefix(args.prefix, True)), ()),
    ):
        compiled = jax.jit(fn, donate_argnums=donate).lower(*a).compile()
        m = compiled.memory_analysis()
        if name.startswith("init"):
            print(f"{name}: out {m.output_size_in_bytes / 1e9:.2f} GB (the weights as "
                  f"the device lays them out), temp {m.temp_size_in_bytes / 1e9:.2f} GB")
            continue
        if donate:
            print(f"  cache as the device lays it out: "
                  f"{m.alias_size_in_bytes} B; by the count "
                  f"{sum(counts.pool_bytes(spec))} B "
                  f"(pools {counts.pool_bytes(spec)})")
            if m.alias_size_in_bytes != sum(counts.pool_bytes(spec)):
                faults.append(f"{name}: the pools are padded on the device")
        copies = pool_copies(compiled.as_text())
        if copies:
            faults.append(f"{name}: copies a pool: {copies[:2]}")
        total = weights + cache_b + m.temp_size_in_bytes
        worst = max(worst, total)
        print(f"{name}: args {m.argument_size_in_bytes / 1e9:.2f} GB, temp "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, out {m.output_size_in_bytes / 1e9:.2f} GB "
              f"(alias {m.alias_size_in_bytes / 1e9:.2f}); weights + cache + temp = "
              f"{total / 1e9:.2f} GB")
    for fault in faults:
        print("FAULT:", fault)
    return 0 if worst <= limit and not faults else 1


if __name__ == "__main__":
    sys.exit(main())
