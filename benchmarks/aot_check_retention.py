#!/usr/bin/env python3
"""The compile check of the power-retention family: do a cell's widest decode
program, its prefill chunk and the program that draws the weights fit one v5e
chip beside the weights and the state slots?  Asked of the TPU compiler here,
without a chip (on-chip-measurement guide, section 2), before chip time is
spent:

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check_retention.py \\
        --config brumby-14b-l8 --batch 8

``aot_check.py`` compiles the dense programs and refuses a configuration
with a ``model`` block; this file compiles the program's own
``retention_decode_forward`` in a 32-step scan at batch B over the slots,
its ``retention_prefill_forward`` on a full chunk and on a re-ask's tail (both
donate the slots, as the engine does), and ``init_retention_params``.  It
prints the compiler's memory analysis and the slots' bytes as the device lays
them out, and measures no time.  The programs' shapes do not depend on a
sequence's length: a state does not grow.
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
    ap.add_argument("--tail", type=int, default=256, help="a re-ask's tail, tokens")
    ap.add_argument("--hlo", default=None, help="write the programs' compiled text here")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import family
    from infinistore_tpu import models
    from infinistore_tpu.kv.cache import StateCacheConfig, init_cache

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
    arg = lambda name: int(sv["args"][sv["args"].index(name) + 1])
    chunk = arg("--prefill-chunk")
    pc = StateCacheConfig.for_model(cfg, sv["n_blocks"], sv["block_tokens"],
                                    arg("--state-stride"), max_rows=args.batch)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                           sharding=chip), tree)

    params = on_chip(jax.eval_shape(lambda: fam["init"](cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(pc)))
    B = args.batch
    decode, prefill = fam["fns"]["decode_fn"], fam["fns"]["prefill_fn"]

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            logits2, cache = decode(
                params, cfg, tokens=tok, positions=p, cache=cache,
                block_table=table, seq_lens=p + 1, slot_block_ids=None,
                slot_ids=None)
            return (logits2, cache), tok
        (logits, cache), toks = jax.lax.scan(step, (logits, cache), jnp.arange(32))
        return toks, logits, cache

    def chunk_fn(p, t, c, slot, start, n):
        return prefill(p, cfg, t, c, slot, start, n)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    i32 = sds((), jnp.int32)
    weights = counts.weight_bytes(spec)
    cache_b = sv["n_blocks"] * sv["block_tokens"] * counts.cache_bytes_per_token(spec)
    limit = 15.75 * 2**30        # what XLA:TPU reported as usable on a v5e (PR 21)
    print(f"weights {weights / 1e9:.2f} GB + {pc.n_slots} state slots "
          f"{cache_b / 1e9:.2f} GB by the count; compiler's HBM limit {limit / 1e9:.2f} GB")
    worst = 0
    for name, fn, a, donate in (
        ("init_retention_params", lambda k: fam["init"](cfg, k),
         (sds((2,), jnp.uint32),), ()),
        (f"decode scan B={B}", decode_scan,
         (params, sds((B, cfg.vocab_size), cfg.dtype), sds((B,), jnp.int32), cache,
          sds((B, 1), jnp.int32)), (3,)),
        (f"prefill chunk {chunk}", chunk_fn,
         (params, sds((1, chunk), jnp.int32), cache, i32, i32, i32), (2,)),
        (f"re-ask tail {args.tail}", chunk_fn,
         (params, sds((1, args.tail), jnp.int32), cache, i32, i32, i32), (2,)),
    ):
        compiled = jax.jit(fn, donate_argnums=donate).lower(*a).compile()
        m = compiled.memory_analysis()
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            with open(os.path.join(args.hlo, name.replace(" ", "_") + ".txt"), "w") as f:
                f.write(compiled.as_text())
        if name.startswith("init"):
            print(f"{name}: out {m.output_size_in_bytes / 1e9:.2f} GB (the weights as "
                  f"the device lays them out), temp {m.temp_size_in_bytes / 1e9:.2f} GB")
            continue
        print(f"  slots as the device lays them out: {m.alias_size_in_bytes / 1e9:.2f} GB")
        total = weights + cache_b + m.temp_size_in_bytes
        worst = max(worst, total)
        print(f"{name}: args {m.argument_size_in_bytes / 1e9:.2f} GB, temp "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, out {m.output_size_in_bytes / 1e9:.2f} GB "
              f"(alias {m.alias_size_in_bytes / 1e9:.2f}); weights + slots + temp = "
              f"{total / 1e9:.2f} GB")
    return 0 if worst <= limit else 1


if __name__ == "__main__":
    sys.exit(main())
