#!/usr/bin/env python3
"""Does a cell's widest decode program, and its largest prefill program, fit
one v5e chip beside the weights and the cache?  Asked of the TPU compiler
here, without a chip (on-chip-measurement guide, section 2), before chip time
is spent on the cell:

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check.py --config qwen2.5-7b-l12 --batch 64 --width 512

It compiles the program's own ``decode_forward`` in a 32-step scan (as
``engine._decode_many`` runs it) at batch B and block-table width W, and its
``prefill_forward`` on a 512-token chunk over the largest prefix buffer, and
prints the compiler's memory analysis.  It measures no time.

The programs it compiles are the dense decoder's: a configuration that is not
served from a dense preset (it carries a ``model`` block) is refused here, and
its family brings a compile check of its own as a new file beside this one.
Weights and cache are counted as run.py counts them (``harness/family.py``).
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
    ap.add_argument("--prefix", type=int, default=4096, help="prefix buffer, tokens")
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
    if "model" in spec or "preset" not in spec:
        print(f"{args.config} is not served from a dense preset: this tool compiles "
              f"the dense programs; its family brings its own compile check",
              file=sys.stderr)
        return 2
    counts = family.counts(spec)
    cfg = models.scaled(getattr(models, spec["preset"]),
                        n_layers=spec["reduced"]["n_layers"])
    sv = spec["serve"]
    pc = PagedCacheConfig(n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.head_dim, n_blocks=sv["n_blocks"],
                          block_tokens=sv["block_tokens"], dtype=cfg.dtype)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                           sharding=chip), tree)

    params = on_chip(jax.eval_shape(lambda: models.init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(pc)))
    B, W, T = args.batch, args.width, sv["block_tokens"]

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jnp.take_along_axis(table, (p // T)[:, None], axis=1)[:, 0]
            logits2, cache = models.decode_forward(
                params, cfg, tokens=tok, positions=p, cache=cache,
                block_table=table, seq_lens=p + 1, slot_block_ids=blocks,
                slot_ids=p % T, use_pallas=False)
            return (logits2, cache), tok
        (logits, cache), toks = jax.lax.scan(step, (logits, cache), jnp.arange(32))
        return toks, logits, cache

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    weights = counts.weight_bytes(spec)
    cache_b = sv["n_blocks"] * T * counts.cache_bytes_per_token(spec)
    limit = 15.75 * 2**30        # what XLA:TPU reported as usable on a v5e (PR 21)
    print(f"weights {weights / 1e9:.2f} GB + cache {cache_b / 1e9:.2f} GB; "
          f"compiler's HBM limit {limit / 1e9:.2f} GB")
    for name, fn, a, donate in (
        (f"decode scan B={B} width={W}", decode_scan,
         (params, sds((B, cfg.vocab_size), cfg.dtype), sds((B,), jnp.int32), cache,
          sds((B, W), jnp.int32)), (3,)),
        (f"prefill chunk 512 over a {args.prefix}-token prefix buffer",
         lambda p, t, kv, n: models.prefill_forward(p, cfg, t, prefix_kv=kv,
                                                    prefix_len=n, use_pallas=False),
         (params, sds((1, 512), jnp.int32),
          sds((cfg.n_layers, 2, 1, args.prefix, cfg.n_kv_heads, cfg.head_dim), cfg.dtype),
          sds((), jnp.int32)), ()),
    ):
        m = jax.jit(fn, donate_argnums=donate).lower(*a).compile().memory_analysis()
        beside = m.temp_size_in_bytes + (0 if donate else cache_b)
        print(f"{name}: args {m.argument_size_in_bytes / 1e9:.2f} GB, temp "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, out {m.output_size_in_bytes / 1e9:.2f} GB "
              f"(alias {m.alias_size_in_bytes / 1e9:.2f}); weights + cache + temp = "
              f"{(weights + cache_b + m.temp_size_in_bytes) / 1e9:.2f} GB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
