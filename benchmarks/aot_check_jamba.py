#!/usr/bin/env python3
"""The compile check of the Mamba-1 / attention family: do a cell's widest
decode program, its prefill chunk over its longest prefix and the program that
draws the weights fit one v5e chip beside the weights, the pages and the
float32 state slots?  Asked of the TPU compiler here,
without a chip (on-chip-measurement guide, section 2), before the cell's sizes
are believed:

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check_jamba.py \\
        --config jamba2-3b --batch 8 --width 1024

``aot_check.py`` compiles the dense programs and refuses a configuration with
a ``model`` block; this file compiles the program's own
``jamba_decode_forward`` in a 32-step scan at batch B over a block table of
``--width`` pages and the rows' slots (both kinds of cache donated, as the
engine does), its ``jamba_prefill_forward`` on a full chunk over a prefix
buffer of ``--prefix`` tokens and on a re-ask's tail, and
``init_jamba_params``.  It prints the compiler's memory analysis, the two
caches' bytes as the device lays them out, whether the decode scan holds the
TPU's decode-attention kernel (one key/value head under a group of 20 query
heads), whether the chunk holds the selective-scan kernel, and how many
copies of a run's stacked weights a program makes (the Mamba layers are a
``lax.scan`` over stacked leaves: a copy a layer would double the weights'
read); it measures no time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(HERE, "harness")]


def kernel_calls(text: str, name: str = "paged_decode_attention") -> int:
    """Calls of one of the TPU's kernels in a compiled program."""
    return sum("tpu_custom_call" in line and name in line
               for line in text.splitlines())


def weight_copies(text: str, shapes) -> int:
    """Instructions of a compiled program whose result is a copy or a
    dynamic slice the size of one layer's matrix out of a run's stack."""
    want = {f"bf16[{','.join(map(str, s))}]" for s in shapes}
    n = 0
    for line in text.splitlines():
        head = line.split(" = ", 1)
        if len(head) == 2 and any(
                head[1].startswith(w) and (" copy(" in head[1]
                                           or " dynamic-slice(" in head[1])
                for w in want):
            n += 1
    return n


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--width", type=int, default=1024, help="block table, pages")
    ap.add_argument("--prefix", type=int, default=16384,
                    help="the prefix buffer a chunk attends to, tokens")
    ap.add_argument("--tail", type=int, default=256, help="a re-ask's tail, tokens")
    ap.add_argument("--hlo", default=None, help="write the programs' compiled text here")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import family
    from infinistore_tpu import models
    from infinistore_tpu.kv.cache import HybridCacheConfig, init_cache

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
    pc = HybridCacheConfig.for_model(cfg, sv["n_blocks"], sv["block_tokens"],
                                     arg("--state-stride"), max_rows=args.batch)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                           sharding=chip), tree)

    params = on_chip(jax.eval_shape(lambda: fam["init"](cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(pc)))
    B, T = args.batch, sv["block_tokens"]
    decode, prefill = fam["fns"]["decode_fn"], fam["fns"]["prefill_fn"]

    def decode_scan(params, logits, pos, cache, table):
        def step(carry, i):
            logits, cache = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            p = pos + i
            blocks = jnp.take_along_axis(table[0], (p // T)[:, None], axis=1)[:, 0]
            logits2, cache = decode(
                params, cfg, tokens=tok, positions=p, cache=cache,
                block_table=table, seq_lens=p + 1,
                slot_block_ids=(blocks, table[1][:, 0]), slot_ids=p % T)
            return (logits2, cache), tok
        (logits, cache), toks = jax.lax.scan(step, (logits, cache), jnp.arange(32))
        return toks, logits, cache

    def chunk_fn(p, t, conv, slot, n, buf, plen):
        return prefill(p, cfg, t, conv, slot, n, prefix_kv=buf, prefix_len=plen,
                       head="none")

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    i32 = sds((), jnp.int32)
    buf = sds((len(pc.page_layers), 2, 1, args.prefix, pc.n_kv_heads, pc.head_dim),
              cfg.dtype)
    weights = counts.weight_bytes(spec)
    cache_b = sv["n_blocks"] * T * counts.cache_bytes_per_token(spec)
    limit = 15.75 * 2**30        # what XLA:TPU reported as usable on a v5e (PR 21)
    print(f"weights {weights / 1e9:.2f} GB + pages and {pc.n_slots} state slots "
          f"{cache_b / 1e9:.2f} GB by the count ({pc.cache_bytes / 1e9:.2f} GB as the "
          f"program allocates); compiler's HBM limit {limit / 1e9:.2f} GB")
    worst = 0
    d, di = cfg.dim, cfg.d_inner
    matrices = ((d, 2 * di), (di, d), (d, cfg.ffn_dim), (cfg.ffn_dim, d))
    for name, fn, a, donate in (
        ("init_jamba_params", lambda k: fam["init"](cfg, k),
         (sds((2,), jnp.uint32),), ()),
        (f"decode scan B={B} width={args.width}", decode_scan,
         (params, sds((B, cfg.vocab_size), cfg.dtype), sds((B,), jnp.int32), cache,
          (sds((B, args.width), jnp.int32), sds((B, 1), jnp.int32))), (3,)),
        (f"prefill chunk {chunk} over {args.prefix}", chunk_fn,
         (params, sds((1, chunk), jnp.int32), cache[1], i32, i32, buf, i32), (2,)),
        (f"re-ask tail {args.tail} over {args.prefix}", chunk_fn,
         (params, sds((1, args.tail), jnp.int32), cache[1], i32, i32, buf, i32), (2,)),
    ):
        compiled = jax.jit(fn, donate_argnums=donate).lower(*a).compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            with open(os.path.join(args.hlo, name.replace(" ", "_") + ".txt"), "w") as f:
                f.write(text)
        if name.startswith("init"):
            print(f"{name}: out {m.output_size_in_bytes / 1e9:.2f} GB (the weights as "
                  f"the device lays them out), temp {m.temp_size_in_bytes / 1e9:.2f} GB")
            continue
        if name.startswith("decode"):
            print(f"  pages and slots as the device lays them out: "
                  f"{m.alias_size_in_bytes / 1e9:.2f} GB; decode-attention kernel "
                  f"in the program: {kernel_calls(text)} calls")
            laid = m.alias_size_in_bytes
        else:
            print(f"  selective-scan kernel in the program: "
                  f"{kernel_calls(text, 'ssm_selective_scan')} calls")
        print(f"  copies or slices of one layer's matrix out of a run's stack: "
              f"{weight_copies(text, matrices)}")
        total = weights + max(cache_b, laid) + m.temp_size_in_bytes + (
            0 if name.startswith("decode") else 2 * buf.size * 2)
        worst = max(worst, total)
        print(f"{name}: args {m.argument_size_in_bytes / 1e9:.2f} GB, temp "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, out {m.output_size_in_bytes / 1e9:.2f} GB "
              f"(alias {m.alias_size_in_bytes / 1e9:.2f}); weights + caches + temp"
              f"{'' if name.startswith('decode') else ' + two prefix buffers'} = "
              f"{total / 1e9:.2f} GB")
    return 0 if worst <= limit else 1


if __name__ == "__main__":
    sys.exit(main())
