#!/usr/bin/env python3
"""Pallas kernel tuning sweep: block sizes / layouts vs XLA, on chip.

Both Pallas attention kernels ship opt-in-OFF; whether either beats XLA
is not measured on a directly attached chip (ROADMAP A6).  This script
is the entry for that measurement: one command sweeps the tunable
surface —
flash ``block_q``/``block_k`` tiles over the Mosaic acceptance shapes,
the paged-decode kernel (ours and, when requested, jax's bundled
production kernel via the model-layer flag) against XLA across context
lengths — and writes a bench-schema JSON so the verdict is a table, not
an afternoon of ad-hoc timing.

    # on a TPU host
    python scripts/pallas_tune.py --json-out pallas_tune.json

    # CPU structural smoke (interpret mode, tiny shapes — validates the
    # sweep plumbing, NOT kernel performance)
    JAX_PLATFORMS=cpu python scripts/pallas_tune.py --force --json-out t.json

Methodology: timed regions chain iterations through evolving inputs (no
two dispatches are identical) and end in a data fetch (the result is
consumed inside the region); every timing is median-of-N with the
relative spread recorded next to it.  Without a TPU (and without
``--force``) the script exits 1 and writes no record: a run that
measured nothing must not read as a capture.

Output schema (``--json-out``, bench family; docs/observability.md
§bench-json): ``{run_id, kind: "pallas_tune", platform, device_kind,
tpu, flash: [{block_q, block_k, shape, t_ms, spread, vs_xla}],
decode: [{ctx, kernel, t_ms, spread, vs_xla}], best: {...}}`` —
``vs_xla > 1`` means the kernel beat XLA at that point; ``best``
summarizes the winning config per family, the number the
``pallas_speedup_vs_xla`` staged assert (bench_tpu.py) settles on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import uuid

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _median_spread(measure, n: int):
    vals = sorted(measure() for _ in range(max(1, n)))
    med = vals[len(vals) // 2]
    spread = (vals[-1] - vals[0]) / med if med > 0 else 0.0
    return med, round(spread, 3)


def _fetch(x) -> float:
    """End a timed region by pulling a scalar reduction to the host."""
    import jax.numpy as jnp

    return float(jnp.sum(x.astype(jnp.float32)))


def _time_chained(step, x0, iters: int) -> float:
    """Seconds/iteration of ``x = step(x)``: the chain defeats dispatch
    memoization, the final fetch defeats optimistic completion."""
    x = step(x0)  # warm (compile)
    _fetch(x)
    t0 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
    _fetch(x)
    return (time.perf_counter() - t0) / iters


def sweep_flash(interpret: bool, small: bool, iters: int, repeats: int):
    """Flash causal prefill: (block_q, block_k) tile sweep vs XLA at the
    Mosaic acceptance shape (B=1, S=512, H=32, Hkv=8, D=128) and a 2k
    long-prompt point."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.models.attention import causal_attention
    from infinistore_tpu.ops import flash_causal_attention_pallas

    rng = np.random.default_rng(0)
    shapes = [(1, 128, 4, 2, 128)] if small else [
        (1, 512, 32, 8, 128),   # the Mosaic acceptance shape
        (1, 2048, 32, 8, 128),  # long-prompt point (r5 flash leg shape)
    ]
    blocks = [(128, 128)] if small else [
        (128, 128), (256, 128), (128, 256), (256, 256), (512, 256),
    ]
    dtype = jnp.float32 if small else jnp.bfloat16
    results = []
    for B, S, H, Hkv, D in shapes:
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
        k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
        v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)

        def xla_step(x):
            return causal_attention(q + x[0, 0, 0, 0] * 1e-6, k, v,
                                    allow_pallas=False)

        t_xla, sp_xla = _median_spread(
            lambda: _time_chained(xla_step, q, iters), repeats)
        for bq, bk in blocks:
            if bq > S:
                continue

            def pl_step(x, _bq=bq, _bk=bk):
                return flash_causal_attention_pallas(
                    q + x[0, 0, 0, 0] * 1e-6, k, v,
                    block_q=_bq, block_k=_bk, interpret=interpret)

            try:
                t_pl, sp_pl = _median_spread(
                    lambda: _time_chained(pl_step, q, iters), repeats)
            except Exception as e:  # noqa: BLE001 — Mosaic rejection is data
                results.append({
                    "shape": [B, S, H, Hkv, D], "block_q": bq,
                    "block_k": bk, "error": repr(e)[:160],
                })
                continue
            results.append({
                "shape": [B, S, H, Hkv, D], "block_q": bq, "block_k": bk,
                "t_ms": round(t_pl * 1e3, 3), "spread": sp_pl,
                "xla_t_ms": round(t_xla * 1e3, 3), "xla_spread": sp_xla,
                "vs_xla": round(t_xla / t_pl, 3) if t_pl > 0 else None,
            })
    return results


def sweep_decode(interpret: bool, small: bool, iters: int, repeats: int):
    """Paged decode attention: our kernel (and jax's bundled one where
    available on chip) vs XLA across context lengths at the serving
    head config (Hkv=8, D=128, T=16, B=4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.models.attention import paged_decode_attention_xla
    from infinistore_tpu.ops import paged_decode_attention_pallas

    rng = np.random.default_rng(1)
    Hkv, D, T = (2, 128, 16) if small else (8, 128, 16)
    H = Hkv * 4
    B = 2 if small else 4
    ctxs = [32] if small else [64, 512, 1536]
    results = []
    for ctx in ctxs:
        n_pages = -(-ctx // T)
        n_blocks = B * n_pages + 1
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        cache = jnp.asarray(
            rng.standard_normal((2, Hkv, n_blocks, T, D)), jnp.float32)
        table = np.zeros((B, n_pages), np.int32)
        for b in range(B):
            table[b] = np.arange(1 + b * n_pages, 1 + (b + 1) * n_pages)
        table = jnp.asarray(table)
        lens = jnp.full((B,), ctx, jnp.int32)

        def xla_step(x):
            return paged_decode_attention_xla(
                q + x[0, 0, 0] * 1e-6, cache[None], 0, table, lens)

        def pl_step(x):
            return paged_decode_attention_pallas(
                q + x[0, 0, 0] * 1e-6, cache, table, lens,
                interpret=interpret)

        t_xla, sp_xla = _median_spread(
            lambda: _time_chained(xla_step, q, iters), repeats)
        try:
            t_pl, sp_pl = _median_spread(
                lambda: _time_chained(pl_step, q, iters), repeats)
        except Exception as e:  # noqa: BLE001
            results.append({"ctx": ctx, "kernel": "istpu",
                            "error": repr(e)[:160]})
            continue
        results.append({
            "ctx": ctx, "kernel": "istpu",
            "t_ms": round(t_pl * 1e3, 3), "spread": sp_pl,
            "xla_t_ms": round(t_xla * 1e3, 3), "xla_spread": sp_xla,
            "vs_xla": round(t_xla / t_pl, 3) if t_pl > 0 else None,
        })
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("pallas_tune.py")
    ap.add_argument("--json-out", default=None, metavar="FILE")
    ap.add_argument("--iters", type=int, default=20,
                    help="chained iterations per timing")
    ap.add_argument("--repeats", type=int, default=3,
                    help="median-of-N repeats per config")
    ap.add_argument("--force", action="store_true",
                    help="run on whatever backend is present (CPU smoke "
                         "via interpret mode, tiny shapes)")
    args = ap.parse_args(argv)

    import jax

    platform = jax.devices()[0].platform
    record = {
        "run_id": uuid.uuid4().hex[:8],
        "kind": "pallas_tune",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "tpu": platform == "tpu",
    }
    if platform != "tpu" and not args.force:
        print(f"no TPU (platform {platform!r}); re-run on the chip, or "
              f"--force for a CPU interpret-mode structural smoke",
              file=sys.stderr)
        return 1

    interpret = platform != "tpu"
    small = interpret
    t0 = time.time()
    record["flash"] = sweep_flash(interpret, small, args.iters,
                                  args.repeats)
    record["decode"] = sweep_decode(interpret, small, args.iters,
                                    args.repeats)
    best = {}
    flash_ok = [r for r in record["flash"] if r.get("vs_xla")]
    if flash_ok:
        win = max(flash_ok, key=lambda r: r["vs_xla"])
        best["flash"] = {k: win[k] for k in
                         ("shape", "block_q", "block_k", "vs_xla")}
    dec_ok = [r for r in record["decode"] if r.get("vs_xla")]
    if dec_ok:
        win = max(dec_ok, key=lambda r: r["vs_xla"])
        best["decode"] = {k: win[k] for k in ("ctx", "kernel", "vs_xla")}
        if not interpret:
            # the headline the staged on-chip assert
            # (pallas_speedup_vs_xla >= 1.0) settles on — real-chip
            # numbers only; interpret-mode timings are not kernel perf
            record["pallas_speedup_vs_xla"] = win["vs_xla"]
    record["best"] = best
    record["wall_s"] = round(time.time() - t0, 1)
    if interpret:
        # interpret-mode timings are NOT kernel performance — mark the
        # record so no trend table ever ingests them as such
        record["interpret_smoke"] = True
    print(json.dumps(record))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
