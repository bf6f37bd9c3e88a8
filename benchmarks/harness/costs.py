"""Operations and bytes the algorithm needs, from shapes alone, and the table
of peaks.  Undercounting keeps a share honest (under 100%); overcounting
does not, so nothing here counts padding, re-reads or recomputation.

``peaks`` and ``share_pct`` are common to every model family.  The counts
below them are the dense grouped-query decoder's: the default of a
configuration that names no ``costs`` module.  Another family's counts are a
module of their own under ``benchmarks/counts/``, found by ``family.counts``;
nothing here is edited for one."""

from __future__ import annotations

import json
import os
from typing import Dict, Sequence, Tuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NotCounted(AttributeError):
    """A family's count module does not count this quantity: the metric that
    needs it is left out of the cell, never read with another family's count."""


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"add it to benchmarks/peaks.json with its source")
    return table[device_kind]


def sizes(cfg: dict) -> dict:
    """The widths the counts need, from a configuration file's HF keys."""
    knobs = cfg.get("knobs", {})
    return {
        "L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
        "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": (cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"]),
        "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "bias": bool(knobs.get("qkv_bias")),
        "qk_norm": bool(knobs.get("qk_norm")),
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
    }


def layer_matmul_params(s: dict) -> int:
    qkv = s["d"] * s["hd"] * (s["h"] + 2 * s["kv"])
    return qkv + s["h"] * s["hd"] * s["d"] + 3 * s["d"] * s["f"]


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    s = sizes(cfg)
    return 2 * s["L"] * s["kv"] * s["hd"] * dtype_bytes


cache_bytes_per_token = kv_bytes_per_token    # the name every family's module gives it


def store_page_bytes(cfg: dict, block_tokens: int) -> int:
    """Bytes of one page as it goes to the store: one layer's K and V of one
    block of tokens."""
    return kv_bytes_per_token(cfg) * block_tokens // cfg["num_hidden_layers"]


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """All weights as the server holds them: layers, embedding, lm_head
    (untied), norms and biases."""
    s = sizes(cfg)
    per_layer = layer_matmul_params(s) + 2 * s["d"]
    if s["bias"]:
        per_layer += s["hd"] * (s["h"] + 2 * s["kv"])
    if s["qk_norm"]:
        per_layer += 2 * s["hd"]
    return dtype_bytes * (s["L"] * per_layer + 2 * s["V"] * s["d"] + s["d"])


def decode_step_bytes(cfg: dict, batch: float, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must read: every weight once except the
    embedding table, of which ``batch`` rows are read; K and V of every live
    token once.  Writes (one KV row per sequence) are left out."""
    s = sizes(cfg)
    embed = dtype_bytes * s["V"] * s["d"]
    return (weight_bytes(cfg, dtype_bytes) - embed
            + batch * s["d"] * dtype_bytes
            + live_tokens * kv_bytes_per_token(cfg, dtype_bytes))


def decode_step_flops(cfg: dict, batch: float, live_tokens: float) -> float:
    """FLOPs one decode step needs: 2 per weight of the layer matmuls and of
    the lm_head for each of ``batch`` rows, and 4 * head_dim * heads per live
    token and layer for attention."""
    s = sizes(cfg)
    return (2 * batch * (s["L"] * layer_matmul_params(s) + s["V"] * s["d"])
            + 4 * s["hd"] * s["h"] * s["L"] * live_tokens)


def prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    """Bytes per computed token of a ``chunk``-token prefill program: every
    layer weight once per program (the KV it writes and reads is left out)."""
    s = sizes(cfg)
    return dtype_bytes * s["L"] * layer_matmul_params(s) / chunk


def prefill_flops_per_token(cfg: dict, prompt_lengths: Sequence[Tuple[int, float]],
                            ) -> float:
    """FLOPs per computed prompt token: 2 per weight of the layer matmuls,
    plus causal attention (QK^T and PV: 4 * head_dim * heads per attended
    position, S/2 positions on average in a prompt of S), averaged over
    ``prompt_lengths`` [(S, weight)] by tokens.  The lm_head is left out: it
    is needed for the last position only."""
    s = sizes(cfg)
    matmul = 2 * s["L"] * layer_matmul_params(s)
    tok = sum(S * w for S, w in prompt_lengths)
    attn = sum(S * w * 4 * s["hd"] * s["h"] * s["L"] * (S / 2)
               for S, w in prompt_lengths) / tok
    return matmul + attn


def share_pct(needed_s: float, measured_s: float, what: str) -> float:
    """A share of a peak in percent.  Over 100% the count is wrong or the
    time leaves work out: raise, never clip."""
    pct = 100.0 * needed_s / measured_s
    if pct > 100.0:
        raise ValueError(f"{what}: {pct:.1f}% of peak; the bytes or FLOPs "
                         f"are overcounted or the time leaves out work")
    return pct
