"""Bytes and FLOPs of the latent-attention, routed-expert decoder
(``model_type`` ``deepseek_v3``), from a configuration file's keys alone.

What is counted is what the algorithm needs and no more (``harness/costs.py``
has the rule: undercounting keeps a share honest): every weight once where it
must be read, of the routed experts only those a perfect program must read,
the latent page's rows once, no padding, no re-reads, no recomputation.

The page: one row of ``kv_lora_rank + qk_rope_head_dim`` values a token a
layer (512 + 64 = 576; 1,152 B in bfloat16), the normalised latent and the
one rotated key all heads share.  There is no K and V by head.

Routed experts at decode: ``batch`` rows choose ``k`` of ``E`` experts each;
the EXPECTED number of distinct experts a step touches is
``E * (1 - (1 - k/E) ** batch)`` under a uniform choice (6 at one row, 40.8
at eight), never all 128 and never fewer than ``k``.  The weights drawn from a
seed route close to uniformly; the program counts the pairs
(``decode.expert_pairs``) and reports the same expectation from its counted
rows (``decode.experts_expected``)."""

from __future__ import annotations

from typing import Sequence, Tuple


def sizes(cfg: dict) -> dict:
    return {
        "L": cfg["num_hidden_layers"], "nd": cfg["first_k_dense_replace"],
        "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "R": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "f_dense": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"], "f": cfg["moe_intermediate_size"],
        "ns": cfg["n_shared_experts"],
        "scaling": float(cfg["routed_scaling_factor"]),
        "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
    }


def attn_params(s: dict) -> int:
    """q, the joint down-projection, the up-projection, o."""
    return (s["d"] * s["H"] * (s["nope"] + s["rope"]) + s["d"] * (s["R"] + s["rope"])
            + s["R"] * s["H"] * (s["nope"] + s["v"]) + s["H"] * s["v"] * s["d"])


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def shared_params(s: dict) -> int:
    return 3 * s["d"] * s["ns"] * s["f"]


def dense_ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["f_dense"]


def norm_params(s: dict) -> int:
    return 2 * s["d"] + s["R"]          # ln_attn, ln_mlp, the latent's norm


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """All weights as the server holds them: bfloat16 but the router's
    matrix and its selection bias, which are float32."""
    s = sizes(cfg)
    n_moe = s["L"] - s["nd"]
    served = (s["L"] * (attn_params(s) + norm_params(s))
              + s["nd"] * dense_ffn_params(s)
              + n_moe * (s["E"] * expert_params(s) + shared_params(s))
              + 2 * s["V"] * s["d"] + s["d"])
    router = n_moe * (s["d"] * s["E"] + s["E"])
    return dtype_bytes * served + 4 * router


def cache_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    s = sizes(cfg)
    return s["L"] * (s["R"] + s["rope"]) * dtype_bytes


def store_page_bytes(cfg: dict, block_tokens: int) -> int:
    """One layer's page of one block as it goes to the store: the rows of
    ``block_tokens`` tokens, one plane."""
    return cache_bytes_per_token(cfg) * block_tokens // cfg["num_hidden_layers"]


def expected_distinct_experts(s: dict, batch: float) -> float:
    """Distinct routed experts one step of ``batch`` rows touches in one
    layer, in expectation under a uniform choice of k of E."""
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** batch) if batch > 0 else 0.0


def moe_decode_bytes(cfg: dict, batch: float, dtype_bytes: int = 2) -> float:
    """The expert layers' share of a decode step: the experts touched, the
    shared experts, the router."""
    s = sizes(cfg)
    n_moe = s["L"] - s["nd"]
    return n_moe * (dtype_bytes * (expected_distinct_experts(s, batch) * expert_params(s)
                                   + shared_params(s)) + 4 * s["d"] * s["E"])


def mla_decode_bytes(cfg: dict, batch: float, live_tokens: float,
                     dtype_bytes: int = 2) -> float:
    """The attention's share of a decode step: its four matrices in every
    layer, and every live token's row once."""
    s = sizes(cfg)
    return (dtype_bytes * s["L"] * attn_params(s)
            + live_tokens * cache_bytes_per_token(cfg, dtype_bytes))


def decode_step_bytes(cfg: dict, batch: float, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must read: attention and norms of every layer,
    the leading dense FFN, in each expert layer the shared experts, the
    router and the EXPECTED DISTINCT routed experts at ``batch`` rows, the
    lm_head, ``batch`` rows of the embedding, every live token's latent row
    once.  Writes are left out."""
    s = sizes(cfg)
    return (mla_decode_bytes(cfg, batch, live_tokens, dtype_bytes)
            + moe_decode_bytes(cfg, batch, dtype_bytes)
            + dtype_bytes * (s["L"] * norm_params(s) + s["nd"] * dense_ffn_params(s)
                             + s["V"] * s["d"] + s["d"] + batch * s["d"]))


def active_matmul_params(s: dict) -> int:
    """Weights one token multiplies in the layers: attention, the dense FFN
    or k routed + the shared experts and the router."""
    n_moe = s["L"] - s["nd"]
    return (s["L"] * attn_params(s) + s["nd"] * dense_ffn_params(s)
            + n_moe * (s["k"] * expert_params(s) + shared_params(s)
                       + s["d"] * s["E"]))


def decode_step_flops(cfg: dict, batch: float, live_tokens: float) -> float:
    """2 per active weight and the lm_head for each of ``batch`` rows; the
    absorbed attention's 2 * (R + rope) for the score and 2 * R for the
    weighted sum, per head, live token and layer."""
    s = sizes(cfg)
    attn = (2 * (s["R"] + s["rope"]) + 2 * s["R"]) * s["H"] * s["L"]
    return 2 * batch * (active_matmul_params(s) + s["V"] * s["d"]) + attn * live_tokens


def prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    """Bytes per computed token of a ``chunk``-token prefill program: every
    layer weight once a program, EVERY expert among them (6 * chunk / 128
    rows an expert: at chunks of hundreds every expert has rows and none
    fills the matrix unit, so the weights' bytes once a chunk bound it)."""
    s = sizes(cfg)
    n_moe = s["L"] - s["nd"]
    per = (s["L"] * attn_params(s) + s["nd"] * dense_ffn_params(s)
           + n_moe * (s["E"] * expert_params(s) + shared_params(s)))
    return (dtype_bytes * per + 4 * n_moe * s["d"] * s["E"]) / chunk


def moe_prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    s = sizes(cfg)
    n_moe = s["L"] - s["nd"]
    return (n_moe * (dtype_bytes * (s["E"] * expert_params(s) + shared_params(s))
                     + 4 * s["d"] * s["E"])) / chunk


def moe_prefill_flops_per_token(cfg: dict) -> float:
    """k routed + the shared experts + the router, 2 per weight: k/E of the
    all-experts FLOPs."""
    s = sizes(cfg)
    return 2.0 * (s["L"] - s["nd"]) * (s["k"] * expert_params(s) + shared_params(s)
                                       + s["d"] * s["E"])


def prefill_flops_per_token(cfg: dict, prompt_lengths: Sequence[Tuple[int, float]],
                            ) -> float:
    """FLOPs per computed prompt token: 2 per active weight (each token's
    own latent up-projected once), plus expanded causal attention (QK^T over
    nope + rope and PV over v: 2 * (nope + rope + v) * heads per attended
    position, S/2 positions on average), averaged over ``prompt_lengths``
    [(S, weight)] by tokens.  Up-projecting a prefix again for each later
    chunk is recomputation and is not counted; nor is the lm_head."""
    s = sizes(cfg)
    tok = sum(S * w for S, w in prompt_lengths)
    per_pos = 2 * (s["nope"] + s["rope"] + s["v"]) * s["H"] * s["L"]
    attn = sum(S * w * per_pos * (S / 2) for S, w in prompt_lengths) / tok
    return 2 * active_matmul_params(s) + attn
