"""Bytes and FLOPs of the short-convolution / attention decoder with routed
experts (``model_type`` ``lfm2_moe``), from a configuration file's keys alone.

What is counted is what the algorithm needs and no more (``harness/costs.py``
has the rule: undercounting keeps a share honest): every weight once where it
must be read, of the routed experts only those a perfect program must read,
a live token's K and V once, a row's states once each way.

A sequence keeps TWO KINDS of cache.  Its ``full_attention`` layers keep a page:
K and V by head, ``2 x kv heads x head`` values a token a layer (2,048 B at 8
heads of 64 in bfloat16; 4,096 B a token over this cut's 2 such layers).  Its
``conv`` layers keep a STATE: ``v`` at the last ``K - 1`` positions, ``(K - 1) x
hidden`` values a layer (8,192 B) whatever the length, held in a slot; the
device holds ``n_blocks x block_tokens / stride`` slots (``--state-stride`` in
``serve.args``), so a slot's bytes over the stride is what it keeps "per
token" of a state, and with the pages' bytes a token the product with
``n_blocks x block_tokens`` is what the server allocates.

Routed experts at decode: ``batch`` rows choose ``k`` of ``E`` experts each; the
EXPECTED number of distinct experts a step touches in a layer is ``E (1 - (1 -
k/E)^batch)`` under a uniform choice (4 at one row, 25.8 of 64 at eight), as
``counts/latent_moe.py`` reckons."""

from __future__ import annotations

from typing import Sequence, Tuple


def sizes(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    return {
        "L": L, "types": tuple(cfg["layer_types"][:L]),
        "nd": cfg["num_dense_layers"], "d": cfg["hidden_size"],
        "H": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
        "f_dense": cfg["intermediate_size"], "E": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"], "f": cfg["moe_intermediate_size"],
        "K": cfg["conv_L_cache"],
        "scaling": float(cfg["routed_scaling_factor"]),
        "V": cfg["vocab_size"], "eps": cfg["norm_eps"],
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
    }


def n_attn(s: dict) -> int:
    return sum(t == "full_attention" for t in s["types"])


def n_conv(s: dict) -> int:
    return sum(t == "conv" for t in s["types"])


def conv_params(s: dict) -> int:
    """W_in, W_out and the K taps."""
    return 3 * s["d"] * s["d"] + s["d"] * s["d"] + s["d"] * s["K"]


def attn_matmul_params(s: dict) -> int:
    return 2 * s["d"] * s["H"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]


def attn_params(s: dict) -> int:
    return attn_matmul_params(s) + 2 * s["hd"]          # and the two head norms


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def dense_ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["f_dense"]


def operator_params(s: dict) -> int:
    """Every layer's operator and its two norms."""
    return (n_conv(s) * conv_params(s) + n_attn(s) * attn_params(s)
            + 2 * s["L"] * s["d"])


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """All weights as the server holds them: bfloat16 but the router's matrix
    and its selection bias, which are float32; the head is the embedding."""
    s = sizes(cfg)
    n_moe = s["L"] - s["nd"]
    served = (operator_params(s) + s["nd"] * dense_ffn_params(s)
              + n_moe * s["E"] * expert_params(s) + s["V"] * s["d"] + s["d"])
    return dtype_bytes * served + 4 * n_moe * (s["d"] * s["E"] + s["E"])


def stride(cfg: dict) -> int:
    args = cfg["serve"]["args"]
    return int(args[args.index("--state-stride") + 1])


def page_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token over the attention layers."""
    s = sizes(cfg)
    return n_attn(s) * 2 * s["kv"] * s["hd"] * dtype_bytes


def layer_state_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    s = sizes(cfg)
    return (s["K"] - 1) * s["d"] * dtype_bytes


def slot_bytes(cfg: dict) -> int:
    """One sequence's state over the conv layers: a slot, and a checkpoint."""
    return n_conv(sizes(cfg)) * layer_state_bytes(cfg)


def cache_bytes_per_token(cfg: dict) -> int:
    """The pages' bytes a token and a slot's over the stride: with ``n_blocks x
    block_tokens`` the bytes of the pool and of every slot (held by a test)."""
    return page_bytes_per_token(cfg) + slot_bytes(cfg) // stride(cfg)


def store_page_bytes(cfg: dict, block_tokens: int) -> int:
    """What run.py sizes the store's pool and its granule from, "one layer's
    page of one block": here the MEAN over the stack's layers of what a block
    sends to the store, an attention layer's page (32,768 B at 16 tokens) and
    a conv layer's state once a stride (8,192 B / 32 blocks), rounded up, so
    that tokens pushed x this x layers / block covers both kinds."""
    s = sizes(cfg)
    per_block = (page_bytes_per_token(cfg) * block_tokens
                 + -(-slot_bytes(cfg) * block_tokens // stride(cfg)))
    return -(-per_block // s["L"])


def expected_distinct_experts(s: dict, batch: float) -> float:
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** batch) if batch > 0 else 0.0


def decode_step_bytes(cfg: dict, batch: float, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must move: every layer's operator and norms, the
    leading dense FFN, in each expert layer the router and the EXPECTED
    DISTINCT routed experts at ``batch`` rows, the head (the whole embedding),
    ``batch`` rows of it as the embedding, every live token's K and V over the
    attention layers once, and each row's states read and written."""
    s = sizes(cfg)
    n_moe = s["L"] - s["nd"]
    return (dtype_bytes * (operator_params(s) + s["nd"] * dense_ffn_params(s)
                           + n_moe * expected_distinct_experts(s, batch)
                           * expert_params(s)
                           + s["V"] * s["d"] + s["d"] + batch * s["d"])
            + 4 * n_moe * s["d"] * s["E"]
            + live_tokens * page_bytes_per_token(cfg, dtype_bytes)
            + 2 * batch * slot_bytes(cfg))


def active_matmul_params(s: dict) -> int:
    """Weights one token multiplies in the layers."""
    n_moe = s["L"] - s["nd"]
    return (n_conv(s) * 4 * s["d"] * s["d"] + n_attn(s) * attn_matmul_params(s)
            + s["nd"] * dense_ffn_params(s)
            + n_moe * (s["k"] * expert_params(s) + s["d"] * s["E"]))


def decode_step_flops(cfg: dict, batch: float, live_tokens: float) -> float:
    """2 per active weight and the head for each of ``batch`` rows; the
    attention layers' score and weighted sum, 4 x head a query head and live
    token; the convolution's K taps."""
    s = sizes(cfg)
    attn = 4 * s["hd"] * s["H"] * n_attn(s)
    return (2 * batch * (active_matmul_params(s) + s["V"] * s["d"]
                         + n_conv(s) * s["K"] * s["d"]) + attn * live_tokens)


def prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    """Every layer weight once a chunk program, EVERY expert among them (k x
    chunk / E rows an expert: at 512 every expert has rows), and the row's
    states read and written once."""
    s = sizes(cfg)
    n_moe = s["L"] - s["nd"]
    per = (operator_params(s) + s["nd"] * dense_ffn_params(s)
           + n_moe * s["E"] * expert_params(s))
    return (dtype_bytes * per + 4 * n_moe * s["d"] * s["E"]
            + 2 * slot_bytes(cfg)) / chunk


def prefill_flops_per_token(cfg: dict, prompt_lengths: Sequence[Tuple[int, float]],
                            ) -> float:
    """2 per active weight, the convolution's taps, and the attention layers'
    causal attention (4 x head a query head and attended position, S / 2
    positions on average), averaged over ``prompt_lengths`` [(S, weight)] by
    tokens.  Not the head."""
    s = sizes(cfg)
    tok = sum(S * w for S, w in prompt_lengths)
    per_pos = 4 * s["hd"] * s["H"] * n_attn(s)
    attn = sum(S * w * per_pos * (S / 2) for S, w in prompt_lengths) / tok
    return 2 * (active_matmul_params(s) + n_conv(s) * s["K"] * s["d"]) + attn
