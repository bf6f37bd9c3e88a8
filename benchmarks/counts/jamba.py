"""Bytes and FLOPs of the Mamba-1 / attention decoder (``model_type`` ``jamba``
at ``num_experts`` 1), from a configuration file's keys alone.

What is counted is what the algorithm needs and no more (``harness/costs.py``
has the rule: undercounting keeps a share honest): every weight once where it
must be read, a live token's K and V once, a row's states once each way.

A sequence keeps TWO KINDS of cache.  Its attention layers (``i %
attn_layer_period == attn_layer_offset``) keep a page: K and V of ONE
key/value head of 128, 512 B a token a layer in bfloat16 (1,024 B a token over
the two such layers).  Its Mamba layers keep a STATE: the recurrence's ``s``
``[d_state, d_inner]`` and the conv's last ``d_conv - 1`` inputs ``[d_conv - 1,
d_inner]``, held as ONE float32 width a layer, ``(16 + 3) x 5120 x 4 = 389,120 B``
(the conv's rows are bfloat16 values, which float32 holds exactly; at their own
type a layer's state would be 358,400 B), whatever the length, in a slot; the
device holds ``n_blocks x block_tokens / stride`` slots (``--state-stride`` in
``serve.args``), so a slot's bytes over the stride is what it keeps "per
token" of a state, and with the pages' bytes a token the product with
``n_blocks x block_tokens`` is what the server allocates.

The selective scan itself (``scan_flops_per_token``, ``scan_bytes_per_token``,
a token a layer) is elementwise and exponential work on the vector unit:
``kernel.ssm_scan_roofline`` holds its time to these at the chip's MATRIX peak
and bandwidth, the only peaks ``peaks.json`` publishes, and so reads in low
single digits by construction."""

from __future__ import annotations

from typing import Sequence, Tuple


def sizes(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    d = cfg["hidden_size"]
    return {
        "L": L, "d": d, "V": cfg["vocab_size"],
        "types": tuple("attention" if i % period == offset else "mamba"
                       for i in range(L)),
        "H": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": d // cfg["num_attention_heads"], "f": cfg["intermediate_size"],
        "di": cfg["mamba_expand"] * d, "N": cfg["mamba_d_state"],
        "K": cfg["mamba_d_conv"], "R": cfg["mamba_dt_rank"],
        "eps": cfg["rms_norm_eps"],
    }


def n_attn(s: dict) -> int:
    return sum(t == "attention" for t in s["types"])


def n_mamba(s: dict) -> int:
    return sum(t == "mamba" for t in s["types"])


def mamba_matmul_params(s: dict) -> int:
    """W_in, W_x, W_dt, W_out."""
    return (s["d"] * 2 * s["di"] + s["di"] * (s["R"] + 2 * s["N"])
            + s["R"] * s["di"] + s["di"] * s["d"])


def mamba_f32_params(s: dict) -> int:
    """b_dt, A_log and D, which the server holds in float32."""
    return s["di"] + s["di"] * s["N"] + s["di"]


def mamba_params(s: dict) -> int:
    """A Mamba mixer: its matrices, the conv's taps and bias, the three inner
    norms, and the float32 leaves."""
    return (mamba_matmul_params(s) + s["di"] * s["K"] + s["di"]
            + s["R"] + 2 * s["N"] + mamba_f32_params(s))


def attn_params(s: dict) -> int:
    return 2 * s["d"] * s["H"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]


def ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def layer_params(s: dict) -> int:
    """Every layer's mixer, its SwiGLU and its two norms."""
    return (n_mamba(s) * mamba_params(s) + n_attn(s) * attn_params(s)
            + s["L"] * (ffn_params(s) + 2 * s["d"]))


def n_params(cfg: dict) -> int:
    """Every parameter once; the head is the embedding."""
    s = sizes(cfg)
    return layer_params(s) + s["V"] * s["d"] + s["d"]


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """All weights as the server holds them: bfloat16 but ``b_dt``, ``A_log``
    and ``D``, which are float32."""
    s = sizes(cfg)
    f32 = n_mamba(s) * mamba_f32_params(s)
    return dtype_bytes * (n_params(cfg) - f32) + 4 * f32


def stride(cfg: dict) -> int:
    args = cfg["serve"]["args"]
    return int(args[args.index("--state-stride") + 1])


def page_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token over the attention layers."""
    s = sizes(cfg)
    return n_attn(s) * 2 * s["kv"] * s["hd"] * dtype_bytes


def layer_state_bytes(cfg: dict) -> int:
    """One Mamba layer's state of one sequence as a slot holds it and as it
    goes to the store: one float32 width."""
    s = sizes(cfg)
    return (s["N"] + s["K"] - 1) * s["di"] * 4


def slot_bytes(cfg: dict) -> int:
    """One sequence's state over the Mamba layers: a slot, and a checkpoint."""
    return n_mamba(sizes(cfg)) * layer_state_bytes(cfg)


def cache_bytes_per_token(cfg: dict) -> int:
    """The pages' bytes a token and a slot's over the stride: with ``n_blocks x
    block_tokens`` the bytes of the pool and of every slot (held by a test)."""
    return page_bytes_per_token(cfg) + slot_bytes(cfg) // stride(cfg)


def store_page_bytes(cfg: dict, block_tokens: int) -> int:
    """What run.py sizes the store's pool and its granule from, "one layer's
    page of one block": here the MEAN over the stack's layers of what a block
    sends to the store, an attention layer's page (8,192 B at 16 tokens) and a
    Mamba layer's state once a stride, rounded up, so that tokens pushed x
    this x layers / block covers both kinds."""
    s = sizes(cfg)
    per_block = (page_bytes_per_token(cfg) * block_tokens
                 + -(-slot_bytes(cfg) * block_tokens // stride(cfg)))
    return -(-per_block // s["L"])


def scan_flops_per_token(cfg: dict) -> float:
    """The recurrence of one token in one Mamba layer: per channel and state
    ``dt A`` (1), the decay's product with the state (1), ``dt x B`` (1, ``dt x``
    once a channel), the sum (1), ``s C`` and its sum over the states (2): 6 a
    (channel, state); the exponential is not counted as a FLOP."""
    s = sizes(cfg)
    return 6.0 * s["di"] * s["N"] + s["di"]


def scan_bytes_per_token(cfg: dict, chunk: int = 512) -> float:
    """What the scan must move for one token of one Mamba layer in a chunk of
    ``chunk``: ``x`` and ``dt`` read and ``y`` written in float32, ``B`` and ``C``
    read, and the state and ``A`` read and the state written once a CHUNK."""
    s = sizes(cfg)
    return (3 * 4 * s["di"] + 2 * 4 * s["N"]
            + 3 * 4 * s["di"] * s["N"] / chunk)


def decode_step_bytes(cfg: dict, batch: float, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must move: every weight (the head is the whole
    embedding), ``batch`` rows of it as the embedding, every live token's K
    and V over the attention layers once, and each row's states read and
    written."""
    s = sizes(cfg)
    return (weight_bytes(cfg, dtype_bytes) + dtype_bytes * batch * s["d"]
            + live_tokens * page_bytes_per_token(cfg, dtype_bytes)
            + 2 * batch * slot_bytes(cfg))


def active_matmul_params(s: dict) -> int:
    """Weights one token multiplies in the layers."""
    return (n_mamba(s) * mamba_matmul_params(s) + n_attn(s) * attn_params(s)
            + s["L"] * ffn_params(s))


def decode_step_flops(cfg: dict, batch: float, live_tokens: float) -> float:
    """2 per active weight and the head for each of ``batch`` rows; the
    attention layers' score and weighted sum, 4 x head a query head and live
    token; the conv's taps and the recurrence a Mamba layer."""
    s = sizes(cfg)
    attn = 4 * s["hd"] * s["H"] * n_attn(s)
    return (2 * batch * (active_matmul_params(s) + s["V"] * s["d"]
                         + n_mamba(s) * s["K"] * s["di"])
            + batch * n_mamba(s) * scan_flops_per_token(cfg)
            + attn * live_tokens)


def prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    """Every layer weight once a chunk program and the row's states read and
    written once."""
    s = sizes(cfg)
    layers = weight_bytes(cfg, dtype_bytes) - dtype_bytes * (
        s["V"] * s["d"] + s["d"])
    return (layers + 2 * slot_bytes(cfg)) / chunk


def prefill_flops_per_token(cfg: dict, prompt_lengths: Sequence[Tuple[int, float]],
                            ) -> float:
    """2 per active weight, the conv's taps, the recurrence, and the attention
    layers' causal attention (4 x head a query head and attended position, S /
    2 positions on average), averaged over ``prompt_lengths`` [(S, weight)] by
    tokens.  Not the head."""
    s = sizes(cfg)
    tok = sum(S * w for S, w in prompt_lengths)
    per_pos = 4 * s["hd"] * s["H"] * n_attn(s)
    attn = sum(S * w * per_pos * (S / 2) for S, w in prompt_lengths) / tok
    return (2 * (active_matmul_params(s) + n_mamba(s) * s["K"] * s["di"])
            + n_mamba(s) * scan_flops_per_token(cfg) + attn)
