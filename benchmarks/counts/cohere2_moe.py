"""Bytes and FLOPs of the parallel-block decoder with sliding-window and full
layers and one chip's share of its routed experts (``model_type``
``cohere2_moe``), from a configuration file's keys alone.

What is counted is what the algorithm needs and no more (``harness/costs.py``
has the rule: undercounting keeps a share honest), the same work whatever
implements it: every held weight once where it must be read, of the HELD
routed experts those a step is EXPECTED to touch, a token's K and V once in
every layer that can see it: ``min(length, sliding_window)`` tokens in a
window layer, ``length`` in a full one.

The share: the router scores all ``published.num_experts`` experts and
chooses ``num_experts_per_tok``; this chip holds ``num_experts`` of them and
computes their terms only.  A pair is local with probability held / all
(16 / 128 = 12.5%); a step of ``batch`` rows is expected to touch
``held * (1 - (1 - k / all) ** batch)`` distinct held experts a layer.  The
program counts the pairs it routes (``decode.expert_pairs``) and, exactly and
on the device, those whose expert it holds (``decode.expert_pairs_local``).

The cache: K and V by head, ``2 * num_key_value_heads * head_dim`` values a
token a layer, in TWO POOLS: the full layers' of ``serve.n_blocks`` blocks
and the window layers' of ``--window-blocks`` blocks (``serve.args``; left
out, as many).  ``cache_bytes_per_token`` is the bytes of BOTH pools per
token of the FULL layers' pool, so that ``n_blocks * block_tokens *
cache_bytes_per_token`` is what the server allocates."""

from __future__ import annotations

from typing import Sequence, Tuple


def sizes(cfg: dict) -> dict:
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {
        "L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
        "H": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "f": cfg["intermediate_size"],
        "E": cfg["published"]["num_experts"], "Eh": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"], "ns": cfg["num_shared_experts"],
        "V": cfg["vocab_size"], "W": cfg["sliding_window"],
        "windowed": [t == "sliding_attention" for t in types],
        "eps": cfg["layer_norm_eps"], "theta": float(cfg["rope_theta"]),
        "logit_scale": float(cfg["logit_scale"]),
    }


def attn_params(s: dict) -> int:
    """q, k, v, o."""
    return 2 * s["d"] * s["H"] * s["hd"] + 2 * s["d"] * s["Hkv"] * s["hd"]


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def shared_params(s: dict) -> int:
    return s["ns"] * expert_params(s)


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """All weights as the server holds them: bfloat16 but the router's
    matrix (over all experts of the source), which is float32; the head is
    the embedding's held slice, once."""
    s = sizes(cfg)
    served = (s["L"] * (attn_params(s) + s["Eh"] * expert_params(s)
                        + shared_params(s) + s["d"])
              + s["V"] * s["d"] + s["d"])
    return dtype_bytes * served + 4 * s["L"] * s["d"] * s["E"]


def layer_token_bytes(s: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    return 2 * s["Hkv"] * s["hd"] * dtype_bytes


def pool_blocks(cfg: dict) -> Tuple[int, int]:
    """(blocks of the full layers' pool, blocks of the window layers')."""
    sv = cfg["serve"]
    args = sv.get("args", [])
    window = (int(args[args.index("--window-blocks") + 1])
              if "--window-blocks" in args else sv["n_blocks"])
    return sv["n_blocks"], window


def cache_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> float:
    """Bytes of both pools over the tokens of the full layers' pool."""
    s = sizes(cfg)
    n_window = sum(s["windowed"])
    full, window = pool_blocks(cfg)
    return (layer_token_bytes(s, dtype_bytes)
            * ((s["L"] - n_window) * full + n_window * window) / full)


def store_page_bytes(cfg: dict, block_tokens: int) -> int:
    """One layer's page of one block as it goes to the store."""
    return layer_token_bytes(sizes(cfg)) * block_tokens


def expected_held_experts(s: dict, batch: float) -> float:
    """Distinct HELD experts one step of ``batch`` rows touches in one
    layer, in expectation under a uniform choice of k of E."""
    return s["Eh"] * (1.0 - (1.0 - s["k"] / s["E"]) ** batch) if batch > 0 else 0.0


def visible_tokens(s: dict, batch: float, live_tokens: float) -> float:
    """Token-layers of K and V one step reads: every live token in a full
    layer, the window's worth of each row's in a window layer (the rows'
    mean length stands for each row's)."""
    if batch <= 0:
        return 0.0
    mean = live_tokens / batch
    return batch * sum(min(mean, s["W"]) if w else mean for w in s["windowed"])


def decode_step_bytes(cfg: dict, batch: float, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must read: attention, norm, shared experts and
    router of every layer, the EXPECTED DISTINCT held experts at ``batch``
    rows, the head's slice, ``batch`` rows of the embedding, the visible K
    and V.  Writes are left out."""
    s = sizes(cfg)
    layers = s["L"] * (dtype_bytes * (attn_params(s) + shared_params(s) + s["d"]
                                      + expected_held_experts(s, batch) * expert_params(s))
                       + 4 * s["d"] * s["E"])
    return (layers + dtype_bytes * (s["V"] * s["d"] + s["d"] + batch * s["d"])
            + visible_tokens(s, batch, live_tokens) * layer_token_bytes(s, dtype_bytes))


def active_matmul_params(s: dict) -> float:
    """Weights one token multiplies in the layers on THIS chip: attention,
    the shared experts, the router, and its expected k * held / all local
    pairs."""
    return s["L"] * (attn_params(s) + shared_params(s) + s["d"] * s["E"]
                     + s["k"] * s["Eh"] / s["E"] * expert_params(s))


def decode_step_flops(cfg: dict, batch: float, live_tokens: float) -> float:
    """2 per active weight and the head's slice for each row; QK^T and PV
    (4 * head_dim a query head) over every visible token-layer."""
    s = sizes(cfg)
    return (2 * batch * (active_matmul_params(s) + s["V"] * s["d"])
            + 4 * s["hd"] * s["H"] * visible_tokens(s, batch, live_tokens))


def prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    """Every layer weight once a ``chunk``-token program, every held expert
    among them (8 * chunk / 128 rows an expert: at chunks of hundreds every
    held expert has rows)."""
    s = sizes(cfg)
    per = s["L"] * (attn_params(s) + s["Eh"] * expert_params(s) + shared_params(s))
    return (dtype_bytes * per + 4 * s["L"] * s["d"] * s["E"]) / chunk


def prefill_flops_per_token(cfg: dict, prompt_lengths: Sequence[Tuple[int, float]],
                            ) -> float:
    """FLOPs per computed prompt token: 2 per active weight, plus causal
    attention (4 * head_dim a query head and attended position): S / 2
    positions on average in a full layer, and in a window layer the mean of
    ``min(position, sliding_window)``; averaged over ``prompt_lengths``
    [(S, weight)] by tokens.  The head is not counted."""
    s = sizes(cfg)
    W = s["W"]

    def attended(S: int, windowed: bool) -> float:
        if not windowed or S <= W:
            return S / 2
        return (W * W / 2 + (S - W) * W) / S

    tok = sum(S * w for S, w in prompt_lengths)
    per_pos = 4 * s["hd"] * s["H"]
    attn = sum(S * w * per_pos * sum(attended(S, win) for win in s["windowed"])
               for S, w in prompt_lengths) / tok
    return 2 * active_matmul_params(s) + attn
