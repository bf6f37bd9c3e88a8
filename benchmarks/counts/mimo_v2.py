"""Bytes and FLOPs of the pre-norm decoder whose window layers stand beside
full layers with pages of two shapes and one chip's share of its routed
experts (``model_type`` ``mimo_v2_flash``), from a configuration file's keys
alone.

What is counted is what the algorithm needs and no more (``harness/costs.py``
has the rule: undercounting keeps a share honest), the same work whatever
implements it: every held weight once where it must be read, of the HELD
routed experts those a step is EXPECTED to touch, a token's key and value
once in every layer that can see it: ``min(length, sliding_window)`` tokens
in a window layer, ``length`` in a full one.

The page: a full layer writes ``num_key_value_heads`` (4) heads a token, a
window layer ``swa_num_key_value_heads`` (8); in both a key is ``head_dim``
(192) and a value ``v_head_dim`` (128) wide: ``H x (192 + 128) x 2`` B a token
a layer, 2,560 B in a full layer and 5,120 B in a window layer, nothing
padded.  TWO POOLS: the full layers' of ``serve.n_blocks`` blocks and the
window layers' of ``--window-blocks`` blocks (``serve.args``).
``cache_bytes_per_token`` is the bytes of BOTH pools per token of the FULL
layers' pool, so that ``n_blocks * block_tokens * cache_bytes_per_token`` is
what the server allocates.

The store: a full layer's page of every chunk goes to the store, a window
layer's only where a later hit can read it (engine ``_window_sent``: the last
``ceil(window / block)`` pages before a chunk boundary or a prompt's end).
``store_page_bytes`` is the FULL layer's page (the store's granule; a window
layer's is two of them) and ``store_page_bytes x num_hidden_layers / block``
is an upper bound of what a token pushes (17,920 B against 11,520 B over
whole chunks: ``pushed_bytes_per_token``).

The share: the router scores all ``published.n_routed_experts`` experts and
chooses ``num_experts_per_tok``; this chip holds ``n_routed_experts`` of them
and computes their terms only.  A pair is local with probability held / all
(16 / 256 = 6.25%)."""

from __future__ import annotations

from typing import Sequence, Tuple


def sizes(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    return {
        "L": L, "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "Hkv": cfg["num_key_value_heads"],
        "Hkv_w": cfg["swa_num_key_value_heads"],
        "hd": cfg["head_dim"], "vd": cfg["v_head_dim"],
        "rot": int(cfg["partial_rotary_factor"] * cfg["head_dim"]),
        "fd": cfg["intermediate_size"], "f": cfg["moe_intermediate_size"],
        "E": cfg["published"]["n_routed_experts"], "Eh": cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
        "W": cfg["sliding_window"],
        "windowed": [bool(w) for w in cfg["hybrid_layer_pattern"][:L]],
        "moe": [bool(m) for m in cfg["moe_layer_freq"][:L]],
        "eps": cfg["layernorm_epsilon"], "theta": float(cfg["rope_theta"]),
        "theta_w": float(cfg["swa_rope_theta"]),
        "vscale": float(cfg["attention_value_scale"]),
    }


def kv_heads(s: dict, windowed: bool) -> int:
    return s["Hkv_w"] if windowed else s["Hkv"]


def attn_params(s: dict, windowed: bool) -> int:
    """q, k, v, o of one layer of a kind."""
    h = kv_heads(s, windowed)
    return (s["d"] * s["H"] * s["hd"] + s["d"] * h * (s["hd"] + s["vd"])
            + s["H"] * s["vd"] * s["d"])


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def dense_params(s: dict) -> int:
    return 3 * s["d"] * s["fd"]


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """All weights as the server holds them: bfloat16 but the routers'
    matrices (over all experts of the source) and biases and the sinks, which
    are float32; the embedding and the untied head are the held slice each."""
    s = sizes(cfg)
    served = 2 * s["V"] * s["d"] + s["d"]
    f32 = 0
    for w, m in zip(s["windowed"], s["moe"]):
        served += attn_params(s, w) + 2 * s["d"]
        served += s["Eh"] * expert_params(s) if m else dense_params(s)
        f32 += (s["d"] * s["E"] + s["E"] if m else 0) + (s["H"] if w else 0)
    return dtype_bytes * served + 4 * f32


def layer_token_bytes(s: dict, windowed: bool, dtype_bytes: int = 2) -> int:
    """The key and value heads of one token in one layer of a kind."""
    return kv_heads(s, windowed) * (s["hd"] + s["vd"]) * dtype_bytes


def pool_token_bytes(cfg: dict, dtype_bytes: int = 2) -> Tuple[int, int]:
    """Bytes held a token: (in the full layers' pool, in the window layers'
    pool, a token IN THE WINDOW)."""
    s = sizes(cfg)
    n_win = sum(s["windowed"])
    return ((s["L"] - n_win) * layer_token_bytes(s, False, dtype_bytes),
            n_win * layer_token_bytes(s, True, dtype_bytes))


def pool_blocks(cfg: dict) -> Tuple[int, int]:
    """(blocks of the full layers' pool, blocks of the window layers')."""
    sv = cfg["serve"]
    args = sv.get("args", [])
    window = (int(args[args.index("--window-blocks") + 1])
              if "--window-blocks" in args else sv["n_blocks"])
    return sv["n_blocks"], window


def pool_bytes(cfg: dict, dtype_bytes: int = 2) -> Tuple[int, int]:
    """Bytes of each pool as the server allocates it."""
    T = cfg["serve"]["block_tokens"]
    return tuple(n * T * b for n, b in zip(pool_blocks(cfg),
                                           pool_token_bytes(cfg, dtype_bytes)))


def cache_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> float:
    """Bytes of both pools over the tokens of the full layers' pool."""
    full, _ = pool_blocks(cfg)
    return sum(pool_bytes(cfg, dtype_bytes)) / (
        full * cfg["serve"]["block_tokens"])


def store_page_bytes(cfg: dict, block_tokens: int) -> int:
    """A FULL layer's page of one block as it goes to the store: the store's
    granule (a window layer's page is two of them), and with
    ``num_hidden_layers`` of them a block an upper bound of what a token
    pushes (``pushed_bytes_per_token`` is what it does)."""
    return layer_token_bytes(sizes(cfg), False) * block_tokens


def window_pages_sent(cfg: dict, block_tokens: int, chunk: int) -> int:
    """Of the ``chunk / block`` pages of a whole prefill chunk, those a window
    layer sends: the ``ceil(window / block)`` before the chunk's boundary."""
    return min(-(-sizes(cfg)["W"] // block_tokens), chunk // block_tokens)


def pushed_bytes_per_token(cfg: dict, block_tokens: int, chunk: int) -> float:
    """What a token of a whole chunk pushes: the full layers' page whole, the
    window layers' where a hit at the chunk's boundary can read it."""
    full, win = pool_token_bytes(cfg)
    return full + win * window_pages_sent(cfg, block_tokens, chunk) / (
        chunk // block_tokens)


def expected_held_experts(s: dict, batch: float) -> float:
    """Distinct HELD experts one step of ``batch`` rows touches in one
    layer, in expectation under a uniform choice of k of E."""
    return s["Eh"] * (1.0 - (1.0 - s["k"] / s["E"]) ** batch) if batch > 0 else 0.0


def visible_token_bytes(s: dict, batch: float, live_tokens: float,
                        dtype_bytes: int = 2) -> float:
    """Bytes of keys and values one step reads: every live token in a full
    layer, the window's worth of each row's in a window layer (the rows' mean
    length stands for each row's), each at its kind's width."""
    if batch <= 0:
        return 0.0
    mean = live_tokens / batch
    return batch * sum((min(mean, s["W"]) if w else mean)
                       * layer_token_bytes(s, w, dtype_bytes)
                       for w in s["windowed"])


def visible_tokens(s: dict, batch: float, live_tokens: float) -> float:
    """Token-layers one step's queries attend to."""
    if batch <= 0:
        return 0.0
    mean = live_tokens / batch
    return batch * sum(min(mean, s["W"]) if w else mean for w in s["windowed"])


def decode_step_bytes(cfg: dict, batch: float, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must read: attention and norms of every layer,
    the dense FFN, the routers, the EXPECTED DISTINCT held experts at
    ``batch`` rows, the head's slice, ``batch`` rows of the embedding, the
    visible keys and values.  Writes are left out."""
    s = sizes(cfg)
    total = dtype_bytes * (s["V"] * s["d"] + s["d"] + batch * s["d"])
    for w, m in zip(s["windowed"], s["moe"]):
        total += dtype_bytes * (attn_params(s, w) + 2 * s["d"])
        if m:
            total += (dtype_bytes * expected_held_experts(s, batch)
                      * expert_params(s) + 4 * s["d"] * s["E"])
        else:
            total += dtype_bytes * dense_params(s)
    return total + visible_token_bytes(s, batch, live_tokens, dtype_bytes)


def active_matmul_params(s: dict) -> float:
    """Weights one token multiplies in the layers on THIS chip: attention,
    the dense FFN, the routers, and its expected k * held / all local pairs."""
    return sum(attn_params(s, w)
               + (s["d"] * s["E"] + s["k"] * s["Eh"] / s["E"] * expert_params(s)
                  if m else dense_params(s))
               for w, m in zip(s["windowed"], s["moe"]))


def decode_step_flops(cfg: dict, batch: float, live_tokens: float) -> float:
    """2 per active weight and the head's slice for each row; QK^T (2 *
    head_dim) and PV (2 * v_head_dim) a query head over every visible
    token-layer."""
    s = sizes(cfg)
    return (2 * batch * (active_matmul_params(s) + s["V"] * s["d"])
            + 2 * (s["hd"] + s["vd"]) * s["H"]
            * visible_tokens(s, batch, live_tokens))


def prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    """Every layer weight once a ``chunk``-token program, every held expert
    among them (8 * chunk / 256 rows an expert: at chunks of hundreds every
    held expert has rows)."""
    s = sizes(cfg)
    per = f32 = 0
    for w, m in zip(s["windowed"], s["moe"]):
        per += attn_params(s, w) + (s["Eh"] * expert_params(s) if m
                                    else dense_params(s))
        f32 += s["d"] * s["E"] if m else 0
    return (dtype_bytes * per + 4 * f32) / chunk


def prefill_flops_per_token(cfg: dict, prompt_lengths: Sequence[Tuple[int, float]],
                            ) -> float:
    """FLOPs per computed prompt token: 2 per active weight, plus causal
    attention (2 * (head_dim + v_head_dim) a query head and attended
    position): S / 2 positions on average in a full layer, and in a window
    layer the mean of ``min(position, sliding_window)``; averaged over
    ``prompt_lengths`` [(S, weight)] by tokens.  The head is not counted."""
    s = sizes(cfg)
    W = s["W"]

    def attended(S: int, windowed: bool) -> float:
        if not windowed or S <= W:
            return S / 2
        return (W * W / 2 + (S - W) * W) / S

    tok = sum(S * w for S, w in prompt_lengths)
    per_pos = 2 * (s["hd"] + s["vd"]) * s["H"]
    attn = sum(S * w * per_pos * sum(attended(S, win) for win in s["windowed"])
               for S, w in prompt_lengths) / tok
    return 2 * active_matmul_params(s) + attn
