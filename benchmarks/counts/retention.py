"""Bytes and FLOPs of the power-retention decoder (``model_type`` ``brumby``),
from a configuration file's keys alone.

What is counted is what the algorithm needs and no more (``harness/costs.py``
has the rule: undercounting keeps a share honest).  The cache's unit is a
STATE, not a page: one layer of one sequence holds ``S`` [kv heads, F, D] and
``z`` [kv heads, F] in float32, ``F = D (D + 1) / 2`` the symmetric square of a
key (8256 at D = 128): 34,080,768 B a layer whatever the length.  The device
holds ``n_blocks x block_tokens / stride`` slots of every layer's state
(``--state-stride`` in the configuration's ``serve.args``), so a slot's bytes
over the stride is what the device keeps "per token" of ``n_blocks x
block_tokens``, and the product is the bytes the server allocates.

A decode step: every weight once but the embedding (of which B rows), plus
each live row's state READ once and not written back: the least any
implementation moves (one that folds keys in a chunk at a time writes the
state once a chunk, not once a token); the program reads and writes it every
step, and the share says so."""

from __future__ import annotations

from typing import Sequence, Tuple


def sizes(cfg: dict) -> dict:
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
            "hd": cfg["head_dim"], "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"])}


def state_dim(s: dict) -> int:
    return s["hd"] * (s["hd"] + 1) // 2


def layer_state_bytes(cfg: dict) -> int:
    """One layer's ``S`` and ``z`` of one sequence, float32."""
    s = sizes(cfg)
    return 4 * s["kv"] * state_dim(s) * (s["hd"] + 1)


def slot_bytes(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * layer_state_bytes(cfg)


def stride(cfg: dict) -> int:
    args = cfg["serve"]["args"]
    return int(args[args.index("--state-stride") + 1])


def matmul_params(s: dict) -> int:
    """Weights one token multiplies in a layer, the gate's matrix apart."""
    return (2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]
            + 3 * s["d"] * s["f"])


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """All weights as the server holds them: bfloat16 but the gate's matrix
    and bias, which are float32."""
    s = sizes(cfg)
    norms = 2 * s["d"] + 2 * s["hd"]
    served = s["L"] * (matmul_params(s) + norms) + 2 * s["V"] * s["d"] + s["d"]
    return dtype_bytes * served + 4 * s["L"] * (s["d"] * s["kv"] + s["kv"])


def cache_bytes_per_token(cfg: dict) -> int:
    """A slot's bytes over the stride: with ``n_blocks x block_tokens`` it
    gives the bytes of every slot the server allocates (held by a test)."""
    return slot_bytes(cfg) // stride(cfg)


def store_page_bytes(cfg: dict, block_tokens: int) -> int:
    """What run.py sizes the store's pool and its granule from: a layer's
    state spread over the blocks of the SHORTEST prompt that pushes one
    (``state.min_checkpoint_tokens`` in the configuration's file: the mix's
    shortest document), so that tokens pushed x this x layers / block covers
    every checkpoint the mix pushes (one a document whatever its length)."""
    return layer_state_bytes(cfg) * block_tokens // cfg["state"]["min_checkpoint_tokens"]


def decode_step_bytes(cfg: dict, batch: float, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    s = sizes(cfg)
    return (weight_bytes(cfg, dtype_bytes) - dtype_bytes * s["V"] * s["d"]
            + dtype_bytes * batch * s["d"] + batch * slot_bytes(cfg))


def decode_step_flops(cfg: dict, batch: float, live_tokens: float) -> float:
    """2 per weight and the lm_head a row; ``phi(q)^T S`` and ``phi(q)^T z`` for
    every query head, and the state's update ``g S + phi(k) v^T`` for every
    key/value head."""
    s = sizes(cfg)
    F = state_dim(s)
    ret = s["L"] * (2 * s["h"] * F * (s["hd"] + 1) + 3 * s["kv"] * F * (s["hd"] + 1))
    return batch * (2 * (s["L"] * (matmul_params(s) + s["d"] * s["kv"])
                         + s["V"] * s["d"]) + ret)


def prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    """Every layer weight once a chunk program, and the row's state read and
    written once a chunk."""
    s = sizes(cfg)
    return (dtype_bytes * s["L"] * matmul_params(s) + 2 * slot_bytes(cfg)) / chunk


def prefill_flops_per_token(cfg: dict, prompt_lengths: Sequence[Tuple[int, float]],
                            chunk: int = 512) -> float:
    """2 per weight; inside a chunk ``q . k`` and the weighted sum over half
    the chunk's positions; ``phi(q)^T [S z]`` of the carried state for every
    query head and ``phi(k) [v 1]^T`` into it for every key/value head.  Not
    the lm_head.  The same for every prompt length: a state does not grow."""
    s = sizes(cfg)
    F = state_dim(s)
    intra = 2 * 2 * s["hd"] * s["h"] * (chunk / 2)
    carried = 2 * F * (s["hd"] + 1) * (s["h"] + s["kv"])
    return s["L"] * (2 * (matmul_params(s) + s["d"] * s["kv"]) + intra + carried)
