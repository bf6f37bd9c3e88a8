"""The fixture family's counts: a module of its own, no line of
``harness/costs.py``.  The numbers are marked, not a model's: a page that is
not K and V by head with a selector's keys beside it, a weight count of its
own, and decode and prefill counts that a test can recognise."""

PAGE_BYTES_PER_TOKEN_LAYER = 6 * 1024     # a latent page + a selector's keys


def sizes(cfg: dict) -> dict:
    """What the family's reference needs (here: the dense reference's keys)."""
    return {
        "L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
        "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "bias": False, "qk_norm": False,
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
    }


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    return 1_000_003 * dtype_bytes * cfg["n_routed_experts"]


def cache_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    return PAGE_BYTES_PER_TOKEN_LAYER * cfg["num_hidden_layers"]


def store_page_bytes(cfg: dict, block_tokens: int) -> int:
    return PAGE_BYTES_PER_TOKEN_LAYER * block_tokens


def decode_step_bytes(cfg: dict, batch: float, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    return weight_bytes(cfg, dtype_bytes) + live_tokens * cache_bytes_per_token(cfg)


def decode_step_flops(cfg: dict, batch: float, live_tokens: float) -> float:
    return 2.0 * batch * 1_000_003


def prefill_bytes_per_token(cfg: dict, chunk: int, dtype_bytes: int = 2) -> float:
    return weight_bytes(cfg, dtype_bytes) / chunk


def prefill_flops_per_token(cfg: dict, prompt_lengths) -> float:
    return 2.0 * 1_000_003
