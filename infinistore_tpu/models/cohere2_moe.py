"""A parallel-block decoder whose layers attend in two ways, with one chip's
share of its routed experts (the ``cohere2_moe`` model type).

What differs from models/llama.py and models/mla_moe.py, and where it lives:

* **Two attention kinds in one stack** (``Cohere2MoeConfig.layer_windows``):
  a ``sliding_attention`` layer rotates q and k (pairs (2i, 2i+1), the whole
  head) and sees the last ``sliding_window`` positions; a ``full_attention``
  layer carries NO positional rotation and sees everything before it.  The
  page is K|V by head, as the dense families'.  A window layer READS ITS
  WINDOW'S PAGES AND NO OTHERS: in decode through
  ``attention.paged_window_decode_attention`` (the table's slots picked by
  index arithmetic on each row's length), in a prefill chunk through the
  window layers' OWN prefix buffer, which holds the window's rows and no
  others (the prefix buffer is one array a pool).  So a page wholly below
  the window
  may hold anything, or never have been loaded from the store
  (engine.prefill_start skips it): it cannot reach the arithmetic.
* **The parallel block**: ``h = LayerNorm(x)`` once (mean subtracted, a
  weight, no bias); ``x <- x + Attn(h) + FFN(h)``.
* **128 query heads over 8 key/value heads**: every contraction is grouped
  (the query viewed [.., 8, 16, D]); no 16-fold copy of K or V exists.
* **The expert layer, one chip's share**: sigmoid scores over ALL
  ``n_experts`` of the source in float32, the ``top_k`` largest chosen,
  their scores normalised over all the chosen; the layer HOLDS experts
  ``[first_expert, first_expert + n_experts_held)`` and computes their terms
  (models/moe.py ``routed_experts(held_from=)``) and the shared experts'
  MEAN.  What the absent experts would add is left out; nothing stands in
  for the other chips or their exchange.  The head is the tied embedding's
  held slice of the vocabulary.

Same contracts as ``models.llama.prefill_forward`` / ``decode_forward``, so
the engine, the scheduler, chunked prefill and the decode scan run it
unchanged; the decode step returns a third value, the (token, expert) pairs
whose expert this chip holds, which the scan sums and hands back with its
tokens (engine ``_decode_many``).  No verify step, no LoRA and no mesh path:
``serve`` refuses them at start-up.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import (
    apply_rope,
    grouped_chunk_attention,
    paged_decode_attention,
    paged_window_decode_attention,
    window_prefix_positions,
)
from .llama import Family, Params, _mlp, head_logits
from .moe import held_pairs, routed_experts


@dataclass(frozen=True)
class Cohere2MoeConfig:
    """Sizes under the names of the source's ``config.json``'s meaning;
    ``FAMILY_KEYS`` maps the file's keys onto these fields."""

    vocab_size: int = 262144        # the slice of the vocabulary held here
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_dim: int = 4096             # one expert's width (intermediate_size)
    n_experts: int = 128            # the router's width: every expert of the source
    top_k: int = 8
    n_shared_experts: int = 4
    sliding_window: int = 4096
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + ("full_attention",)
    norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    logit_scale: float = 1.0
    # the share: experts [first_expert, first_expert + n_experts_held)
    n_experts_held: int = 128
    first_expert: int = 0
    dtype: Any = jnp.bfloat16

    @property
    def kv_page(self) -> Tuple[int, int, int]:
        """(planes, heads, width): K and V by KV head, in every layer."""
        return (2, self.n_kv_heads, self.head_dim)

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """Per layer, the window its attention READS (gathers, not masks),
        or None for a layer that reads everything.  The engine takes from
        this which layers' pages of a stored prefix need not be fetched."""
        return tuple(self.sliding_window if t == "sliding_attention" else None
                     for t in self.layer_types)

    @property
    def expert_routing(self) -> Tuple[int, int, int]:
        """(expert layers, experts a token, experts a layer): what the step
        profiler counts routed pairs from (engine/stepprof.note_decode)."""
        return (self.n_layers, self.top_k, self.n_experts)


# config.json key -> field; every one is a published size the model file must
# state; none but the depth, the experts held and the vocabulary held may
# differ from the source
FAMILY_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "ffn_dim", "num_experts": "n_experts",
    "num_experts_per_tok": "top_k", "num_shared_experts": "n_shared_experts",
    "sliding_window": "sliding_window", "layer_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "logit_scale": "logit_scale",
}
# what the equations here assume of the source; a file that says otherwise
# names a model this module does not compute
FAMILY_FIXED = {
    "model_type": "cohere2_moe", "use_parallel_block": True,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "shared_expert_combination_strategy": "average",
    "first_k_dense_replace": 0, "position_embedding_type": "rope_gptj",
    "rotary_pct": 1.0, "tie_word_embeddings": True, "hidden_act": "silu",
    "attention_bias": False, "use_qk_norm": False, "rope_scaling": None,
    "order_of_interleaved_layers": "local_attn_first",
    "use_gated_activation": True, "use_embedding_sharing": True,
    "use_parallel_embedding": False,
}
# keys of the source that repeat a size above or that no equation reads
FAMILY_OTHER = ("layer_switch", "layer_types", "max_position_embeddings",
                "architectures", "torch_dtype", "prefix_dense_intermediate_size",
                "prefix_dense_sliding_window_pattern", "attention_dropout",
                "initializer_range", "use_cache", "pad_token_id",
                "bos_token_id", "eos_token_id", "rms_norm_eps",
                "rope_parameters", "tf_legacy_loss")
# what ``reduced`` may name, and the floors of the cut (model-configs guide,
# section 4): a whole period of the layer pattern and four layers, eight
# routed experts, an eighth of the vocabulary
REDUCIBLE = ("num_hidden_layers", "num_experts", "vocab_size")
MIN_EXPERTS_HELD = 8


def config_from_file(path: str, spec: dict) -> Tuple[str, Cohere2MoeConfig, int]:
    """``(model_id, cfg, seed)`` from a ``--model`` file of this family:
    ``{"family": "cohere2_moe", "published": {config.json's keys},
    "reduced": {"num_hidden_layers": n, "num_experts": held, "vocab_size":
    held}, "stands_for": {"chips_per_layer": c, "how": ...}, "seed": s}``.
    Every key of ``FAMILY_KEYS`` must be there (a width is never defaulted
    and never overridden: ``reduced`` may name the depth, the experts HELD
    and the vocabulary HELD only; the router's width and the experts a token
    stay the source's), the keys of ``FAMILY_FIXED`` must say what this
    module computes, and a share (fewer experts or a slice of the
    vocabulary) states the deployment it is a share of."""
    pub = spec.get("published", {})
    missing = sorted(set(FAMILY_KEYS) - set(pub))
    if missing:
        raise ValueError(f"{path}: published lacks {missing}: every size of "
                         f"the source is stated, none is defaulted")
    unknown = sorted(set(pub) - set(FAMILY_KEYS) - set(FAMILY_FIXED)
                     - set(FAMILY_OTHER))
    if unknown:
        raise ValueError(f"{path}: published has keys this family does not "
                         f"read: {unknown} (widths are never overridden)")
    for k, want in FAMILY_FIXED.items():
        if k in pub and pub[k] != want:
            raise ValueError(f"{path}: {k}={pub[k]!r}; this family computes "
                             f"{k}={want!r} only")
    types = pub.get("layer_types")
    switch = pub.get("layer_switch", 4)
    period = tuple(["sliding_attention"] * (switch - 1) + ["full_attention"])
    if types is None:
        types = list(period) * (pub["num_hidden_layers"] // switch)
    if (len(types) != pub["num_hidden_layers"]
            or any(t != period[i % switch] for i, t in enumerate(types))):
        raise ValueError(f"{path}: layer_types is not {switch - 1} "
                         f"sliding_attention then one full_attention, repeated "
                         f"over num_hidden_layers")
    reduced = spec.get("reduced", {})
    if set(reduced) - set(REDUCIBLE):
        raise ValueError(f"{path}: 'reduced' may name {list(REDUCIBLE)} only "
                         f"(no width, not the experts a token), got "
                         f"{sorted(reduced)}")
    n_layers = reduced.get("num_hidden_layers", pub["num_hidden_layers"])
    held = reduced.get("num_experts", pub["num_experts"])
    vocab = reduced.get("vocab_size", pub["vocab_size"])
    for name, v, lo, hi in (
            ("num_hidden_layers", n_layers, max(4, switch), pub["num_hidden_layers"]),
            ("num_experts", held, max(MIN_EXPERTS_HELD, pub["num_experts_per_tok"]),
             pub["num_experts"]),
            ("vocab_size", vocab, -(-pub["vocab_size"] // 8), pub["vocab_size"])):
        if not (isinstance(v, int) and lo <= v <= hi):
            raise ValueError(f"{path}: reduced {name}={v!r} must be in "
                             f"[{lo}, {hi}]")
    if n_layers % switch:
        raise ValueError(f"{path}: num_hidden_layers={n_layers} cuts a period "
                         f"of {switch} layers")
    if held < pub["num_experts"] or vocab < pub["vocab_size"]:
        stands = spec.get("stands_for")
        if not (isinstance(stands, dict)
                and isinstance(stands.get("chips_per_layer"), int)
                and stands["chips_per_layer"] >= 2
                and isinstance(stands.get("how"), str) and stands["how"]):
            raise ValueError(f"{path}: a share (num_experts {held} of "
                             f"{pub['num_experts']}, vocab_size {vocab} of "
                             f"{pub['vocab_size']}) states its deployment: "
                             f"stands_for = {{chips_per_layer, how}}")
    cfg = Cohere2MoeConfig(**{f: pub[k] for k, f in FAMILY_KEYS.items()})
    seed = spec.get("seed", 0)
    if not (isinstance(seed, int) and seed >= 0):
        raise ValueError(f"{path}: seed must be a non-negative integer")
    # the id commits to everything the weights depend on
    name = spec.get("name", "cohere2_moe")
    widths = "-".join(str(pub[k]) for k in sorted(FAMILY_KEYS)
                      if k not in REDUCIBLE)
    tag = hashlib.sha256(widths.encode()).hexdigest()[:8]
    return (f"{name}-{tag}-l{n_layers}-e{held}-v{vocab}-seed{seed}",
            replace(cfg, n_layers=n_layers, n_experts_held=held,
                    vocab_size=vocab, layer_types=tuple(types[:n_layers])),
            seed)


def init_cohere2_moe_params(cfg: Cohere2MoeConfig, key: jax.Array) -> Params:
    """Random weights from ``key``, one jitted program; ``layers`` is a tuple
    of one dict a layer (a layer's slice of a stacked expert leaf would be
    copied in every step: models/mla_moe.py).  Layer ``li`` draws from
    ``split(split(key, L + 1)[li], 9)``: 0 wq, 1 wk, 2 wv, 3 wo, 4 the
    router, 5-7 the held routed experts, ``split([8], 3)`` the shared
    experts; the embedding (and tied head) from ``split(key, L + 1)[L]``;
    normal / sqrt(fan_in).  The router's matrix is float32."""
    L, d, hd = cfg.n_layers, cfg.dim, cfg.head_dim
    H, Hkv, E, Eh = cfg.n_heads, cfg.n_kv_heads, cfg.n_experts, cfg.n_experts_held
    f, fs = cfg.ffn_dim, cfg.n_shared_experts * cfg.ffn_dim

    def dense(key, shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def build(key):
        keys = jax.random.split(key, L + 1)
        layers = []
        for li in range(L):
            k = jax.random.split(keys[li], 9)
            ks = jax.random.split(k[8], 3)
            layers.append({
                "wq": dense(k[0], (d, H * hd), d),
                "wk": dense(k[1], (d, Hkv * hd), d),
                "wv": dense(k[2], (d, Hkv * hd), d),
                "wo": dense(k[3], (H * hd, d), H * hd),
                "ln": jnp.ones((d,), cfg.dtype),
                "router": dense(k[4], (d, E), d, jnp.float32),
                "w_gate": dense(k[5], (Eh, d, f), d),
                "w_up": dense(k[6], (Eh, d, f), d),
                "w_down": dense(k[7], (Eh, f, d), f),
                "ws_gate": dense(ks[0], (d, fs), d),
                "ws_up": dense(ks[1], (d, fs), d),
                "ws_down": dense(ks[2], (fs, d), fs),
            })
        return {"embed": dense(keys[L], (cfg.vocab_size, d), d),
                "layers": tuple(layers), "ln_out": jnp.ones((d,), cfg.dtype)}

    return jax.jit(build)(key)


def layernorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """LayerNorm with a weight and no bias, in float32."""
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def expert_layer(layer: Params, cfg: Cohere2MoeConfig, h: jax.Array,
                 live: jax.Array | None = None) -> Tuple[jax.Array, jax.Array]:
    """h [B, S, dim] (normalised) -> (this share's routed terms plus the
    shared experts' mean, the number of (token, expert) pairs whose expert is
    held here).  ``live`` [B] leaves a batch's pad rows out of that count."""
    B, S, d = h.shape
    flat = h.reshape(B * S, d)
    with jax.named_scope("istpu.moe.route"):
        # float32 at full precision: the choice of experts is discrete
        scores = jax.nn.sigmoid(jnp.dot(
            flat.astype(jnp.float32), layer["router"],
            precision=jax.lax.Precision.HIGHEST))
        chosen, idx = jax.lax.top_k(scores, cfg.top_k)
        w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        idx = idx.astype(jnp.int32)
        n_local = held_pairs(idx, cfg.first_expert, cfg.n_experts_held, live)
    with jax.named_scope("istpu.moe.experts"):
        whole = cfg.n_experts_held == cfg.n_experts
        y = routed_experts(flat, idx, w, layer["w_gate"], layer["w_up"],
                           layer["w_down"],
                           held_from=None if whole else cfg.first_expert)
    with jax.named_scope("istpu.moe.shared"):
        # the four shared experts side by side are one SwiGLU of four times
        # the width; their mean is a quarter of it
        shared = _mlp({"w_gate": layer["ws_gate"], "w_up": layer["ws_up"],
                       "w_down": layer["ws_down"]}, flat)
        y = y + (shared.astype(jnp.float32)
                 / cfg.n_shared_experts).astype(y.dtype)
    return y.reshape(B, S, d), n_local


def _qkv(layer: Params, cfg: Cohere2MoeConfig, h: jax.Array,
         positions: jax.Array, window: Optional[int]):
    """h [B, S, dim] -> q [B, S, H, D], k, v [B, S, H_kv, D]; q and k rotated
    where the layer is a window layer, as they are where it is a full one."""
    B, S, _ = h.shape
    q = (h @ layer["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ layer["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if window is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _head(params: Params, cfg: Cohere2MoeConfig, x: jax.Array) -> jax.Array:
    x = layernorm(x, params["ln_out"], cfg.norm_eps)
    logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


def cohere2_moe_prefill_forward(
    params: Params,
    cfg: Cohere2MoeConfig,
    tokens: jax.Array,
    prefix_kv: Tuple[jax.Array, jax.Array] | None = None,
    prefix_len: jax.Array | None = None,
    head: str = "all",
    head_row: jax.Array | None = None,
) -> Tuple[jax.Array | None, Tuple[jax.Array, jax.Array]]:
    """tokens [B, S] -> (logits [B, S, V held], kv BY POOL: the full layers'
    [L_full, 2, B, S, H_kv, D] and the window layers' [L_win, 2, B, S, H_kv,
    D]).

    The contract of ``models.llama.prefill_forward`` with one prefix buffer A
    POOL (kv/cache.PagedCacheConfig.pools): ``prefix_kv[0]`` [L_full, 2, B, P,
    H_kv, D] is the reused prefix's K and V (exact, or a padded buffer of
    which ``prefix_len`` rows are valid), which a full layer attends to whole;
    ``prefix_kv[1]`` [L_win, 2, B, R, H_kv, D] holds the ``R`` rows that END
    where the chunk starts (``attention.window_prefix_positions``; the engine
    keeps the window's worth), then the chunk's own: a window layer's scores
    are a band of ``R`` + the chunk's keys, and no row below is read or held.
    The returned rows cover the new tokens.  ``head`` / ``head_row``: where
    the norm and the head run, as there (``llama.head_logits``)."""
    B, S = tokens.shape
    P = 0 if prefix_kv is None else prefix_kv[0].shape[3]
    start = P if prefix_len is None else prefix_len
    q_pos = jnp.arange(S) + start
    positions = jnp.broadcast_to(q_pos, (B, S))
    x = params["embed"][tokens]
    kvs = ([], [])
    for li, layer in enumerate(params["layers"]):
        window = cfg.layer_windows[li]
        p = int(window is not None)   # the layer's pool, and its place in it
        lp = len(kvs[p])
        h = layernorm(x, layer["ln"], cfg.norm_eps)
        q, k, v = _qkv(layer, cfg, h, positions, window)
        kvs[p].append(jnp.stack([k, v], axis=0))
        with jax.named_scope("istpu.attn.window" if window is not None
                             else "istpu.attn.full"):
            k_pos, k_valid = q_pos, None
            if prefix_kv is not None:
                pk, pv = prefix_kv[p][lp, 0], prefix_kv[p][lp, 1]
                if window is not None:
                    b_pos, b_valid = window_prefix_positions(pk.shape[1], start)
                else:
                    b_pos = jnp.arange(P)
                    b_valid = (None if prefix_len is None
                               else b_pos < prefix_len)
                if b_valid is not None:
                    k_valid = jnp.concatenate([b_valid, jnp.ones((S,), bool)])
                k_pos = jnp.concatenate([b_pos, q_pos])
                k = jnp.concatenate([pk, k], axis=1)
                v = jnp.concatenate([pv, v], axis=1)
            attn = grouped_chunk_attention(q, k, v, q_pos, k_pos, k_valid,
                                           window)
        ffn, _ = expert_layer(layer, cfg, h)
        x = x + attn.reshape(B, S, -1) @ layer["wo"] + ffn
    return head_logits(x, head, head_row, partial(_head, params, cfg)
                       ), tuple(jnp.stack(rows) for rows in kvs)


def cohere2_moe_decode_forward(
    params: Params,
    cfg: Cohere2MoeConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: Tuple[jax.Array, jax.Array],
    block_table: Tuple[jax.Array, jax.Array],
    seq_lens: jax.Array,
    slot_block_ids: Tuple[jax.Array, jax.Array],
    slot_ids: jax.Array,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], jax.Array]:
    """Single-token paged decode; the contract of
    ``models.llama.decode_forward`` over a cache of TWO POOLS
    (kv/cache.PagedCacheConfig.pools): ``cache``, ``block_table`` and
    ``slot_block_ids`` are pairs (the full layers' pool, the window layers'
    pool), each pool [its layers, 2, H_kv, its blocks, T, D] with a table of
    its own.  A third value comes back: the step's (token, expert) pairs
    whose expert is held here, over the live rows (a pad row's page id lies
    past the pool).  A window layer gathers its window's pages out of its
    pool's table, a full layer its pool's whole table."""
    from ..kv.cache import write_token_kv

    B = tokens.shape[0]
    pools = list(cache)
    live = slot_block_ids[0] < pools[0].shape[3]
    x = params["embed"][tokens][:, None, :]
    pos = positions[:, None]
    n_local = jnp.zeros((), jnp.int32)
    seen = [0, 0]                     # layers met so far, by pool
    for li, layer in enumerate(params["layers"]):
        window = cfg.layer_windows[li]
        p = int(window is not None)   # the layer's pool, and its place in it
        lp, seen[p] = seen[p], seen[p] + 1
        h = layernorm(x, layer["ln"], cfg.norm_eps)
        q, k, v = _qkv(layer, cfg, h, pos, window)
        pools[p] = write_token_kv(pools[p], lp, slot_block_ids[p], slot_ids,
                                  k[:, 0], v[:, 0])
        if window is not None:
            with jax.named_scope("istpu.attn.window"):
                attn = paged_window_decode_attention(
                    q[:, 0], pools[p], lp, block_table[p], seq_lens, window)
        else:
            with jax.named_scope("istpu.attn.full"):
                attn = paged_decode_attention(
                    q[:, 0], pools[p], lp, block_table[p], seq_lens)
        ffn, n = expert_layer(layer, cfg, h, live)
        n_local = n_local + n
        x = x + (attn.reshape(B, -1) @ layer["wo"])[:, None, :] + ffn
    return _head(params, cfg, x)[:, 0], tuple(pools), n_local


FAMILY = Family(name="cohere2_moe", config_cls=Cohere2MoeConfig,
                config_from_file=config_from_file, init=init_cohere2_moe_params,
                prefill_fn=cohere2_moe_prefill_forward, decode_fn=cohere2_moe_decode_forward)
