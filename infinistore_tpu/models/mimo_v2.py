"""A pre-norm decoder whose layers attend in two ways AND WRITE PAGES OF TWO
SHAPES, with one chip's share of its routed experts (the ``mimo_v2_flash``
model type).

What differs from models/cohere2_moe.py (the other stack of window and full
layers), and where it lives:

* **A page has the shape of its layer's kind** (``MimoV2Config.kv_pages``,
  ``kv/cache.py`` ``pool_kv``).  A full layer writes ``n_kv_heads`` (4)
  key/value heads a token, a window layer ``swa_n_kv_heads`` (8), and in both
  a key is ``head_dim`` (192) wide and a value ``v_head_dim`` (128).  The page
  of a pool is one row a token, its heads' keys side by side and then their
  values: 4 x 320 = 1,280 values in a full layer, 8 x 320 = 2,560 in a window
  layer, nothing padded.  The prefill's prefix buffer is one array A POOL: the
  full layers' over the prefix, the window layers' over the rows that end
  where the chunk starts (``attention.window_prefix_positions``).
* **A sink in the window layers' softmax**: one learned logit a query head in
  the denominator, no value (``attention.softmax_with_sink``).
* **A partial rotation**: the first ``rotary_dim`` (64) dimensions of every
  query and key head, dimension ``i`` with ``i + 32``, theta by layer kind
  (``attention.apply_rope_leading``).
* **Values scaled** by ``attention_value_scale`` where they are projected, so
  the page holds the scaled value.
* **Sequential block**: ``h = x + Attn(RMSNorm(x))``, ``x' = h +
  FFN(RMSNorm(h))``; the head is a final RMSNorm and an untied matrix.
* **The FFN**: layer 0 a dense SwiGLU; every other layer sigmoid scores over
  ALL ``n_experts`` of the source in float32, the ``top_k`` largest of
  ``score + bias`` chosen, weighed by their own scores over their sum
  (``moe.sigmoid_top_k``), no shared expert; the layer HOLDS experts
  ``[first_expert, first_expert + n_experts_held)`` and computes their terms
  (``moe.routed_experts(held_from=)``).  What the absent experts would add is
  left out; nothing stands in for the other chips or their exchange.

Same contracts as ``models.cohere2_moe``'s forwards over a cache of two pools,
so the engine, the scheduler, chunked prefill and the decode scan run it
unchanged; the decode step returns the (token, expert) pairs whose expert this
chip holds as its third value.  No verify step, no LoRA and no mesh path:
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
    apply_rope_leading,
    grouped_chunk_attention,
    paged_decode_attention,
    paged_window_decode_attention,
    split_kv_rows,
    window_prefix_positions,
)
from .llama import Family, Params, _mlp, head_logits, rmsnorm
from .moe import held_pairs, routed_experts, sigmoid_top_k


@dataclass(frozen=True)
class MimoV2Config:
    """Sizes under the names of the source's ``config.json``'s meaning;
    ``FAMILY_KEYS`` maps the file's keys onto these fields."""

    vocab_size: int = 152576        # the slice of the vocabulary held here
    dim: int = 4096
    n_layers: int = 48
    n_heads: int = 64
    n_kv_heads: int = 4             # a full layer's
    swa_n_kv_heads: int = 8         # a window layer's
    head_dim: int = 192             # a query's and a key's width
    v_head_dim: int = 128
    ffn_dim: int = 16384            # the dense layers' SwiGLU
    moe_ffn_dim: int = 2048         # one expert's width
    n_experts: int = 256            # the router's width: every expert of the source
    top_k: int = 8
    sliding_window: int = 128
    norm_eps: float = 1e-5
    rope_theta: float = 5_000_000.0
    swa_rope_theta: float = 10_000.0
    partial_rotary_factor: float = 0.334
    value_scale: float = 0.707
    # per layer: 1 a window layer, 0 a full one; 1 an expert layer, 0 a dense one
    layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 0)
    moe_layers: Tuple[int, ...] = (0, 1, 1, 1, 1, 1)
    # the share: experts [first_expert, first_expert + n_experts_held)
    n_experts_held: int = 256
    first_expert: int = 0
    dtype: Any = jnp.bfloat16

    @property
    def rotary_dim(self) -> int:
        """The leading dimensions of a head that are rotated."""
        return int(self.partial_rotary_factor * self.head_dim)

    @property
    def kv_pages(self) -> Tuple[Tuple[int, int, int], ...]:
        """(key/value heads, key width, value width) by layer kind: the full
        layers' page, then the window layers' (kv/cache.py ``pool_kv``)."""
        return ((self.n_kv_heads, self.head_dim, self.v_head_dim),
                (self.swa_n_kv_heads, self.head_dim, self.v_head_dim))

    @property
    def kv_page(self) -> Tuple[int, int, int]:
        """(planes, heads, width) of the FULL layers' page: one plane of one
        row a token, the heads' keys and then their values."""
        h, k, v = self.kv_pages[0]
        return (1, 1, h * (k + v))

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """Per layer, the window its attention READS (gathers, not masks),
        or None for a layer that reads everything."""
        return tuple(self.sliding_window if w else None
                     for w in self.layer_pattern)

    @property
    def expert_routing(self) -> Tuple[int, int, int]:
        """(expert layers, experts a token, experts a layer): what the step
        profiler counts routed pairs from (engine/stepprof.note_decode)."""
        return (sum(self.moe_layers), self.top_k, self.n_experts)


# config.json key -> field; every one is a published size the model file must
# state; none but the depth, the experts held and the vocabulary held may
# differ from the source
FAMILY_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "swa_num_key_value_heads": "swa_n_kv_heads", "head_dim": "head_dim",
    "v_head_dim": "v_head_dim", "intermediate_size": "ffn_dim",
    "moe_intermediate_size": "moe_ffn_dim", "n_routed_experts": "n_experts",
    "num_experts_per_tok": "top_k", "sliding_window": "sliding_window",
    "layernorm_epsilon": "norm_eps", "rope_theta": "rope_theta",
    "swa_rope_theta": "swa_rope_theta",
    "partial_rotary_factor": "partial_rotary_factor",
    "attention_value_scale": "value_scale",
}
# what the equations here assume of the source; a file that says otherwise
# names a model this module does not compute
FAMILY_FIXED = {
    "model_type": "mimo_v2_flash", "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": None,
    "n_shared_experts": None,
}
# keys that must repeat a size above (the window layers' query heads and
# widths are the full layers')
FAMILY_SAME = {"swa_num_attention_heads": "num_attention_heads",
               "swa_head_dim": "head_dim", "swa_v_head_dim": "v_head_dim",
               "sliding_window_size": "sliding_window",
               "attention_chunk_size": "sliding_window"}
FAMILY_OTHER = ("hybrid_layer_pattern", "moe_layer_freq",
                "max_position_embeddings", "architectures", "torch_dtype")
REDUCIBLE = ("num_hidden_layers", "n_routed_experts", "vocab_size")
MIN_EXPERTS_HELD = 8


def config_from_file(path: str, spec: dict) -> Tuple[str, MimoV2Config, int]:
    """``(model_id, cfg, seed)`` from a ``--model`` file of this family:
    ``{"family": "mimo_v2_flash", "published": {config.json's keys},
    "reduced": {"num_hidden_layers": n, "n_routed_experts": held,
    "vocab_size": held}, "stands_for": {"chips_per_layer": c, "how": ...},
    "seed": s}``.  Every key of ``FAMILY_KEYS`` and both layer patterns must
    be there (a width is never defaulted and never overridden), the keys of
    ``FAMILY_FIXED`` must say what this module computes, and a share states
    the deployment it is a share of.  The depth keeps the leading dense
    layer, a window layer and a full expert layer at least: the first
    ``n`` entries of the source's patterns."""
    pub = spec.get("published", {})
    missing = sorted((set(FAMILY_KEYS) | {"hybrid_layer_pattern",
                                          "moe_layer_freq"}) - set(pub))
    if missing:
        raise ValueError(f"{path}: published lacks {missing}: every size of "
                         f"the source is stated, none is defaulted")
    unknown = sorted(set(pub) - set(FAMILY_KEYS) - set(FAMILY_FIXED)
                     - set(FAMILY_SAME) - set(FAMILY_OTHER))
    if unknown:
        raise ValueError(f"{path}: published has keys this family does not "
                         f"read: {unknown} (widths are never overridden)")
    for k, want in FAMILY_FIXED.items():
        if k in pub and pub[k] != want:
            raise ValueError(f"{path}: {k}={pub[k]!r}; this family computes "
                             f"{k}={want!r} only")
    for k, same in FAMILY_SAME.items():
        if k in pub and pub[k] != pub[same]:
            raise ValueError(f"{path}: {k}={pub[k]!r} differs from {same}="
                             f"{pub[same]!r}; this family computes them equal")
    L = pub["num_hidden_layers"]
    pattern, moe = pub["hybrid_layer_pattern"], pub["moe_layer_freq"]
    if not (len(pattern) == len(moe) == L
            and all(v in (0, 1) for v in list(pattern) + list(moe))):
        raise ValueError(f"{path}: hybrid_layer_pattern and moe_layer_freq "
                         f"are one 0 or 1 a layer over num_hidden_layers")
    reduced = spec.get("reduced", {})
    if set(reduced) - set(REDUCIBLE):
        raise ValueError(f"{path}: 'reduced' may name {list(REDUCIBLE)} only "
                         f"(no width, not the experts a token), got "
                         f"{sorted(reduced)}")
    n_layers = reduced.get("num_hidden_layers", L)
    held = reduced.get("n_routed_experts", pub["n_routed_experts"])
    vocab = reduced.get("vocab_size", pub["vocab_size"])
    for name, v, lo, hi in (
            ("num_hidden_layers", n_layers, 4, L),
            ("n_routed_experts", held,
             max(MIN_EXPERTS_HELD, pub["num_experts_per_tok"]),
             pub["n_routed_experts"]),
            ("vocab_size", vocab, -(-pub["vocab_size"] // 8), pub["vocab_size"])):
        if not (isinstance(v, int) and lo <= v <= hi):
            raise ValueError(f"{path}: reduced {name}={v!r} must be in "
                             f"[{lo}, {hi}]")
    kept = list(zip(pattern[:n_layers], moe[:n_layers]))
    if not ((1, 1) in kept and (0, 1) in kept):
        raise ValueError(f"{path}: num_hidden_layers={n_layers} cuts the "
                         f"stack before it has shown a window layer and a "
                         f"full layer with experts (a whole period)")
    if held < pub["n_routed_experts"] or vocab < pub["vocab_size"]:
        stands = spec.get("stands_for")
        if not (isinstance(stands, dict)
                and isinstance(stands.get("chips_per_layer"), int)
                and stands["chips_per_layer"] >= 2
                and isinstance(stands.get("how"), str) and stands["how"]):
            raise ValueError(f"{path}: a share (n_routed_experts {held} of "
                             f"{pub['n_routed_experts']}, vocab_size {vocab} "
                             f"of {pub['vocab_size']}) states its deployment: "
                             f"stands_for = {{chips_per_layer, how}}")
    cfg = MimoV2Config(**{f: pub[k] for k, f in FAMILY_KEYS.items()})
    if cfg.rotary_dim % 2 or not 0 < cfg.rotary_dim <= cfg.head_dim:
        raise ValueError(f"{path}: partial_rotary_factor x head_dim = "
                         f"{cfg.rotary_dim} is no even part of a head")
    seed = spec.get("seed", 0)
    if not (isinstance(seed, int) and seed >= 0):
        raise ValueError(f"{path}: seed must be a non-negative integer")
    # the id commits to everything the weights depend on
    name = spec.get("name", "mimo_v2_flash")
    widths = "-".join(str(pub[k]) for k in sorted(FAMILY_KEYS)
                      if k not in REDUCIBLE)
    tag = hashlib.sha256(widths.encode()).hexdigest()[:8]
    return (f"{name}-{tag}-l{n_layers}-e{held}-v{vocab}-seed{seed}",
            replace(cfg, n_layers=n_layers, n_experts_held=held,
                    vocab_size=vocab,
                    layer_pattern=tuple(pattern[:n_layers]),
                    moe_layers=tuple(moe[:n_layers])),
            seed)


# the sink's logit is drawn around this: a window of 128 keys whose scores
# are about N(0, 1) sums to exp(5.3), so a sink near 4 takes a fifth of a
# query's weight and a program that dropped it is seen by the check
SINK_MEAN = 4.0


def init_mimo_v2_params(cfg: MimoV2Config, key: jax.Array) -> Params:
    """Random weights from ``key``, one jitted program; ``layers`` is a tuple
    of one dict a layer (the kinds hold different leaves).  Layer ``li`` draws
    from ``split(split(key, L + 2)[li], 12)``: 0 wq, 1 wk, 2 wv, 3 wo, 4-6
    the dense FFN (a dense layer), 7 the router, 8-10 the held routed experts
    (an expert layer), 11 the sink (a window layer: ``SINK_MEAN`` + normal,
    float32); the embedding from ``split(key, L + 2)[L]``, the head from
    ``[L + 1]``; normal / sqrt(fan_in).  The router's matrix is float32; the
    selection bias is zeros (the checkpoint's values are not in
    ``config.json``)."""
    L, d, H = cfg.n_layers, cfg.dim, cfg.n_heads
    hd, vd = cfg.head_dim, cfg.v_head_dim
    E, Eh, f = cfg.n_experts, cfg.n_experts_held, cfg.moe_ffn_dim

    def dense(key, shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def build(key):
        keys = jax.random.split(key, L + 2)
        layers = []
        for li in range(L):
            k = jax.random.split(keys[li], 12)
            Hkv = cfg.swa_n_kv_heads if cfg.layer_pattern[li] else cfg.n_kv_heads
            layer = {
                "wq": dense(k[0], (d, H * hd), d),
                "wk": dense(k[1], (d, Hkv * hd), d),
                "wv": dense(k[2], (d, Hkv * vd), d),
                "wo": dense(k[3], (H * vd, d), H * vd),
                "ln_attn": jnp.ones((d,), cfg.dtype),
                "ln_mlp": jnp.ones((d,), cfg.dtype),
            }
            if cfg.layer_pattern[li]:
                layer["sink"] = SINK_MEAN + jax.random.normal(
                    k[11], (H,), jnp.float32)
            if cfg.moe_layers[li]:
                layer.update(
                    router=dense(k[7], (d, E), d, jnp.float32),
                    router_bias=jnp.zeros((E,), jnp.float32),
                    w_gate=dense(k[8], (Eh, d, f), d),
                    w_up=dense(k[9], (Eh, d, f), d),
                    w_down=dense(k[10], (Eh, f, d), f))
            else:
                layer.update(
                    w_gate=dense(k[4], (d, cfg.ffn_dim), d),
                    w_up=dense(k[5], (d, cfg.ffn_dim), d),
                    w_down=dense(k[6], (cfg.ffn_dim, d), cfg.ffn_dim))
            layers.append(layer)
        return {"embed": dense(keys[L], (cfg.vocab_size, d), d),
                "layers": tuple(layers), "ln_out": jnp.ones((d,), cfg.dtype),
                "lm_head": dense(keys[L + 1], (d, cfg.vocab_size), d)}

    return jax.jit(build)(key)


def expert_layer(layer: Params, cfg: MimoV2Config, h: jax.Array,
                 live: jax.Array | None = None) -> Tuple[jax.Array, jax.Array]:
    """h [B, S, dim] (normalised) -> (this share's routed terms, the number of
    (token, expert) pairs whose expert is held here).  ``live`` [B] leaves a
    batch's pad rows out of that count."""
    B, S, d = h.shape
    flat = h.reshape(B * S, d)
    with jax.named_scope("istpu.moe.route"):
        # float32 at full precision: the choice of experts is discrete
        scores = jax.nn.sigmoid(jnp.dot(
            flat.astype(jnp.float32), layer["router"],
            precision=jax.lax.Precision.HIGHEST))
        idx, w = sigmoid_top_k(scores, layer["router_bias"], cfg.top_k, 1.0)
        n_local = held_pairs(idx, cfg.first_expert, cfg.n_experts_held, live)
    with jax.named_scope("istpu.moe.experts"):
        whole = cfg.n_experts_held == cfg.n_experts
        y = routed_experts(flat, idx, w, layer["w_gate"], layer["w_up"],
                           layer["w_down"],
                           held_from=None if whole else cfg.first_expert)
    return y.reshape(B, S, d), n_local


def _ffn(layer: Params, cfg: MimoV2Config, h: jax.Array,
         live: jax.Array | None = None) -> Tuple[jax.Array, jax.Array]:
    """The dense SwiGLU or the expert layer: which one a layer holds is its
    pytree's structure, static at trace time."""
    if "router" in layer:
        return expert_layer(layer, cfg, h, live)
    return _mlp(layer, h), jnp.zeros((), jnp.int32)


def _q_and_row(layer: Params, cfg: MimoV2Config, h: jax.Array,
               positions: jax.Array, windowed: bool):
    """h [B, S, dim] (normalised) -> q [B, S, H, head_dim] with its leading
    ``rotary_dim`` dimensions rotated, and the page's row [B, S, H_kv x
    (head_dim + v_head_dim)]: the layer kind's heads' keys (rotated likewise)
    side by side, then their values times ``value_scale``."""
    B, S, _ = h.shape
    theta = cfg.swa_rope_theta if windowed else cfg.rope_theta
    q = (h @ layer["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]).reshape(B, S, -1, cfg.head_dim)
    q = apply_rope_leading(q, positions, theta, cfg.rotary_dim)
    k = apply_rope_leading(k, positions, theta, cfg.rotary_dim)
    v = ((h @ layer["wv"]).astype(jnp.float32) * cfg.value_scale).astype(h.dtype)
    return q, jnp.concatenate([k.reshape(B, S, -1), v], axis=-1)


def _head(params: Params, cfg: MimoV2Config, x: jax.Array) -> jax.Array:
    return rmsnorm(x, params["ln_out"], cfg.norm_eps) @ params["lm_head"]


def mimo_v2_prefill_forward(
    params: Params,
    cfg: MimoV2Config,
    tokens: jax.Array,
    prefix_kv: Tuple[jax.Array, jax.Array] | None = None,
    prefix_len: jax.Array | None = None,
    head: str = "all",
    head_row: jax.Array | None = None,
) -> Tuple[jax.Array | None, Tuple[jax.Array, jax.Array]]:
    """tokens [B, S] -> (logits [B, S, V held], rows BY POOL: the full layers'
    [L_full, 1, B, S, 1, W_full] and the window layers' [L_win, 1, B, S, 1,
    W_win]).

    The contract of ``models.cohere2_moe.cohere2_moe_prefill_forward``:
    ``prefix_kv`` is one buffer A POOL.  The full layers' [L_full, 1, B, P, 1,
    W_full] holds the prefix's rows (exact, or padded with ``prefix_len`` of
    them valid).  The window layers' [L_win, 1, B, R, 1, W_win] holds the
    ``R`` rows that END where the chunk starts, whatever ``R`` is
    (``attention.window_prefix_positions``): inside a chunk a window layer's
    scores are a band of ``R`` + the chunk's keys, not the prefix."""
    B, S = tokens.shape
    P = 0 if prefix_kv is None else prefix_kv[0].shape[3]
    start = P if prefix_len is None else prefix_len
    q_pos = jnp.arange(S) + start
    positions = jnp.broadcast_to(q_pos, (B, S))
    x = params["embed"][tokens]
    rows_of = ([], [])
    for li, layer in enumerate(params["layers"]):
        window = cfg.layer_windows[li]
        p = int(window is not None)
        lp = len(rows_of[p])
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, row = _q_and_row(layer, cfg, h, positions, window is not None)
        rows_of[p].append(row)
        with jax.named_scope("istpu.attn.window" if window is not None
                             else "istpu.attn.full"):
            k_pos, k_valid = q_pos, None
            if prefix_kv is not None:
                buf = prefix_kv[p][lp, 0, :, :, 0]          # [B, rows, W]
                if window is not None:
                    b_pos, b_valid = window_prefix_positions(buf.shape[1], start)
                else:
                    b_pos = jnp.arange(P)
                    b_valid = (None if prefix_len is None
                               else b_pos < prefix_len)
                if b_valid is not None:
                    k_valid = jnp.concatenate([b_valid, jnp.ones((S,), bool)])
                k_pos = jnp.concatenate([b_pos, q_pos])
                row = jnp.concatenate([buf, row], axis=1)
            k, v = split_kv_rows(row, cfg.kv_pages[p][0], cfg.head_dim)
            attn = grouped_chunk_attention(q, k, v, q_pos, k_pos, k_valid,
                                           window, layer.get("sink"))
        x = x + attn.reshape(B, S, -1) @ layer["wo"]
        ffn, _ = _ffn(layer, cfg, rmsnorm(x, layer["ln_mlp"], cfg.norm_eps))
        x = x + ffn
    return (head_logits(x, head, head_row, partial(_head, params, cfg)),
            tuple(jnp.stack(rows)[:, None, :, :, None, :] for rows in rows_of))


def mimo_v2_decode_forward(
    params: Params,
    cfg: MimoV2Config,
    tokens: jax.Array,
    positions: jax.Array,
    cache: Tuple[jax.Array, jax.Array],
    block_table: Tuple[jax.Array, jax.Array],
    seq_lens: jax.Array,
    slot_block_ids: Tuple[jax.Array, jax.Array],
    slot_ids: jax.Array,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], jax.Array]:
    """Single-token paged decode over a cache of TWO POOLS OF TWO PAGE SHAPES
    (kv/cache.PagedCacheConfig.pool_kv): ``cache``, ``block_table`` and
    ``slot_block_ids`` are pairs (the full layers' pool, the window layers'
    pool), each pool [its layers, 1, 1, its blocks, T, its row] with a table
    of its own.  A window layer gathers its window's pages out of its pool's
    table (the sink in its softmax), a full layer its pool's whole table.  The
    third value: the step's (token, expert) pairs whose expert is held here,
    over the live rows."""
    from ..kv.cache import write_token_rows

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
        split = (cfg.kv_pages[p][0], cfg.head_dim)
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, row = _q_and_row(layer, cfg, h, pos, window is not None)
        pools[p] = write_token_rows(pools[p], lp, slot_block_ids[p], slot_ids,
                                    row[:, 0, None, None, :])   # [B, 1, 1, W]
        if window is not None:
            with jax.named_scope("istpu.attn.window"):
                attn = paged_window_decode_attention(
                    q[:, 0], pools[p], lp, block_table[p], seq_lens, window,
                    sink=layer["sink"], kv_split=split)
        else:
            with jax.named_scope("istpu.attn.full"):
                attn = paged_decode_attention(
                    q[:, 0], pools[p], lp, block_table[p], seq_lens,
                    kv_split=split)
        x = x + (attn.reshape(B, -1) @ layer["wo"])[:, None, :]
        ffn, n = _ffn(layer, cfg, rmsnorm(x, layer["ln_mlp"], cfg.norm_eps),
                      live)
        n_local = n_local + n
        x = x + ffn
    return _head(params, cfg, x)[:, 0], tuple(pools), n_local


FAMILY = Family(name="mimo_v2_flash", config_cls=MimoV2Config,
                config_from_file=config_from_file, init=init_mimo_v2_params,
                prefill_fn=mimo_v2_prefill_forward,
                decode_fn=mimo_v2_decode_forward)
