"""Latent attention over a one-plane page, and a stack of routed-expert
layers behind leading dense ones (the ``deepseek_v3`` model type).

What differs from models/llama.py, and where it lives:

* **The page** (``MlaMoeConfig.kv_page``): one plane of one row per token,
  ``[c (kv_lora_rank); k_r (qk_rope_head_dim)]`` -- the RMS-normalised latent
  and the one rotated key all heads share.  No K and V by head.  The cache,
  the transfer engine and the store take the page from
  ``PagedCacheConfig.for_model`` and move it as they move any page.
* **Two attention paths over that page** (models/attention.py):
  ``latent_expanded_attention`` for prefill chunks (keys and values
  up-projected from the latent, one head at a time's worth of weights) and
  ``latent_absorbed_decode_attention`` for the decode step (the query carried
  into the latent space; the value up-projection after the weighted sum).
  They are the same function of the page.
* **The expert layer** (models/moe.py ``routed_experts``): sigmoid scores, a
  selection bias, the top-k's scores normalised and scaled, experts computed
  for the tokens routed to them, shared experts beside them.  No token is
  dropped and no capacity is set.

Same contracts as ``models.llama.prefill_forward`` / ``decode_forward``, so
the engine, the scheduler, chunked prefill and the decode scan run it
unchanged.  There is no verify step (speculation), no LoRA and no mesh path
for this family: ``serve`` refuses them at start-up.

Rotary pairs are (2i, 2i+1) as everywhere in this package (attention.py
``apply_rope``): the published checkpoints' interleaved pairing, without the
permutation Hugging Face applies to reach its rotate-half form.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, replace
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import (
    apply_rope,
    latent_absorbed_decode_attention,
    latent_expanded_attention,
)
from .llama import Family, Params, _mlp, head_logits, rmsnorm
from .moe import routed_experts, sigmoid_top_k


@dataclass(frozen=True)
class MlaMoeConfig:
    """Sizes under the names of the source's ``config.json``'s meaning;
    ``FAMILY_KEYS`` maps the file's keys onto these fields."""

    vocab_size: int = 128256
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 6144             # the leading dense layers' width
    n_dense_layers: int = 1         # first_k_dense_replace
    n_experts: int = 128
    top_k: int = 6
    moe_ffn_dim: int = 768
    n_shared_experts: int = 2
    routed_scaling: float = 2.448
    norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_page(self) -> Tuple[int, int, int]:
        """(planes, heads, width) of what a token writes per layer: one
        latent row."""
        return (1, 1, self.latent_width)

    @property
    def expert_routing(self) -> Tuple[int, int, int]:
        """(expert layers, experts a token, experts a layer): what the step
        profiler counts routed pairs from (engine/stepprof.note_decode)."""
        return (self.n_layers - self.n_dense_layers, self.top_k,
                self.n_experts)


# config.json key -> field; every one of them is a published size the model
# file must state, and none but the depth may differ from the source
FAMILY_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "intermediate_size": "ffn_dim", "first_k_dense_replace": "n_dense_layers",
    "n_routed_experts": "n_experts", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "moe_ffn_dim",
    "n_shared_experts": "n_shared_experts",
    "routed_scaling_factor": "routed_scaling", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
}
# what the equations here assume of the source; a file that says otherwise
# names a model this module does not compute
FAMILY_FIXED = {
    "model_type": "deepseek_v3", "q_lora_rank": None, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "rope_scaling": None, "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "moe_layer_freq": 1, "rope_interleave": True,
}
# keys of the source that repeat a size above or that no equation reads; a
# repeated size must agree (``_consistent``)
FAMILY_OTHER = ("head_dim", "max_position_embeddings", "num_key_value_heads",
                "qk_head_dim", "architectures", "torch_dtype")


def config_from_file(path: str, spec: dict) -> Tuple[str, MlaMoeConfig, int]:
    """``(model_id, cfg, seed)`` from a ``--model`` file of this family:
    ``{"family": "deepseek_v3", "source": ..., "published": {config.json's
    keys}, "reduced": {"num_hidden_layers": n}, "seed": s}``.  Every key of
    ``FAMILY_KEYS`` must be there (a width is never defaulted and never
    overridden), the keys of ``FAMILY_FIXED`` must say what this module
    computes where they are given, and ``reduced`` may cut the depth only,
    to no fewer layers than the leading dense ones plus one."""
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
    for k, want in (("qk_head_dim", pub["qk_nope_head_dim"] + pub["qk_rope_head_dim"]),
                    ("num_key_value_heads", pub["num_attention_heads"])):
        if pub.get(k, want) != want:
            raise ValueError(f"{path}: {k}={pub[k]!r} does not follow from "
                             f"the other sizes ({want})")
    reduced = spec.get("reduced", {})
    if set(reduced) - {"num_hidden_layers"}:
        raise ValueError(f"{path}: 'reduced' may change num_hidden_layers "
                         f"only, got {sorted(reduced)}")
    cfg = MlaMoeConfig(**{f: pub[k] for k, f in FAMILY_KEYS.items()})
    n_layers = reduced.get("num_hidden_layers", cfg.n_layers)
    if not (isinstance(n_layers, int)
            and cfg.n_dense_layers < n_layers <= cfg.n_layers):
        raise ValueError(f"{path}: num_hidden_layers must be in "
                         f"({cfg.n_dense_layers}, {cfg.n_layers}]")
    seed = spec.get("seed", 0)
    if not (isinstance(seed, int) and seed >= 0):
        raise ValueError(f"{path}: seed must be a non-negative integer")
    # the id commits to everything the weights depend on
    name = spec.get("name", "deepseek_v3")
    widths = "-".join(str(pub[k]) for k in sorted(FAMILY_KEYS)
                      if k != "num_hidden_layers")
    tag = hashlib.sha256(widths.encode()).hexdigest()[:8]
    return (f"{name}-{tag}-l{n_layers}-seed{seed}",
            replace(cfg, n_layers=n_layers), seed)


def init_mla_moe_params(cfg: MlaMoeConfig, key: jax.Array) -> Params:
    """Random weights from ``key``, one jitted program.  ``layers`` is a
    tuple of one dict a layer, NOT leaves stacked over layers: the leading
    dense layers and the expert layers hold different leaves, and a layer's
    slice of a stacked expert leaf feeding the grouped matrix product is
    COPIED by XLA:TPU in every step (384 MB a leaf at the published widths,
    compiler's memory analysis), where a leaf of its own is read in place.

    Layer ``li`` draws from ``split(split(key, L + 2)[li], 12)``: 0 wq,
    1 w_kva, 2 w_kvb, 3 wo, 4-6 the dense FFN (leading layers), 7 the
    router, 8-10 the routed experts, ``split([11], 3)`` the shared experts;
    normal / sqrt(fan_in).  The router's matrix is float32; the selection
    bias is zeros (the checkpoint's values are not in ``config.json``)."""
    L, nd = cfg.n_layers, cfg.n_dense_layers
    d, H, E = cfg.dim, cfg.n_heads, cfg.n_experts
    f, fs = cfg.moe_ffn_dim, cfg.n_shared_experts * cfg.moe_ffn_dim
    kvb_out = H * (cfg.qk_nope_head_dim + cfg.v_head_dim)

    def dense(key, shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def build(key):
        keys = jax.random.split(key, L + 2)
        layers = []
        for li in range(L):
            k = jax.random.split(keys[li], 12)
            layer = {
                "wq": dense(k[0], (d, H * cfg.qk_head_dim), d),
                "w_kva": dense(k[1], (d, cfg.latent_width), d),
                "w_kvb": dense(k[2], (cfg.kv_lora_rank, kvb_out),
                               cfg.kv_lora_rank),
                "wo": dense(k[3], (H * cfg.v_head_dim, d), H * cfg.v_head_dim),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), cfg.dtype),
                "ln_attn": jnp.ones((d,), cfg.dtype),
                "ln_mlp": jnp.ones((d,), cfg.dtype),
            }
            if li < nd:
                layer.update(
                    w_gate=dense(k[4], (d, cfg.ffn_dim), d),
                    w_up=dense(k[5], (d, cfg.ffn_dim), d),
                    w_down=dense(k[6], (cfg.ffn_dim, d), cfg.ffn_dim))
            else:
                ks = jax.random.split(k[11], 3)
                layer.update(
                    router=dense(k[7], (d, E), d, jnp.float32),
                    router_bias=jnp.zeros((E,), jnp.float32),
                    w_gate=dense(k[8], (E, d, f), d),
                    w_up=dense(k[9], (E, d, f), d),
                    w_down=dense(k[10], (E, f, d), f),
                    ws_gate=dense(ks[0], (d, fs), d),
                    ws_up=dense(ks[1], (d, fs), d),
                    ws_down=dense(ks[2], (fs, d), fs))
            layers.append(layer)
        return {
            "embed": dense(keys[-2], (cfg.vocab_size, d), d),
            "layers": tuple(layers),
            "ln_out": jnp.ones((d,), cfg.dtype),
            "lm_head": dense(keys[-1], (d, cfg.vocab_size), d),
        }

    return jax.jit(build)(key)


def expert_layer(layer: Params, cfg: MlaMoeConfig, x: jax.Array) -> jax.Array:
    """x [B, S, dim] -> the routed experts' weighted sum plus the shared
    experts' SwiGLU.  Scores and the choice are float32."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    with jax.named_scope("istpu.moe.route"):
        # float32 at full precision: the choice of experts is discrete
        scores = jax.nn.sigmoid(jnp.dot(
            flat.astype(jnp.float32), layer["router"],
            precision=jax.lax.Precision.HIGHEST))
        idx, w = sigmoid_top_k(scores, layer["router_bias"], cfg.top_k,
                               cfg.routed_scaling)
    with jax.named_scope("istpu.moe.experts"):
        y = routed_experts(flat, idx, w, layer["w_gate"], layer["w_up"],
                           layer["w_down"])
    with jax.named_scope("istpu.moe.shared"):
        y = y + _mlp({"w_gate": layer["ws_gate"], "w_up": layer["ws_up"],
                      "w_down": layer["ws_down"]}, flat)
    return y.reshape(B, S, d)


def _ffn(layer: Params, cfg: MlaMoeConfig, h: jax.Array) -> jax.Array:
    """The leading layers' dense SwiGLU, or the expert layer: which one a
    layer holds is its pytree's structure, static at trace time."""
    return expert_layer(layer, cfg, h) if "router" in layer else _mlp(layer, h)


def _q_and_latent(layer: Params, cfg: MlaMoeConfig, h: jax.Array,
                  positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """h [B, S, dim] (normalised) -> q [B, S, H, nope + rope] with its rope
    part rotated, and the page row [B, S, rank + rope]: the normalised latent
    and the rotated shared key."""
    B, S, _ = h.shape
    R, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    q = (h @ layer["wq"]).reshape(B, S, cfg.n_heads, cfg.qk_head_dim)
    q = jnp.concatenate(
        [q[..., :cfg.qk_nope_head_dim],
         apply_rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta)],
        axis=-1)
    ckr = h @ layer["w_kva"]
    c = rmsnorm(ckr[..., :R], layer["kv_norm"], cfg.norm_eps)
    k_r = apply_rope(ckr[..., None, R:], positions, cfg.rope_theta)[..., 0, :]
    return q, jnp.concatenate([c, k_r], axis=-1)


def mla_moe_prefill_forward(
    params: Params,
    cfg: MlaMoeConfig,
    tokens: jax.Array,
    prefix_kv: jax.Array | None = None,
    prefix_len: jax.Array | None = None,
    head: str = "all",
    head_row: jax.Array | None = None,
) -> Tuple[jax.Array | None, jax.Array]:
    """tokens [B, S] -> (logits [B, S, V], rows [L, 1, B, S, 1, rank+rope]).

    The contract of ``models.llama.prefill_forward`` with the page's one
    plane where K and V stood: ``prefix_kv`` [L, 1, B, P, 1, W] is the
    reused prefix's rows (a padded buffer of which ``prefix_len`` are valid),
    the returned rows cover the new tokens only.  Attention runs EXPANDED:
    the prefix's and the chunk's rows are up-projected to keys and values
    by head (attention.latent_expanded_attention).  ``head`` / ``head_row``:
    where the norm and the head run, as there (``llama.head_logits``)."""
    B, S = tokens.shape
    P = 0 if prefix_kv is None else prefix_kv.shape[3]
    start = P if prefix_len is None else prefix_len
    positions = jnp.broadcast_to(jnp.arange(S) + start, (B, S))
    x = params["embed"][tokens]
    rows = []
    for li, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, ckr = _q_and_latent(layer, cfg, h, positions)
        rows.append(ckr[None, :, :, None, :])      # [1, B, S, 1, W]
        full = ckr if prefix_kv is None else jnp.concatenate(
            [prefix_kv[li, 0, :, :, 0], ckr], axis=1)
        attn = latent_expanded_attention(
            q, full, layer["w_kvb"], cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            q_offset=P, prefix_pad=P if prefix_len is not None else None,
            prefix_len=prefix_len)
        x = x + attn.reshape(B, S, -1) @ layer["wo"]
        h = rmsnorm(x, layer["ln_mlp"], cfg.norm_eps)
        x = x + _ffn(layer, cfg, h)
    return head_logits(
        x, head, head_row,
        lambda x: rmsnorm(x, params["ln_out"], cfg.norm_eps)
        @ params["lm_head"]), jnp.stack(rows)


def mla_moe_decode_forward(
    params: Params,
    cfg: MlaMoeConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: jax.Array,
    block_table: jax.Array,
    seq_lens: jax.Array,
    slot_block_ids: jax.Array,
    slot_ids: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Single-token paged decode; the contract of
    ``models.llama.decode_forward`` over the one-plane cache
    [L, 1, 1, n_blocks, T, rank+rope].  Each layer writes the token's row
    into its page slot and attends ABSORBED: 32 queries in the latent space
    over one key row per token, gathered by (layer, page) index out of the
    whole cache (attention.latent_absorbed_decode_attention)."""
    from ..kv.cache import write_token_rows

    B = tokens.shape[0]
    x = params["embed"][tokens][:, None, :]
    pos = positions[:, None]
    for li, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, ckr = _q_and_latent(layer, cfg, h, pos)
        cache = write_token_rows(cache, li, slot_block_ids, slot_ids,
                                 ckr[:, 0, None, None, :])   # [B, 1, 1, W]
        attn = latent_absorbed_decode_attention(
            q[:, 0], cache, li, block_table, seq_lens, layer["w_kvb"],
            cfg.kv_lora_rank, cfg.qk_nope_head_dim)
        x = x + (attn.reshape(B, -1) @ layer["wo"])[:, None, :]
        h = rmsnorm(x, layer["ln_mlp"], cfg.norm_eps)
        x = x + _ffn(layer, cfg, h)
    x = rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return x[:, 0] @ params["lm_head"], cache


FAMILY = Family(name="deepseek_v3", config_cls=MlaMoeConfig,
                config_from_file=config_from_file, init=init_mla_moe_params,
                prefill_fn=mla_moe_prefill_forward, decode_fn=mla_moe_decode_forward)
