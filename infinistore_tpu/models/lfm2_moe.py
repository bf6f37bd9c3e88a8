"""Gated short-convolution layers among grouped-query attention layers, with
routed experts (the ``lfm2_moe`` model type): a stack whose sequence keeps TWO
KINDS of cache, pages for its attention layers and a state of fixed size for
its convolution layers (kv/cache.py ``HybridCacheConfig``,
engine/hybrid_engine.py).

A block is ``x = x + Op(RMSNorm(x))``, then ``x = x + FFN(RMSNorm(x))``; the
parts shared with other families are imported, not copied (``rmsnorm``,
``_attn_qkv`` with Qwen3's per-head Q/K norm, ``_mlp``, ``head_logits`` of
models/llama.py; ``sigmoid_top_k`` and ``routed_experts`` of models/moe.py;
the paged attention of models/attention.py).  What is this family's own:

* **The ``conv`` operator** (``layer_types``): ``[B | C | u] = h W_in`` (dim ->
  3 x dim, no bias), ``v = B * u``, ``c_t = sum_j w[:, j] v_{t-(K-1)+j}``
  (depthwise, causal, ``K = conv_L_cache``, no bias; ``v`` before the
  sequence's start is zero), ``y = C * c``, ``out = y W_out``.  No position
  enters.  What a sequence keeps of such a layer is ``v`` at its last ``K - 1``
  positions, ``[(K - 1) x dim]`` values in the activations' type whatever its
  length (the published code keeps ``K`` rows and drops the oldest on the next
  step; the arithmetic is the same).  The sum runs in float32 over the
  bfloat16 rows and is the same expression in a chunk and in a decode step.
* **The ``full_attention`` operator**: grouped-query attention, heads of
  ``dim / n_heads`` (64 at the published widths), RMSNorm over each head of Q
  and K, rotary embedding, no bias; its K and V are the page.
* **The FFN**: the first ``num_dense_layers`` layers a SwiGLU of
  ``intermediate_size``; every later layer ``num_experts`` routed experts of
  ``moe_intermediate_size``, ``num_experts_per_tok`` a token, no shared
  expert: ``s = sigmoid(h W_r)`` in float32, the chosen are the top of ``s + b``
  (``use_expert_bias``: ``b`` chooses and does not weigh), weights ``s_chosen /
  (sum s_chosen + 1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``.
  The selection bias is SEEDED (normal x 0.02): with zeros no check could
  tell a bias that chooses from one that also weighs.
* **The head** is the tied embedding.

The cache the forwards take is the pair the engine holds: ``pages`` ``[attention
layers, 2, H_kv / 2, n_blocks, T, 2 D]`` (heads of 64 side by side in pairs:
``kv_pack``) and ``conv`` ``[slots, conv layers, (K - 1)
x dim]``, a running row's state in the slot the engine gave it.  A prefill
chunk of one row starts from its slot's state and leaves there the last ``K -
1`` rows of ``v`` OF REAL TOKENS (a padded tail never enters a state); a decode
step shifts each live row's state by one and writes it back (a pad row's slot
lies past the slots: its read clamps, its write is dropped).  No verify step,
no LoRA and no mesh path: ``serve`` refuses them at start-up.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import grouped_chunk_attention, paged_decode_attention
from .llama import Family, Params, _attn_qkv, _mlp, head_logits, rmsnorm
from .moe import routed_experts, sigmoid_top_k

# the epsilon of the chosen scores' normalisation, as the family's code has it
NORM_TOPK_EPS = 1e-6
BIAS_STD = 0.02         # the seeded selection bias: normal x this


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """Sizes under the names ``models.llama``'s shared parts read (``_attn_qkv``
    takes this config as it takes a ``LlamaConfig``); ``FAMILY_KEYS`` maps the
    source's ``config.json`` onto them."""

    vocab_size: int = 65536
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 11776            # the leading dense layers' SwiGLU
    moe_ffn_dim: int = 1536         # one expert's width
    n_experts: int = 64
    top_k: int = 4
    n_dense_layers: int = 2
    conv_kernel: int = 3            # K = conv_L_cache
    layer_types: Tuple[str, ...] = ("conv", "conv", "full_attention", "conv") * 10
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    routed_scaling: float = 1.0
    dtype: Any = jnp.bfloat16
    # what ``_attn_qkv`` asks of a config, fixed for this family
    qk_norm: bool = True
    attn_bias: bool = False
    rope_scaling: Any = None
    query_pre_attn_scalar: Any = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def kv_pack(self) -> int:
        """KV heads side by side in one row of a page: a head of 64 fills half
        of the TPU's 128-lane tile, and a lone 64 does not lower in the decode
        kernel ("Slice shape along dimension 5 must be aligned to tiling
        (128), but is 64"), so adjacent heads share a row in pairs
        (models/attention.py ``paged_decode_attention`` reads them so)."""
        n = max(1, 128 // self.head_dim)
        return n if 128 % self.head_dim == 0 and self.n_kv_heads % n == 0 else 1

    @property
    def kv_page(self) -> Tuple[int, int, int]:
        """(planes, rows of heads, width): K and V of the ATTENTION layers,
        ``kv_pack`` adjacent KV heads to a row (8 heads of 64: 4 rows of 128;
        the bytes are the same)."""
        return (2, self.n_kv_heads // self.kv_pack, self.head_dim * self.kv_pack)

    @property
    def attn_layers(self) -> Tuple[int, ...]:
        """The layers of the stack that keep pages."""
        return tuple(li for li, t in enumerate(self.layer_types)
                     if t == "full_attention")

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        """The layers of the stack that keep a state."""
        return tuple(li for li, t in enumerate(self.layer_types) if t == "conv")

    @property
    def conv_state_shape(self) -> Tuple[int, int]:
        """One conv layer's state of one sequence: ``v`` at its last ``K - 1``
        positions."""
        return (self.conv_kernel - 1, self.dim)

    # what a sequence keeps (kv/cache.py ``cache_kind``) and the kind's names
    # (``HybridCacheConfig.for_model``)
    cache_kind = "hybrid"
    page_layers = attn_layers
    state_layers = conv_layers

    @property
    def state_width(self) -> int:
        return int(np.prod(self.conv_state_shape))

    @property
    def expert_routing(self) -> Tuple[int, int, int]:
        """(expert layers, experts a token, experts a layer): what the step
        profiler counts routed pairs from (engine/stepprof.note_decode)."""
        return (self.n_layers - self.n_dense_layers, self.top_k, self.n_experts)


FAMILY_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_dim",
    "moe_intermediate_size": "moe_ffn_dim", "num_experts": "n_experts",
    "num_experts_per_tok": "top_k", "num_dense_layers": "n_dense_layers",
    "conv_L_cache": "conv_kernel", "norm_eps": "norm_eps",
    "routed_scaling_factor": "routed_scaling",
}
# what the equations here assume of the source
FAMILY_FIXED = {
    "model_type": "lfm2_moe", "conv_bias": False, "norm_topk_prob": True,
    "use_expert_bias": True, "tie_embedding": True,
}
FAMILY_OTHER = ("layer_types", "rope_parameters", "max_position_embeddings",
                "architectures", "torch_dtype", "dtype", "bos_token_id",
                "eos_token_id", "pad_token_id", "use_cache",
                "initializer_range", "transformers_version")


def config_from_file(path: str, spec: dict) -> Tuple[str, Lfm2MoeConfig, int]:
    """``(model_id, cfg, seed)`` from a ``--model`` file of this family:
    ``{"family": "lfm2_moe", "published": {config.json's keys}, "reduced":
    {"num_hidden_layers": n}, "seed": s}``.  Every size is stated and none is
    overridden; ``reduced`` may cut the depth only, to the source's FIRST ``n``
    layers: the leading dense layers, then whole periods of the layer
    pattern and at least four layers."""
    pub = spec.get("published", {})
    missing = sorted((set(FAMILY_KEYS) | {"layer_types", "rope_parameters"})
                     - set(pub))
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
    rope = pub["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"{path}: rope_type {rope.get('rope_type')!r}; this "
                         f"family rotates by the default rule only")
    types = tuple(pub["layer_types"])
    if (len(types) != pub["num_hidden_layers"]
            or set(types) - {"conv", "full_attention"}):
        raise ValueError(f"{path}: layer_types names 'conv' or "
                         f"'full_attention' for each of num_hidden_layers")
    cfg = Lfm2MoeConfig(**{f: pub[k] for k, f in FAMILY_KEYS.items()},
                        rope_theta=float(rope["rope_theta"]), layer_types=types)
    if cfg.dim % cfg.n_heads or cfg.n_heads % cfg.n_kv_heads:
        raise ValueError(f"{path}: {cfg.n_heads} query heads over "
                         f"{cfg.n_kv_heads} key/value heads of hidden_size "
                         f"{cfg.dim} / {cfg.n_heads}")
    reduced = spec.get("reduced", {})
    if set(reduced) - {"num_hidden_layers"}:
        raise ValueError(f"{path}: 'reduced' may change num_hidden_layers "
                         f"only, got {sorted(reduced)}")
    n_layers = reduced.get("num_hidden_layers", cfg.n_layers)
    nd = cfg.n_dense_layers
    # the pattern's period, read off the source's own list past the dense layers
    period = next((p for p in range(1, len(types))
                   if all(types[i] == types[i + p]
                          for i in range(nd, len(types) - p))), len(types))
    if not (isinstance(n_layers, int) and nd + 4 <= n_layers <= cfg.n_layers):
        raise ValueError(f"{path}: num_hidden_layers must be in "
                         f"[{nd + 4}, {cfg.n_layers}]")
    if n_layers < cfg.n_layers and (n_layers - nd) % period:
        raise ValueError(f"{path}: num_hidden_layers={n_layers} cuts a period "
                         f"of {period} layers after the {nd} dense ones")
    seed = spec.get("seed", 0)
    if not (isinstance(seed, int) and seed >= 0):
        raise ValueError(f"{path}: seed must be a non-negative integer")
    name = spec.get("name", "lfm2_moe")
    widths = "-".join(str(pub[k]) for k in sorted(FAMILY_KEYS)
                      if k != "num_hidden_layers")
    tag = hashlib.sha256((widths + str(types[:n_layers])).encode()
                         ).hexdigest()[:8]
    return (f"{name}-{tag}-l{n_layers}-seed{seed}",
            replace(cfg, n_layers=n_layers, layer_types=types[:n_layers]), seed)


def init_lfm2_moe_params(cfg: Lfm2MoeConfig, key: jax.Array) -> Params:
    """Random weights from ``key``, one jitted program; ``layers`` is a tuple
    of one dict a layer (the layers hold different leaves, and a layer's
    slice of a stacked expert leaf would be copied in every step:
    models/mla_moe.py).  Layer ``li`` draws from ``split(split(key, L + 1)[li],
    12)``: a conv operator 0 ``w_in``, 1 ``conv_w`` (fan-in ``K``), 2 ``w_out``;
    an attention operator 0-3 wq wk wv wo; 4-6 the dense SwiGLU; 7 the router
    (float32), 8 the selection bias (float32, normal x 0.02), 9-11 the
    experts; the embedding (and tied head) from ``split(key, L + 1)[L]``;
    normal / sqrt(fan_in) but the bias."""
    L, d, hd, K = cfg.n_layers, cfg.dim, cfg.head_dim, cfg.conv_kernel
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    E, f = cfg.n_experts, cfg.moe_ffn_dim

    def dense(key, shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def build(key):
        keys = jax.random.split(key, L + 1)
        layers = []
        for li in range(L):
            k = jax.random.split(keys[li], 12)
            layer = {"ln_attn": jnp.ones((d,), cfg.dtype),
                     "ln_mlp": jnp.ones((d,), cfg.dtype)}
            if cfg.layer_types[li] == "conv":
                layer.update(w_in=dense(k[0], (d, 3 * d), d),
                             conv_w=dense(k[1], (d, K), K),
                             w_out=dense(k[2], (d, d), d))
            else:
                layer.update(wq=dense(k[0], (d, nq), d),
                             wk=dense(k[1], (d, nkv), d),
                             wv=dense(k[2], (d, nkv), d),
                             wo=dense(k[3], (nq, d), nq),
                             q_norm=jnp.ones((hd,), cfg.dtype),
                             k_norm=jnp.ones((hd,), cfg.dtype))
            if li < cfg.n_dense_layers:
                layer.update(w_gate=dense(k[4], (d, cfg.ffn_dim), d),
                             w_up=dense(k[5], (d, cfg.ffn_dim), d),
                             w_down=dense(k[6], (cfg.ffn_dim, d), cfg.ffn_dim))
            else:
                layer.update(
                    router=dense(k[7], (d, E), d, jnp.float32),
                    router_bias=BIAS_STD * jax.random.normal(
                        k[8], (E,), jnp.float32),
                    w_gate=dense(k[9], (E, d, f), d),
                    w_up=dense(k[10], (E, d, f), d),
                    w_down=dense(k[11], (E, f, d), f))
            layers.append(layer)
        return {"embed": dense(keys[L], (cfg.vocab_size, d), d),
                "layers": tuple(layers), "ln_out": jnp.ones((d,), cfg.dtype)}

    return jax.jit(build)(key)


def route(layer: Params, cfg: Lfm2MoeConfig, flat: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """flat [N, dim] (normalised) -> (experts [N, k], weights [N, k] float32):
    sigmoid scores in float32 at full precision (the choice is discrete), the
    top of ``scores + bias`` chosen, their own scores weighing them."""
    scores = jax.nn.sigmoid(jnp.dot(
        flat.astype(jnp.float32), layer["router"],
        precision=jax.lax.Precision.HIGHEST))
    return sigmoid_top_k(scores, layer["router_bias"], cfg.top_k,
                         cfg.routed_scaling, eps=NORM_TOPK_EPS)


def _ffn(layer: Params, cfg: Lfm2MoeConfig, h: jax.Array) -> jax.Array:
    """The leading layers' dense SwiGLU, or the expert layer: which one a
    layer holds is its pytree's structure, static at trace time."""
    if "router" not in layer:
        return _mlp(layer, h)
    B, S, d = h.shape
    flat = h.reshape(B * S, d)
    with jax.named_scope("istpu.moe.route"):
        idx, w = route(layer, cfg, flat)
    with jax.named_scope("istpu.moe.experts"):
        y = routed_experts(flat, idx, w, layer["w_gate"], layer["w_up"],
                           layer["w_down"])
    return y.reshape(B, S, d)


def short_conv(layer: Params, cfg: Lfm2MoeConfig, h: jax.Array,
               state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The gated short convolution over h [B, S, dim] (normalised) that
    continues ``state`` [B, (K - 1) x dim], the ``K - 1`` rows of ``v`` before
    it (zeros at a sequence's start).  Returns ``(out [B, S, dim], v_ext [B, K
    - 1 + S, dim])``: the carried rows then the chunk's own, of which the
    caller keeps the ``K - 1`` it wants (a decode step the last; a padded chunk
    those before its padding).  Three shifted multiply-adds in float32."""
    B, S, d = h.shape
    K = cfg.conv_kernel
    bcu = h @ layer["w_in"]
    v = bcu[..., :d] * bcu[..., 2 * d:]
    v_ext = jnp.concatenate([state.reshape(B, K - 1, d), v], axis=1)
    w = layer["conv_w"].astype(jnp.float32)                 # [dim, K]
    c = sum(w[:, j] * v_ext[:, j: j + S].astype(jnp.float32) for j in range(K))
    y = bcu[..., d: 2 * d] * c.astype(h.dtype)
    return y @ layer["w_out"], v_ext


def _head(params: Params, cfg: Lfm2MoeConfig, x: jax.Array) -> jax.Array:
    x = rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["embed"])


def lfm2_moe_prefill_forward(
    params: Params,
    cfg: Lfm2MoeConfig,
    tokens: jax.Array,
    conv: jax.Array,
    slot: jax.Array,
    n_valid: jax.Array,
    prefix_kv: jax.Array | None = None,
    prefix_len: jax.Array | None = None,
    head: str = "all",
    head_row: jax.Array | None = None,
) -> Tuple[jax.Array | None, Tuple[jax.Array, jax.Array]]:
    """One prefill chunk of one row: tokens [1, S] -> (logits, (kv [attention
    layers, 2, 1, S] + ``kv_page``'s rows of heads, conv)).

    The attention layers keep the contract of ``models.llama.prefill_forward``
    over THEIR layers alone, K and V in the page's rows (``kv_page``):
    ``prefix_kv`` [attention layers, 2, 1, P, rows, width] is the reused
    prefix's K and V (exact, or a padded buffer of which ``prefix_len`` rows
    are valid), the returned rows cover the new tokens.
    The conv layers read and write ``conv`` [slots, conv layers, (K - 1) x
    dim], donated: the chunk starts from slot ``slot``'s state and leaves
    there ``v`` at the last ``K - 1`` of its first ``n_valid`` positions (the
    rest pad a last chunk to whole pages and enter no state; with fewer
    than ``K - 1`` valid the older rows stay).  ``head`` / ``head_row``: where
    the norm and the head run (``llama.head_logits``)."""
    B, S = tokens.shape
    assert B == 1, "a prefill chunk is one row's: its state is one slot's"
    P = 0 if prefix_kv is None else prefix_kv.shape[3]
    start = P if prefix_len is None else prefix_len
    q_pos = jnp.arange(S) + start
    positions = q_pos[None]
    x = params["embed"][tokens]
    kvs, ai, ci = [], 0, 0
    for li, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        if cfg.layer_types[li] == "conv":
            with jax.named_scope("istpu.conv.chunk"):
                at = (slot, ci, 0)
                state = jax.lax.dynamic_slice(
                    conv, at, (1, 1, conv.shape[2]))[:, 0]
                op, v_ext = short_conv(layer, cfg, h, state)
                # rows [n_valid, n_valid + K - 1) of the carried rows and the
                # chunk's: the K - 1 before the first position not computed
                kept = jax.lax.dynamic_slice_in_dim(
                    v_ext, n_valid, cfg.conv_kernel - 1, axis=1)
                conv = jax.lax.dynamic_update_slice(
                    conv, kept.reshape(1, 1, -1), at)
            ci += 1
        else:
            q, k, v = _attn_qkv(layer, cfg, h, positions)
            # the page's rows: ``kv_pack`` adjacent heads side by side
            kvs.append(jnp.stack([k, v], axis=0).reshape(
                (2, B, S) + cfg.kv_page[1:]))
            with jax.named_scope("istpu.attn.full"):
                k_pos, k_valid = q_pos, None
                if prefix_kv is not None:
                    by_head = (B, P, cfg.n_kv_heads, cfg.head_dim)
                    k = jnp.concatenate(
                        [prefix_kv[ai, 0].reshape(by_head), k], axis=1)
                    v = jnp.concatenate(
                        [prefix_kv[ai, 1].reshape(by_head), v], axis=1)
                    k_pos = jnp.concatenate([jnp.arange(P), q_pos])
                    if prefix_len is not None:
                        k_valid = jnp.concatenate(
                            [jnp.arange(P) < prefix_len, jnp.ones((S,), bool)])
                attn = grouped_chunk_attention(q, k, v, q_pos, k_pos, k_valid)
            op = attn.reshape(B, S, -1) @ layer["wo"]
            ai += 1
        x = x + op
        x = x + _ffn(layer, cfg, rmsnorm(x, layer["ln_mlp"], cfg.norm_eps))
    return head_logits(x, head, head_row, partial(_head, params, cfg)
                       ), (jnp.stack(kvs), conv)


def lfm2_moe_decode_forward(
    params: Params,
    cfg: Lfm2MoeConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: Tuple[jax.Array, jax.Array],
    block_table: Tuple[jax.Array, jax.Array],
    seq_lens: jax.Array,
    slot_block_ids: Tuple[jax.Array, jax.Array],
    slot_ids: jax.Array,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token decode under the engine's scan; the contract of
    ``models.llama.decode_forward`` over a cache of TWO KINDS: ``cache`` is
    ``(pages, conv)`` and ``block_table`` ``(the attention layers' table [B,
    width], each row's state slot [B, 1])``; ``slot_block_ids`` is a pair as
    the table is, its first the page a row's token is written to.  An
    attention layer writes the token's K and V and reads the row's live
    pages; a conv layer shifts each row's state by this token's ``v`` and
    writes it back.  A pad row names a page past the pool and a slot past the
    slots: both writes are dropped."""
    from ..kv.cache import write_token_kv

    pages, conv = cache
    table, rows = block_table[0], block_table[1][:, 0]
    B = tokens.shape[0]
    x = params["embed"][tokens][:, None, :]
    pos = positions[:, None]
    ai = ci = 0
    for li, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        if cfg.layer_types[li] == "conv":
            with jax.named_scope("istpu.conv.step"):
                op, v_ext = short_conv(layer, cfg, h, conv[rows, ci])
                conv = conv.at[rows, ci].set(
                    v_ext[:, 1:].reshape(B, -1), mode="drop")
            ci += 1
        else:
            q, k, v = _attn_qkv(layer, cfg, h, pos)
            pages = write_token_kv(
                pages, ai, slot_block_ids[0], slot_ids,
                k[:, 0].reshape((B,) + cfg.kv_page[1:]),
                v[:, 0].reshape((B,) + cfg.kv_page[1:]))
            with jax.named_scope("istpu.attn.full"):
                attn = paged_decode_attention(q[:, 0], pages, ai, table,
                                              seq_lens)
            op = (attn.reshape(B, -1) @ layer["wo"])[:, None, :]
            ai += 1
        x = x + op
        x = x + _ffn(layer, cfg, rmsnorm(x, layer["ln_mlp"], cfg.norm_eps))
    return _head(params, cfg, x[:, 0]), (pages, conv)


FAMILY = Family(name="lfm2_moe", config_cls=Lfm2MoeConfig,
                config_from_file=config_from_file, init=init_lfm2_moe_params,
                prefill_fn=lfm2_moe_prefill_forward, decode_fn=lfm2_moe_decode_forward)
