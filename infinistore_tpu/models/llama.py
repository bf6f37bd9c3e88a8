"""Llama-3 family in pure JAX (pytree params, bf16, GQA, RoPE, SwiGLU).

The reference serves Llama via vLLM and only moves its KV; a TPU-native
framework owns the model too.  Design: params are a plain pytree (dict) so
``jax.sharding`` specs attach cleanly (parallel/sharding.py); all forwards
are pure functions of (params, inputs) with the config closed over as a
static argument -- one XLA program per shape, MXU-sized matmuls in bf16.

Three entry points:
* ``prefill_forward``  -- full-sequence causal forward; returns logits and
  per-layer KV laid out for paging ([L, 2, B, S, Hkv, D]).
* ``decode_forward``   -- single-token step against the paged HBM cache
  (kv/cache.py), returning logits and the updated cache.
* ``train_step_fn``    -- next-token cross-entropy + SGD update (used by the
  multi-chip dry run; serving frameworks still need a tuning path).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import (
    apply_rope,
    causal_attention,
    chunk_kernel_layers,
    paged_decode_attention,
    paged_multitoken_attention_xla,
    prefix_attention_args,
)

Params = Dict[str, Any]


@dataclass(frozen=True)
class Family:
    """What a model family is to everything above its module, stated once
    at the module's end (``FAMILY``) and held in ``models.FAMILIES``: the
    ``family`` a config file names, the config's type, the reading of such a
    file (``(path, spec) -> (model_id, cfg, seed)``), the weights from a key,
    and the forwards the engine's hooks take.  The dense family's record has
    no forwards of its own: the engine's defaults are its."""

    name: Optional[str]
    config_cls: type
    config_from_file: Callable
    init: Callable
    prefill_fn: Optional[Callable] = None
    decode_fn: Optional[Callable] = None

    @property
    def fns(self) -> Dict[str, Callable]:
        """The engine's keyword arguments; empty for the dense family."""
        return {k: f for k, f in (("prefill_fn", self.prefill_fn),
                                  ("decode_fn", self.decode_fn)) if f}


@dataclass(frozen=True)
class LlamaConfig:
    """One dense-decoder config covering the Llama/Mistral/Qwen families.

    The reference serves all of these through vLLM's model zoo; here one
    parametric architecture covers them: ``attn_bias`` (Qwen2/2.5 QKV
    biases), ``qk_norm`` (Qwen3 per-head RMSNorm on Q/K before RoPE),
    ``sliding_window`` (Mistral-style windowed causal attention), and
    ``head_dim_override`` (Qwen3 decouples head_dim from dim/n_heads)."""

    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    # Llama-3.1-style context-extension RoPE remap: (factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings); a tuple, not a
    # dict, so the frozen config stays hashable (attention.rope_freqs)
    rope_scaling: Tuple[float, float, float, int] | None = None
    attn_bias: bool = False  # QKV projection biases (Qwen2/2.5)
    qk_norm: bool = False  # per-head RMSNorm on Q/K before RoPE (Qwen3)
    # attend only to the last N positions (Mistral SWA).  When EVERY layer
    # is windowed (pattern 1) the engine returns window-dead pages to the
    # pool (engine._reclaim_window_pages).  A mixed local/global stack of
    # THIS module (Gemma-2) keeps and gathers all pages (one pool, a block
    # spans the layer stack) and the mask hides them; a family whose config
    # names its window layers (cfg.layer_windows) gives them a page
    # pool of their own: a sequence holds their pages for its window only,
    # gathers those, and fetches no others from the store.
    sliding_window: int | None = None
    # the window applies to layers with ``li % window_pattern == 0``
    # (Gemma-2 alternates local/global attention: pattern 2); pattern 1 =
    # every layer (Mistral)
    window_pattern: int = 1
    head_dim_override: int | None = None
    # --- Gemma-2 family knobs ---
    act: str = "silu"  # "gelu_tanh" (GeGLU) for Gemma
    attn_softcap: float | None = None   # tanh soft-cap on attention logits
    final_softcap: float | None = None  # tanh soft-cap on output logits
    norm_offset: bool = False           # RMSNorm scales by (1 + w)
    post_norms: bool = False            # post-attn/post-ffn norms (sandwich)
    embed_scale: bool = False           # hidden state scaled by sqrt(dim)
    # attention scale becomes 1/sqrt(query_pre_attn_scalar) when set
    query_pre_attn_scalar: float | None = None
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.dim // self.n_heads

    @property
    def kv_page(self) -> Tuple[int, int, int]:
        """(planes, heads, width) of what a token writes per layer: K and V
        by KV head (kv/cache.py ``PagedCacheConfig.for_model``)."""
        return (2, self.n_kv_heads, self.head_dim)


# -- presets (Llama-3 shapes) --
LLAMA3_8B = LlamaConfig()
LLAMA3_70B = LlamaConfig(
    dim=8192, n_layers=80, n_heads=64, n_kv_heads=8, ffn_dim=28672
)
LLAMA3_1B = LlamaConfig(  # Llama-3.2-1B shapes
    dim=2048, n_layers=16, n_heads=32, n_kv_heads=8, ffn_dim=8192
)
TINY = LlamaConfig(
    vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=256
)

# -- sibling dense families (same machinery, different knobs) --
MISTRAL_7B = LlamaConfig(  # v0.1 shapes: windowed attention, theta 1e4
    vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, rope_theta=10000.0, sliding_window=4096,
)
QWEN25_7B = LlamaConfig(  # QKV biases
    vocab_size=152064, dim=3584, n_layers=28, n_heads=28, n_kv_heads=4,
    ffn_dim=18944, rope_theta=1000000.0, norm_eps=1e-6, attn_bias=True,
)
QWEN3_8B = LlamaConfig(  # Q/K norm, decoupled head_dim
    vocab_size=151936, dim=4096, n_layers=36, n_heads=32, n_kv_heads=8,
    ffn_dim=12288, rope_theta=1000000.0, norm_eps=1e-6, qk_norm=True,
    head_dim_override=128,
)
GEMMA2_9B = LlamaConfig(  # GeGLU, softcaps, sandwich norms, local/global
    vocab_size=256000, dim=3584, n_layers=42, n_heads=16, n_kv_heads=8,
    ffn_dim=14336, rope_theta=10000.0, norm_eps=1e-6,
    head_dim_override=256, act="gelu_tanh", attn_softcap=50.0,
    final_softcap=30.0, norm_offset=True, post_norms=True, embed_scale=True,
    query_pre_attn_scalar=256.0, sliding_window=4096, window_pattern=2,
)


def scaled(cfg: LlamaConfig, **kw) -> LlamaConfig:
    return replace(cfg, **kw)


def config_from_file(path: str, spec: dict) -> Tuple[str, LlamaConfig, int]:
    """``(model_id, cfg, seed)`` of a checked-in model config file
    (``configs/*.json``) that names no ``family`` (a file that names one is
    read by that family's module: ``models.load_config_file``).  Such a file
    names a dense
    preset: a preset of this module by name, the
    ``published`` sizes it must agree with (so a jax-free launcher can read
    them from the file, and a file cannot quietly serve other widths), a
    ``reduced`` block that may cut ``n_layers`` only (widths are never cut
    — a toy width measures overheads, not the model), and the seed the
    weights are drawn from.  The id commits to everything the weights
    depend on, so two files that build different weights never share store
    keys.  Other keys (``source``, ``stands_for``, ``assumed``) document
    the cut."""
    base = globals().get(spec.get("preset"))
    if type(base) is not LlamaConfig:
        raise ValueError(f"{path}: preset {spec.get('preset')!r} is not a "
                         f"dense preset of infinistore_tpu.models")
    for k, v in spec.get("published", {}).items():
        if getattr(base, k, None) != v:
            raise ValueError(f"{path}: published {k}={v!r} is not preset "
                             f"{spec['preset']}'s {getattr(base, k, None)!r}"
                             f" (widths are never overridden)")
    reduced = spec.get("reduced", {})
    if set(reduced) - {"n_layers"}:
        raise ValueError(f"{path}: 'reduced' may change n_layers only, got "
                         f"{sorted(reduced)}")
    n_layers = reduced.get("n_layers", base.n_layers)
    if not (isinstance(n_layers, int) and 1 <= n_layers <= base.n_layers):
        raise ValueError(f"{path}: n_layers must be in [1, {base.n_layers}]")
    seed = spec.get("seed", 0)
    if not (isinstance(seed, int) and seed >= 0):
        raise ValueError(f"{path}: seed must be a non-negative integer")
    model_id = f"{spec['preset'].lower()}-l{n_layers}-seed{seed}"
    return model_id, replace(base, n_layers=n_layers), seed


def init_params(cfg: LlamaConfig, key: jax.Array, out_shardings=None) -> Params:
    """Random weights from ``key``.  One jitted program that draws every
    stacked leaf ([n_layers, ...], scan-friendly, pp-shardable) directly, so
    the peak is the weights plus one leaf's temporaries — never a per-layer
    copy next to the stack — and, with ``out_shardings`` (a pytree of
    shardings matching the result), each device only ever holds its shard:
    that is what lets a model larger than one chip initialise on a mesh.
    Layer ``li`` draws from ``split(split(key, L + 2)[li], 10)``."""
    hd = cfg.head_dim
    L = cfg.n_layers
    # with the Gemma (1 + w) convention, zeros give identity scale
    ln_one = (jnp.zeros if cfg.norm_offset else jnp.ones)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(
            cfg.dtype
        )

    def build(key):
        keys = jax.random.split(key, L + 2)
        lk = jax.vmap(lambda k: jax.random.split(k, 10))(keys[:L])  # [L, 10]

        def stacked(i, shape, fan_in):
            return jax.vmap(lambda k: dense(k, shape, fan_in))(lk[:, i])

        layers = {
            "wq": stacked(0, (cfg.dim, cfg.n_heads * hd), cfg.dim),
            "wk": stacked(1, (cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
            "wv": stacked(2, (cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
            "wo": stacked(3, (cfg.n_heads * hd, cfg.dim), cfg.n_heads * hd),
            "w_gate": stacked(4, (cfg.dim, cfg.ffn_dim), cfg.dim),
            "w_up": stacked(5, (cfg.dim, cfg.ffn_dim), cfg.dim),
            "w_down": stacked(6, (cfg.ffn_dim, cfg.dim), cfg.ffn_dim),
            "ln_attn": ln_one((L, cfg.dim), cfg.dtype),
            "ln_mlp": ln_one((L, cfg.dim), cfg.dtype),
        }
        if cfg.post_norms:  # Gemma-2 sandwich norms
            layers["ln_post_attn"] = ln_one((L, cfg.dim), cfg.dtype)
            layers["ln_post_mlp"] = ln_one((L, cfg.dim), cfg.dtype)
        if cfg.attn_bias:
            layers["bq"] = stacked(7, (cfg.n_heads * hd,), cfg.dim)
            layers["bk"] = stacked(8, (cfg.n_kv_heads * hd,), cfg.dim)
            layers["bv"] = stacked(9, (cfg.n_kv_heads * hd,), cfg.dim)
        if cfg.qk_norm:
            layers["q_norm"] = jnp.ones((L, hd), cfg.dtype)
            layers["k_norm"] = jnp.ones((L, hd), cfg.dtype)
        return {
            "embed": dense(keys[-2], (cfg.vocab_size, cfg.dim), cfg.dim),
            "layers": layers,
            "ln_out": ln_one((cfg.dim,), cfg.dtype),
            "lm_head": dense(keys[-1], (cfg.dim, cfg.vocab_size), cfg.dim),
        }

    return jax.jit(build, out_shardings=out_shardings)(key)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float,
            offset: bool = False) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if offset:
        # Gemma convention: scale by (1 + w) in f32, then cast (HF
        # Gemma2RMSNorm) — checkpoints store w around 0, not around 1
        return ((x32 * scale) * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return (x32 * scale).astype(x.dtype) * w


def _norm(cfg: LlamaConfig, x: jax.Array, w: jax.Array) -> jax.Array:
    return rmsnorm(x, w, cfg.norm_eps, offset=cfg.norm_offset)


def _window_for(cfg: LlamaConfig, li: int) -> int | None:
    """Per-layer sliding window: Gemma-2 alternates local/global layers
    (window_pattern=2); Mistral windows every layer (pattern=1)."""
    if cfg.sliding_window is None or li % cfg.window_pattern != 0:
        return None
    return cfg.sliding_window


def _embed(params: Params, cfg: LlamaConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embed_scale:  # Gemma: hidden scaled by sqrt(dim), in model dtype
        x = x * jnp.asarray(np.sqrt(cfg.dim), dtype=x.dtype)
    return x


def _final_logits(params: Params, cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    logits = x @ params["lm_head"]
    if cfg.final_softcap is not None:
        capped = cfg.final_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_softcap
        )
        logits = capped.astype(logits.dtype)
    return logits


def head_logits(x: jax.Array, head: str, head_row, project):
    """A prefill's logits where a row of them is kept: ``project`` (a
    family's final norm and output head) over what ``head`` names of the
    last hidden states ``x`` [B, S, D].  ``head`` is STATIC: ``"all"``, every
    position (logits [B, S, V]); ``"row"``, one position a row, ``x[b,
    head_row[b]]`` for the ``n <= B`` rows that the TRACED int32 ``head_row``
    [n] names (logits [n, V]: a prompt's last chunk keeps one row, the
    others would be thrown away); ``"none"``, nothing (``None``: a chunk
    that another follows runs no norm and no head)."""
    if head == "none":
        return None
    if head == "row":
        x = x[jnp.arange(head_row.shape[0]), head_row]
    elif head != "all":
        raise ValueError(f"prefill head {head!r}: 'all', 'row' or 'none'")
    return project(x)


def _lora_term(x, lora, name, ids, scale):
    """Batched adapter delta for one projection (models/lora.py), or 0."""
    if lora is None or name not in lora:
        return 0
    from .lora import lora_delta

    A, B = lora[name]
    return lora_delta(x, A, B, ids, scale)


def _layer_lora(bank_tree, li: int):
    from .lora import layer_lora

    return layer_lora(bank_tree, li)


def _attn_qkv(layer: Params, cfg: LlamaConfig, x: jax.Array, positions: jax.Array,
              lora=None, adapter_ids=None, lora_scale: float = 1.0):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q, k, v = x @ layer["wq"], x @ layer["wk"], x @ layer["wv"]
    if lora is not None:
        q = q + _lora_term(x, lora, "wq", adapter_ids, lora_scale)
        k = k + _lora_term(x, lora, "wk", adapter_ids, lora_scale)
        v = v + _lora_term(x, lora, "wv", adapter_ids, lora_scale)
    if cfg.attn_bias:
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:  # per-head RMSNorm before RoPE (Qwen3)
        q = rmsnorm(q, layer["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, layer["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    if cfg.query_pre_attn_scalar is not None:
        # attention kernels divide by sqrt(head_dim); pre-scaling q makes
        # the net scale 1/sqrt(query_pre_attn_scalar) (Gemma-2)
        q = q * jnp.asarray(
            np.sqrt(hd) / np.sqrt(cfg.query_pre_attn_scalar), dtype=q.dtype
        )
    return q, k, v


def _mlp(layer: Params, x: jax.Array, cfg: LlamaConfig | None = None) -> jax.Array:
    gate = x @ layer["w_gate"]
    if cfg is not None and cfg.act == "gelu_tanh":  # GeGLU (Gemma)
        act = jax.nn.gelu(gate, approximate=True)
    else:
        act = jax.nn.silu(gate)
    return (act * (x @ layer["w_up"])) @ layer["w_down"]


def _layer(ix: int):
    def get(stacked: Params) -> Params:
        return jax.tree.map(lambda x: x[ix], stacked)

    return get


def prefill_forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,
    prefix_kv: jax.Array | None = None,
    use_pallas: bool = True,
    prefix_len: jax.Array | None = None,
    lora=None,
    adapter_ids: jax.Array | None = None,
    lora_scale: float = 1.0,
    head: str = "all",
    head_row: jax.Array | None = None,
) -> Tuple[jax.Array | None, jax.Array]:
    """tokens: [B, S] -> (logits [B, S, V], kv [L, 2, B, S, Hkv, D]).

    ``head`` (static) says where the final norm and the output head run
    (``head_logits``): ``"all"`` positions, the default and the form above;
    ``"row"``, at ``head_row`` alone (traced int32 [n]: logits [n, V], the
    same norm and head over one position a row); ``"none"``, nowhere
    (logits ``None``).  The KV is the same in all three.

    ``prefix_kv`` ([L, 2, B, P, Hkv, D], RoPE already applied) enables
    chunked prefill on top of a reused prefix: ``tokens`` are positions
    P..P+S-1 and attend to the prefix KV plus themselves causally.  The
    returned KV covers only the new tokens.

    ``prefix_len`` (traced int32 scalar): when ``prefix_kv`` is a padded
    buffer, only its first ``prefix_len`` rows are valid — the token
    positions start there and the slack is masked out of attention.  Keeping
    the buffer at a few bucketed capacities bounds chunked prefill's
    compile count (engine/engine.py).

    ``use_pallas`` is accepted and unused: benchmarks/aot_check.py and
    benchmarks/tests/test_benchmark.py still pass it (ROADMAP C13).
    """
    B, S = tokens.shape
    P = 0 if prefix_kv is None else prefix_kv.shape[3]
    start = P if prefix_len is None else prefix_len
    positions = jnp.broadcast_to(jnp.arange(S) + start, (B, S))
    x = _embed(params, cfg, tokens)
    kvs = []
    for li in range(cfg.n_layers):
        layer = _layer(li)(params["layers"])
        ll = None if lora is None else _layer_lora(lora, li)
        win = _window_for(cfg, li)
        h = _norm(cfg, x, layer["ln_attn"])
        q, k, v = _attn_qkv(layer, cfg, h, positions,
                            lora=ll, adapter_ids=adapter_ids,
                            lora_scale=lora_scale)
        kvs.append(jnp.stack([k, v], axis=0))  # [2, B, S, Hkv, D]
        if prefix_kv is None:
            attn = causal_attention(
                q, k, v, window=win, softcap=cfg.attn_softcap,
            )
        else:
            k_full = jnp.concatenate([prefix_kv[li, 0], k], axis=1)
            v_full = jnp.concatenate([prefix_kv[li, 1], v], axis=1)
            attn = causal_attention(
                q, k_full, v_full, window=win, softcap=cfg.attn_softcap,
                **prefix_attention_args(P, prefix_len),
            )
        a = attn.reshape(B, S, -1)
        a = a @ layer["wo"] + _lora_term(a, ll, "wo", adapter_ids, lora_scale)
        if cfg.post_norms:
            a = _norm(cfg, a, layer["ln_post_attn"])
        x = x + a
        h = _norm(cfg, x, layer["ln_mlp"])
        m = _mlp(layer, h, cfg)
        if cfg.post_norms:
            m = _norm(cfg, m, layer["ln_post_mlp"])
        x = x + m
    return head_logits(
        x, head, head_row,
        lambda x: _final_logits(params, cfg, _norm(cfg, x, params["ln_out"])),
    ), jnp.stack(kvs)


def prefill_kernel_layers(cfg: LlamaConfig, tokens, prefix_kv=None,
                          prefix_len=None, *, windows=None, **_) -> int:
    """How many layers of the program ``prefill_forward`` makes of these
    arguments (arrays or abstract values) run their attention in the TPU's
    chunk kernel when the program is lowered for one: the attention's own
    test, layer by layer, on the call the loop above builds (q of the
    model's dtype, K and V the prefix buffer's rows and the chunk's own,
    the layer's window, the model's soft cap).  ``windows``: another
    forward's windows, a layer each (models/moe.py).  An engine finds it as
    its prefill function's ``kernel_layers``."""
    B, S = tokens.shape
    P = None if prefix_kv is None else prefix_kv.shape[3]
    q = jax.ShapeDtypeStruct((B, S, cfg.n_heads, cfg.head_dim), cfg.dtype)
    kv = jax.ShapeDtypeStruct(
        (B, (P or 0) + S, cfg.n_kv_heads, cfg.head_dim),
        cfg.dtype if P is None else jnp.result_type(prefix_kv.dtype, cfg.dtype))
    if windows is None:
        windows = [_window_for(cfg, li) for li in range(cfg.n_layers)]
    return chunk_kernel_layers(q, kv, P, prefix_len, windows, cfg.attn_softcap)


prefill_forward.kernel_layers = prefill_kernel_layers


def decode_forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: jax.Array,
    block_table: jax.Array,
    seq_lens: jax.Array,
    slot_block_ids: jax.Array,
    slot_ids: jax.Array,
    use_pallas: bool = True,
    lora=None,
    adapter_ids: jax.Array | None = None,
    lora_scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Single-token paged decode.

    ``use_pallas`` is accepted and unused: benchmarks/aot_check.py and
    benchmarks/tests/test_benchmark.py still pass it (ROADMAP C13).

    tokens/positions: [B]; cache: [L, 2, Hkv, n_blocks, T, D]
    (kv/cache.py layout -- heads outside blocks, so a (head, page) tile
    [T, D] is contiguous); block_table: [B, max_pages]; seq_lens: [B]
    (*including* this token); slot_block_ids/slot_ids: [B] where to scatter
    this token's K/V.  Returns (logits [B, V], updated cache).

    The layers are unrolled and each hands the WHOLE cache and its index to
    the write and to the attention, which find that layer's pages by index
    (write_token_kv; attention.paged_decode_attention: on a TPU the kernel
    that copies each row's live pages by the table, elsewhere and under a
    window or a soft cap the gather of the whole table,
    attention.gather_layer_kv): ``cache[li]`` is never formed, because
    XLA:TPU copies the layer's slab when a slice feeds a gather or a call.
    """
    from ..kv.cache import write_token_kv

    B = tokens.shape[0]
    x = _embed(params, cfg, tokens)[:, None, :]  # [B, 1, dim]
    pos = positions[:, None]
    for li in range(cfg.n_layers):
        layer = _layer(li)(params["layers"])
        ll = None if lora is None else _layer_lora(lora, li)
        h = _norm(cfg, x, layer["ln_attn"])
        q, k, v = _attn_qkv(layer, cfg, h, pos, lora=ll,
                            adapter_ids=adapter_ids, lora_scale=lora_scale)
        # scatter this token's kv into its page slot
        cache = write_token_kv(cache, li, slot_block_ids, slot_ids, k[:, 0], v[:, 0])
        attn = paged_decode_attention(
            q[:, 0], cache, li, block_table, seq_lens,
            window=_window_for(cfg, li), softcap=cfg.attn_softcap,
        )
        a = attn.reshape(B, -1)[:, None, :]
        a = a @ layer["wo"] + _lora_term(a, ll, "wo", adapter_ids, lora_scale)
        if cfg.post_norms:
            a = _norm(cfg, a, layer["ln_post_attn"])
        x = x + a
        h = _norm(cfg, x, layer["ln_mlp"])
        m = _mlp(layer, h, cfg)
        if cfg.post_norms:
            m = _norm(cfg, m, layer["ln_post_mlp"])
        x = x + m
    x = _norm(cfg, x, params["ln_out"])
    return _final_logits(params, cfg, x[:, 0]), cache


def verify_forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: jax.Array,
    block_table: jax.Array,
    slot_block_ids: jax.Array,
    slot_ids: jax.Array,
    lora=None,
    adapter_ids: jax.Array | None = None,
    lora_scale: float = 1.0,
    last_only: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Multi-token paged step: process a short run of tokens against the
    paged cache in ONE forward (the speculative-decode verify step — the
    target model scores all draft proposals at once instead of one
    dispatch per token).

    tokens/positions/slot_block_ids/slot_ids: [B, S]; cache:
    [L, 2, Hkv, n_blocks, T, D]; block_table: [B, max_pages].  The tokens'
    K/V are scattered into their page slots first, then each token attends
    to the paged history plus the run causally by absolute position.
    Returns (logits [B, S, V], updated cache).  The row after the FINAL
    token is the bonus-token distribution speculative decoding samples
    from — the device-resident reconcile in engine/speculative.py reads
    it straight out of the same compiled program instead of re-verifying
    on the host.

    ``last_only=True`` (static) projects only the final position through
    ``lm_head`` and returns logits [B, 1, V]: a resync/refresh step that
    only needs the next-token distribution skips S-1 wasted [dim, V]
    projections — at Llama vocab sizes the lm_head matmul dominates a
    short verify, so the fused rounds' per-round draft resync uses this
    form.
    """
    from ..kv.cache import write_tokens_kv

    B, S = tokens.shape
    x = _embed(params, cfg, tokens)  # [B, S, dim]
    for li in range(cfg.n_layers):
        layer = _layer(li)(params["layers"])
        ll = None if lora is None else _layer_lora(lora, li)
        h = _norm(cfg, x, layer["ln_attn"])
        q, k, v = _attn_qkv(layer, cfg, h, positions, lora=ll,
                            adapter_ids=adapter_ids, lora_scale=lora_scale)
        cache = write_tokens_kv(cache, li, slot_block_ids, slot_ids, k, v)
        attn = paged_multitoken_attention_xla(
            q, cache, li, block_table, positions, window=_window_for(cfg, li),
            softcap=cfg.attn_softcap,
        )
        a = attn.reshape(B, S, -1)
        a = a @ layer["wo"] + _lora_term(a, ll, "wo", adapter_ids, lora_scale)
        if cfg.post_norms:
            a = _norm(cfg, a, layer["ln_post_attn"])
        x = x + a
        h = _norm(cfg, x, layer["ln_mlp"])
        m = _mlp(layer, h, cfg)
        if cfg.post_norms:
            m = _norm(cfg, m, layer["ln_post_mlp"])
        x = x + m
    x = _norm(cfg, x, params["ln_out"])
    if last_only:
        x = x[:, -1:]
    return _final_logits(params, cfg, x), cache


def loss_fn(params: Params, cfg: LlamaConfig, tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy over [B, S] tokens."""
    logits, _ = prefill_forward(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean()


def train_step_fn(cfg: LlamaConfig, lr: float = 1e-3):
    def step(params: Params, tokens: jax.Array):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, cfg, tokens))(params)
        params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
        return params, loss

    return step


# a file that names no ``family`` is this one's (``models.load_config_file``)
FAMILY = Family(name=None, config_cls=LlamaConfig,
                config_from_file=config_from_file, init=init_params)
