"""Mixtral-style sparse-MoE Llama: shared attention, top-k routed experts.

Second model family beyond dense Llama (the reference serves whatever vLLM
loads; a standalone framework owns its model zoo).  Design mirrors
models/llama.py: params are a plain pytree with a stacked [n_layers] leaf
axis, forwards are pure functions, bf16 matmuls sized for the MXU.

The module has ONE expert layer, ``routed_experts``: each token's k
(token, expert) pairs are sorted by expert and the three matrices of the
SwiGLU run as grouped matrix products over the experts that have tokens
(``jax.lax.ragged_dot``), so a step reads the experts it touches and a
prefill chunk spends k/E of the all-experts FLOPs.  Shapes stay static (k
pairs a token, whatever the load), no token is dropped and no capacity is
set.  ``moe_ffn`` (Mixtral: softmax over the top-k logits) and
models/mla_moe.py's expert layer (sigmoid scores, a selection bias,
normalised and scaled) differ in their gates only.  ``all_experts_ffn`` runs
every expert on every token and lets the gate zero the rest: the CPU tests'
oracle, and the math the expert-parallel path (parallel/moe.py) reproduces
with each device computing its local experts and one psum over ``ep``.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import causal_attention, prefix_attention_args
from .llama import (
    LlamaConfig,
    Params,
    _attn_qkv,
    _layer,
    prefill_kernel_layers,
    rmsnorm,
)


@dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    # DeepSeek-MoE-style SHARED experts: always-on FFN capacity added to
    # the routed output ungated (n shared experts of ffn_dim each,
    # implemented as one fused dense FFN of width n * ffn_dim — the sum
    # of n independent FFNs of the same input is exactly that).  0 =
    # Mixtral-style pure routing (param structure unchanged).
    n_shared_experts: int = 0

    @property
    def expert_routing(self) -> Tuple[int, int, int]:
        """(expert layers, experts a token, experts a layer), for the step
        profiler's routed-pair counts (engine/stepprof.note_decode)."""
        return (self.n_layers, self.top_k, self.n_experts)


MIXTRAL_8X7B = MoEConfig(
    vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, n_experts=8, top_k=2, rope_theta=1e6,
)
TINY_MOE = MoEConfig(
    vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=256, n_experts=4, top_k=2,
)


def scaled_moe(cfg: MoEConfig, **kw) -> MoEConfig:
    return replace(cfg, **kw)


def init_moe_params(cfg: MoEConfig, key: jax.Array) -> Params:
    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(
            cfg.dtype
        )

    keys = jax.random.split(key, cfg.n_layers + 2)
    hd = cfg.head_dim
    E = cfg.n_experts
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[li], 9)
        layers.append(
            {
                "wq": dense(k[0], (cfg.dim, cfg.n_heads * hd), cfg.dim),
                "wk": dense(k[1], (cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
                "wv": dense(k[2], (cfg.dim, cfg.n_kv_heads * hd), cfg.dim),
                "wo": dense(k[3], (cfg.n_heads * hd, cfg.dim), cfg.n_heads * hd),
                # router stays fp32: tiny, and gate ordering is precision-
                # sensitive (top-k ties)
                "router": jax.random.normal(k[4], (cfg.dim, E), jnp.float32)
                / np.sqrt(cfg.dim),
                "w_gate": dense(k[5], (E, cfg.dim, cfg.ffn_dim), cfg.dim),
                "w_up": dense(k[6], (E, cfg.dim, cfg.ffn_dim), cfg.dim),
                "w_down": dense(k[7], (E, cfg.ffn_dim, cfg.dim), cfg.ffn_dim),
                "ln_attn": jnp.ones((cfg.dim,), cfg.dtype),
                "ln_mlp": jnp.ones((cfg.dim,), cfg.dtype),
            }
        )
        if cfg.n_shared_experts > 0:
            ks = jax.random.split(k[8], 3)
            sf = cfg.n_shared_experts * cfg.ffn_dim
            layers[-1]["ws_gate"] = dense(ks[0], (cfg.dim, sf), cfg.dim)
            layers[-1]["ws_up"] = dense(ks[1], (cfg.dim, sf), cfg.dim)
            layers[-1]["ws_down"] = dense(ks[2], (sf, cfg.dim), sf)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, cfg.dim), cfg.dim),
        "layers": stacked,
        "ln_out": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": dense(keys[-1], (cfg.dim, cfg.vocab_size), cfg.dim),
    }


def top_k_gates(router_logits: jax.Array, top_k: int) -> jax.Array:
    """[..., E] logits -> [..., E] gate weights: softmax over the top-k
    entries, exact zeros elsewhere (Mixtral gating)."""
    E = router_logits.shape[-1]
    vals, idx = jax.lax.top_k(router_logits, top_k)  # [..., k]
    probs = jax.nn.softmax(vals, axis=-1)
    onehot = jax.nn.one_hot(idx, E, dtype=probs.dtype)  # [..., k, E]
    return jnp.einsum("...k,...ke->...e", probs, onehot)


def sigmoid_top_k(scores: jax.Array, bias: jax.Array, top_k: int,
                  scaling: float, eps: float = 1e-20
                  ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores [N, E] (float32) -> (experts [N, k], weights [N, k]):
    the k largest of ``scores + bias`` are chosen (the bias steers the choice
    only), and their OWN scores, normalised to sum to one and times
    ``scaling``, weigh them (``noaux_tc`` with one group).  ``eps`` is what a
    family's code adds to the chosen scores' sum (1e-6 makes the weights sum
    to one less its share)."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps) * scaling
    return idx.astype(jnp.int32), w


def held_pairs(idx: jax.Array, first_expert: int, n_held: int,
               live: jax.Array | None = None) -> jax.Array:
    """Of the (token, expert) pairs ``idx`` [B * S, k] names, how many name an
    expert of ``[first_expert, first_expert + n_held)``, the experts one
    chip's share of an expert-parallel layer holds.  ``live`` [B]: the
    batch's rows that are sequences (a pad row's pairs are not counted)."""
    local = (idx >= first_expert) & (idx < first_expert + n_held)
    if live is not None:
        local &= jnp.repeat(live, idx.shape[0] // live.shape[0])[:, None]
    return jnp.sum(local.astype(jnp.int32))


def routed_experts(x: jax.Array, idx: jax.Array, weights: jax.Array,
                   w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   held_from: int | None = None) -> jax.Array:
    """Experts computed for the tokens routed to them.

    x: [N, dim]; idx: [N, k] the expert of each of a token's k pairs;
    weights: [N, k] float32; w_gate/w_up: [E, dim, ffn]; w_down: [E, ffn,
    dim] -> [N, dim] = sum_k weights * SwiGLU_idx(x).

    The N * k pairs are sorted by expert, so each expert's rows are one run;
    ``ragged_dot`` multiplies each run by its expert's matrix and visits no
    matrix whose run is empty.  The weighted pairs are summed per token in
    float32.  Static shapes: N * k rows whatever the load.

    ``held_from``: the layer holds only the E experts ``[held_from,
    held_from + E)`` of those ``idx`` chooses among (one chip's share of an
    expert-parallel layer).  Pairs whose expert is absent sort behind every
    run and belong to none, so the grouped products skip them, and their
    term is left out of the sum: the result is this share's part of the
    layer, which the shares of all chips add up to.  ``None`` (every expert
    is held) lowers as it always has."""
    N, k = idx.shape
    E = w_gate.shape[0]
    flat = idx.reshape(N * k)
    if held_from is not None:
        local = flat - held_from
        flat = jnp.where((local >= 0) & (local < E), local, E)
    order = jnp.argsort(flat)                    # stable: pairs by expert
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    xs = x[order // k]                           # [N * k, dim]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, sizes))
    h = h * jax.lax.ragged_dot(xs, w_up, sizes)
    y = jax.lax.ragged_dot(h, w_down, sizes,
                           preferred_element_type=jnp.float32)
    y = y * weights.reshape(N * k)[order][:, None]
    if held_from is not None:
        # rows behind the last run: whatever the product left there is not
        # a term of the sum
        y = jnp.where((flat[order] < E)[:, None], y, 0.0)
    out = jnp.zeros((N * k, y.shape[-1]), jnp.float32).at[order].set(y)
    return out.reshape(N, k, -1).sum(axis=1).astype(x.dtype)


def all_experts_ffn(x: jax.Array, gates: jax.Array, w_gate: jax.Array,
                    w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """The oracle: every expert on every token, ``gates`` [N, E] (zeros off
    the chosen experts) weighing them.  x: [N, dim] -> [N, dim]."""
    h = jax.nn.silu(jnp.einsum("nd,edf->nef", x, w_gate))
    h = h * jnp.einsum("nd,edf->nef", x, w_up)
    out = jnp.einsum("nef,efd->ned", h, w_down)
    return jnp.einsum("ned,ne->nd", out, gates.astype(x.dtype))


def moe_ffn(layer: Params, x: jax.Array, top_k: int) -> jax.Array:
    """Mixtral's MoE FFN.  x: [B, S, dim] -> [B, S, dim]: softmax over the
    top-k router logits, the chosen experts computed for their tokens
    (``routed_experts``).

    When the layer carries shared-expert weights (``ws_*``,
    DeepSeek-MoE style), their always-on FFN output adds to the routed
    sum UNGATED — the branch is static at trace time (pytree
    structure), so Mixtral-style layers compile exactly as before."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    vals, idx = jax.lax.top_k(flat.astype(jnp.float32) @ layer["router"], top_k)
    routed = routed_experts(
        flat, idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1),
        layer["w_gate"], layer["w_up"], layer["w_down"]).reshape(B, S, d)
    if "ws_gate" in layer:
        routed = routed + _shared_expert_ffn(layer, x)
    return routed


def _shared_expert_ffn(layer: Params, x: jax.Array) -> jax.Array:
    """The always-on shared-expert SwiGLU — ONE definition reused by the
    dense and expert-parallel paths (llama's ``_mlp`` over the ws_*
    leaves), so the two can never silently diverge."""
    from .llama import _mlp

    return _mlp(
        {"w_gate": layer["ws_gate"], "w_up": layer["ws_up"],
         "w_down": layer["ws_down"]},
        x,
    )


def moe_prefill_forward(
    params: Params,
    cfg: MoEConfig,
    tokens: jax.Array,
    prefix_kv: jax.Array | None = None,
    prefix_len: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """tokens: [B, S] -> (logits [B, S, V], kv [L, 2, B, S, Hkv, D]).

    Same contract as models.llama.prefill_forward (including chunked
    prefill on a padded/bucketed ``prefix_kv`` with traced
    ``prefix_len``), so the serving engines and KV paging work unchanged
    for MoE models.
    """
    B, S = tokens.shape
    Pfx = 0 if prefix_kv is None else prefix_kv.shape[3]
    start = Pfx if prefix_len is None else prefix_len
    positions = jnp.broadcast_to(jnp.arange(S) + start, (B, S))
    x = params["embed"][tokens]
    kvs = []
    for li in range(cfg.n_layers):
        layer = _layer(li)(params["layers"])
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _attn_qkv(layer, cfg, h, positions)
        kvs.append(jnp.stack([k, v], axis=0))
        if prefix_kv is None:
            attn = causal_attention(q, k, v, window=cfg.sliding_window)
        else:
            k_full = jnp.concatenate([prefix_kv[li, 0], k], axis=1)
            v_full = jnp.concatenate([prefix_kv[li, 1], v], axis=1)
            attn = causal_attention(
                q, k_full, v_full, window=cfg.sliding_window,
                **prefix_attention_args(Pfx, prefix_len),
            )
        x = x + attn.reshape(B, S, -1) @ layer["wo"]
        h = rmsnorm(x, layer["ln_mlp"], cfg.norm_eps)
        x = x + moe_ffn(layer, h, cfg.top_k)
    x = rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return x @ params["lm_head"], jnp.stack(kvs)


def _moe_prefill_kernel_layers(cfg: MoEConfig, tokens, prefix_kv=None,
                               prefix_len=None, **_) -> int:
    """``llama.prefill_kernel_layers`` for the loop above: every layer under
    the model's one window, and no soft cap."""
    return prefill_kernel_layers(
        replace(cfg, attn_softcap=None), tokens, prefix_kv, prefix_len,
        windows=[cfg.sliding_window] * cfg.n_layers)


moe_prefill_forward.kernel_layers = _moe_prefill_kernel_layers


def moe_decode_forward(
    params: Params,
    cfg: MoEConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: jax.Array,
    block_table: jax.Array,
    seq_lens: jax.Array,
    slot_block_ids: jax.Array,
    slot_ids: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Single-token paged MoE decode; contract of models.llama.decode_forward."""
    from ..kv.cache import write_token_kv
    from .attention import paged_decode_attention

    B = tokens.shape[0]
    x = params["embed"][tokens][:, None, :]
    pos = positions[:, None]
    for li in range(cfg.n_layers):
        layer = _layer(li)(params["layers"])
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _attn_qkv(layer, cfg, h, pos)
        cache = write_token_kv(cache, li, slot_block_ids, slot_ids, k[:, 0], v[:, 0])
        attn = paged_decode_attention(
            q[:, 0], cache, li, block_table, seq_lens,
            window=cfg.sliding_window,
        )
        x = x + (attn.reshape(B, -1) @ layer["wo"])[:, None, :]
        h = rmsnorm(x, layer["ln_mlp"], cfg.norm_eps)
        x = x + moe_ffn(layer, h, cfg.top_k)
    x = rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return x[:, 0] @ params["lm_head"], cache


def moe_verify_forward(
    params: Params,
    cfg: MoEConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: jax.Array,
    block_table: jax.Array,
    slot_block_ids: jax.Array,
    slot_ids: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Multi-token paged MoE step; contract of models.llama.verify_forward
    (the speculative-decode verify step for MoE engines)."""
    from ..kv.cache import write_tokens_kv
    from .attention import paged_multitoken_attention_xla

    B, S = tokens.shape
    x = params["embed"][tokens]
    for li in range(cfg.n_layers):
        layer = _layer(li)(params["layers"])
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _attn_qkv(layer, cfg, h, positions)
        cache = write_tokens_kv(cache, li, slot_block_ids, slot_ids, k, v)
        attn = paged_multitoken_attention_xla(
            q, cache, li, block_table, positions, window=cfg.sliding_window
        )
        x = x + attn.reshape(B, S, -1) @ layer["wo"]
        h = rmsnorm(x, layer["ln_mlp"], cfg.norm_eps)
        x = x + moe_ffn(layer, h, cfg.top_k)
    x = rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return x @ params["lm_head"], cache


def moe_loss_fn(params: Params, cfg: MoEConfig, tokens: jax.Array) -> jax.Array:
    logits, _ = moe_prefill_forward(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean()


def moe_train_step_fn(cfg: MoEConfig, lr: float = 1e-3):
    def step(params: Params, tokens: jax.Array):
        loss, grads = jax.value_and_grad(lambda p: moe_loss_fn(p, cfg, tokens))(params)
        params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
        return params, loss

    return step
