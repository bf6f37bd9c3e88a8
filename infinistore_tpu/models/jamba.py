"""Mamba-1 selective-scan layers among multi-query attention layers (the
``jamba`` model type at ``num_experts`` 1): a stack whose sequence keeps TWO
KINDS of cache, pages for its attention layers and a float32 state for its
Mamba layers (kv/cache.py ``HybridCacheConfig``, engine/hybrid_engine.py).

A block is ``x = x + Mixer(RMSNorm(x))``, then ``x = x + FFN(RMSNorm(x))``; the
parts shared with other families are imported, not copied (``rmsnorm``,
``_mlp``, ``head_logits`` of models/llama.py; the paged attention
of models/attention.py).  What is this family's own:

* **The Mamba mixer** (every layer ``i`` with ``i % attn_layer_period !=
  attn_layer_offset``), ``d_inner = mamba_expand x hidden``: ``[x | z] = h W_in``;
  ``x = SiLU(conv(x))``, a depthwise causal sum over ``d_conv`` positions with
  a bias, zeros before the sequence's start; ``[r | B | C] = x W_x``; ``r, B, C``
  each through an RMSNorm with a learned weight (this family's three inner
  norms); ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; the selective
  scan (models/ssm_scan.py) ``s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t``, ``y_t =
  s_t . C_t + D x_t``; ``out = (y * SiLU(z)) W_out``.  No position enters.
  ``A_log``, ``D``, ``b_dt``, ``dt``, the state and the recurrence are float32;
  weights and activations the model's type.  What a sequence keeps of such a
  layer is ``s`` ``[d_state, d_inner]`` and the conv's last ``d_conv - 1`` inputs
  ``[d_conv - 1, d_inner]``: ONE float32 width a layer in a slot, ``(d_state +
  d_conv - 1) x d_inner`` values (the conv's rows are the activations' values,
  which float32 holds exactly), whatever the sequence's length.
* **The attention mixer**: grouped-query attention (one key/value head at
  the published sizes), no bias, NO position embedding (the Mamba layers
  carry order); its K and V are the page.
* **The FFN**: one SwiGLU a layer (``num_experts`` 1; a file with more is
  refused: the expert layers of the larger models of the family are not built
  here).  **The head** is the tied embedding.

The Mamba layers have ONE shape: their leaves are stacked (``params["mamba"]``,
``[Mamba layers, ...]``; ``params["attn"]`` is a tuple of one dict an attention
layer) and a program walks them in a ``lax.scan`` (``_walk_stack``), so it holds
a few layer bodies and not 28.  The prefill chunk is ONE scan over all of
them, an attention layer under a ``lax.switch`` after the Mamba layer it
follows (one Mamba body, and one call of the scan's kernel, whose time the
trace then shows under one name); the decode scan, which the conditional
slows by a third, a scan a run of Mamba layers between attention layers.

The cache the forwards take is the pair the engine holds: ``pages`` ``[attention
layers, 2, H_kv, n_blocks, T, D]`` and ``state`` ``[slots, Mamba layers, width /
128, 128]`` float32.  A prefill chunk of one row starts from its slot's state and leaves
there the state after its last REAL token: ``dt`` is zeroed at a padded
position, which is the identity of the recurrence, and the conv's kept rows
are those before the first position not computed.  A decode step moves each
live row's state by one token and writes it back (a pad row's slot lies past
the slots: its read clamps, its write is dropped).  No verify step, no LoRA
and no mesh path: ``serve`` refuses them at start-up.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import grouped_chunk_attention, paged_decode_attention
from .llama import Family, Params, _mlp, head_logits, rmsnorm
from .ssm_scan import selective_scan, selective_step

# the seeded step: ``b_dt`` is the inverse softplus of a step drawn
# log-uniformly in [DT_MIN, DT_MAX], as the family initialises it
DT_MIN, DT_MAX = 0.001, 0.1
# ``W_dt``'s draw over the normed ``r`` moves the step's logarithm by about this
DT_PROJ_STD = 0.5
# how each program walks the stack (``_walk_stack``): as the chip read them
ONE_BODY_PREFILL = True
ONE_BODY_DECODE = False


@dataclass(frozen=True)
class JambaConfig:
    """Sizes under the names ``models.llama``'s shared parts read; ``FAMILY_KEYS``
    maps the source's ``config.json`` onto them."""

    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    ffn_dim: int = 8192
    attn_period: int = 14
    attn_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple("attention" if i % self.attn_period == self.attn_offset
                     else "mamba" for i in range(self.n_layers))

    @property
    def kv_page(self) -> Tuple[int, int, int]:
        """(planes, heads, width): K and V of the ATTENTION layers."""
        return (2, self.n_kv_heads, self.head_dim)

    # what a sequence keeps (kv/cache.py ``cache_kind``): pages for the
    # ``page_layers`` and a state for the ``state_layers``
    cache_kind = "hybrid"

    @property
    def page_layers(self) -> Tuple[int, ...]:
        """The layers of the stack that keep pages."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "attention")

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers of the stack that keep a state."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "mamba")

    @property
    def state_parts(self) -> Tuple[Tuple[int, int], ...]:
        """One Mamba layer's state of one sequence, the parts of its one
        width in order: the recurrence's ``s``, then the conv's kept inputs."""
        return ((self.d_state, self.d_inner), (self.d_conv - 1, self.d_inner))

    @property
    def state_width(self) -> int:
        return sum(a * b for a, b in self.state_parts)

    # the state's update is a scan over the chunk: the engine counts the
    # tokens through it (engine/hybrid_engine.py)
    state_update = "scan"
    # a slot holds a layer's width as rows of one lane tile: the layer axis
    # is then no tiled axis of the slots (kv/cache.py ``state_lanes``)
    state_lanes = 128

    @property
    def attn_follows(self) -> Tuple[int, ...]:
        """For each Mamba layer in the stack's order, 0 or ``1 +`` the number of
        the attention layer that comes right after it (at most one does:
        ``attn_period >= 2``)."""
        out = []
        for i, t in enumerate(self.layer_types):
            if t == "mamba":
                out.append(0)
            elif out:
                out[-1] = 1 + self.page_layers.index(i)
        return tuple(out)


FAMILY_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_dim",
    "attn_layer_period": "attn_period", "attn_layer_offset": "attn_offset",
    "mamba_d_state": "d_state", "mamba_d_conv": "d_conv",
    "mamba_dt_rank": "dt_rank", "mamba_expand": "expand",
    "rms_norm_eps": "norm_eps",
}
# what the equations here assume of the source
FAMILY_FIXED = {
    "model_type": "jamba", "mamba_conv_bias": True, "mamba_proj_bias": False,
    "tie_word_embeddings": True, "hidden_act": "silu", "num_experts": 1,
    "attention_dropout": 0.0,
}
FAMILY_OTHER = ("num_experts_per_tok", "expert_layer_period",
                "expert_layer_offset", "max_position_embeddings",
                "architectures", "torch_dtype", "dtype", "bos_token_id",
                "eos_token_id", "pad_token_id", "use_cache",
                "use_mamba_kernels", "initializer_range",
                "num_logits_to_keep", "output_router_logits",
                "router_aux_loss_coef", "sliding_window",
                "transformers_version")


def config_from_file(path: str, spec: dict) -> Tuple[str, JambaConfig, int]:
    """``(model_id, cfg, seed)`` from a ``--model`` file of this family:
    ``{"family": "jamba", "published": {config.json's keys}, "seed": s}``.
    Every size is stated and none is overridden; nothing is cut (``reduced``
    must be empty or absent): the published depth is served whole."""
    pub = spec.get("published", {})
    if pub.get("num_experts", 1) != 1:
        raise ValueError(
            f"{path}: num_experts={pub['num_experts']}: the expert layers of "
            f"the larger models of this family are not built here (every "
            f"layer's FFN is one SwiGLU: num_experts 1)")
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
    if spec.get("reduced"):
        raise ValueError(f"{path}: 'reduced' {sorted(spec['reduced'])}: this "
                         f"family is served at its published depth, uncut")
    cfg = JambaConfig(**{f: pub[k] for k, f in FAMILY_KEYS.items()})
    if cfg.dim % cfg.n_heads or cfg.n_heads % cfg.n_kv_heads:
        raise ValueError(f"{path}: {cfg.n_heads} query heads over "
                         f"{cfg.n_kv_heads} key/value heads of hidden_size "
                         f"{cfg.dim} / {cfg.n_heads}")
    if not (0 <= cfg.attn_offset < cfg.attn_period) or not cfg.page_layers \
            or not cfg.state_layers:
        raise ValueError(f"{path}: attn_layer_period {cfg.attn_period} / "
                         f"offset {cfg.attn_offset} over {cfg.n_layers} layers "
                         f"leave no attention layer or no Mamba layer: this "
                         f"family keeps pages AND a state")
    if cfg.attn_offset == 0 or cfg.attn_period < 2:
        raise ValueError(f"{path}: an attention layer follows a Mamba layer "
                         f"here (attn_layer_offset >= 1, attn_layer_period >= "
                         f"2): it runs in the body of the scan over them")
    seed = spec.get("seed", 0)
    if not (isinstance(seed, int) and seed >= 0):
        raise ValueError(f"{path}: seed must be a non-negative integer")
    name = spec.get("name", "jamba")
    widths = "-".join(str(pub[k]) for k in sorted(FAMILY_KEYS))
    tag = hashlib.sha256(widths.encode()).hexdigest()[:8]
    return f"{name}-{tag}-l{cfg.n_layers}-seed{seed}", cfg, seed


def init_jamba_params(cfg: JambaConfig, key: jax.Array) -> Params:
    """Random weights from ``key``, one jitted program.  Layer ``li`` draws from
    ``split(split(key, L + 1)[li], 9)``: a Mamba mixer 0 ``w_in``, 1 ``conv_w``
    (fan-in ``d_conv``), 2 ``w_x``, 3 ``w_dt`` (normal x ``DT_PROJ_STD`` /
    sqrt(dt_rank): it moves the step's logarithm by about that), 4 the step,
    uniform in its logarithm over [DT_MIN, DT_MAX], of which ``b_dt`` is the
    inverse softplus, 8 ``w_out``; an attention mixer 0-3 wq wk wv wo; 5-7
    the SwiGLU.  ``A_log = log(n + 1)`` for state ``n`` (``A[c, n] = -(n + 1)``),
    ``D`` ones, the conv's bias zeros, every norm ones: as the family
    initialises them (drawn like another weight, every channel would forget
    within two tokens and no check could tell a loaded checkpoint from
    zeros).  The embedding (and tied head) from ``split(key, L + 1)[L]``;
    normal / sqrt(fan_in) but ``w_dt`` and ``b_dt``."""
    L, d, hd, di = cfg.n_layers, cfg.dim, cfg.head_dim, cfg.d_inner
    N, K, R = cfg.d_state, cfg.d_conv, cfg.dt_rank
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    f32 = jnp.float32

    def dense(key, shape, fan_in, dtype=cfg.dtype, std=1.0):
        return (jax.random.normal(key, shape, f32) * (std / np.sqrt(fan_in))
                ).astype(dtype)

    def ffn(k):
        return {"ln_attn": jnp.ones((d,), cfg.dtype),
                "ln_mlp": jnp.ones((d,), cfg.dtype),
                "w_gate": dense(k[5], (d, cfg.ffn_dim), d),
                "w_up": dense(k[6], (d, cfg.ffn_dim), d),
                "w_down": dense(k[7], (cfg.ffn_dim, d), cfg.ffn_dim)}

    def mamba(key):
        k = jax.random.split(key, 9)
        step = jnp.exp(jax.random.uniform(k[4], (di,), f32)
                       * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN))
        return ffn(k) | {
            "w_in": dense(k[0], (d, 2 * di), d),
            "conv_w": dense(k[1], (di, K), K),
            "conv_b": jnp.zeros((di,), cfg.dtype),
            "w_x": dense(k[2], (di, R + 2 * N), di),
            "dt_norm": jnp.ones((R,), cfg.dtype),
            "b_norm": jnp.ones((N,), cfg.dtype),
            "c_norm": jnp.ones((N,), cfg.dtype),
            "w_dt": dense(k[3], (R, di), R, std=DT_PROJ_STD),
            # softplus(b_dt) = step
            "b_dt": step + jnp.log(-jnp.expm1(-step)),
            # [d_state, d_inner]: states on sublanes (models/ssm_scan.py)
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=f32))[:, None], (N, di)),
            "d_skip": jnp.ones((di,), f32),
            "w_out": dense(k[8], (di, d), di)}

    def attention(key):
        k = jax.random.split(key, 9)
        return ffn(k) | {"wq": dense(k[0], (d, nq), d),
                         "wk": dense(k[1], (d, nkv), d),
                         "wv": dense(k[2], (d, nkv), d),
                         "wo": dense(k[3], (nq, d), nq)}

    def build(key):
        keys = jax.random.split(key, L + 1)
        return {"embed": dense(keys[L], (cfg.vocab_size, d), d),
                "mamba": jax.lax.map(mamba, keys[np.asarray(cfg.state_layers)]),
                "attn": tuple(attention(keys[li]) for li in cfg.page_layers),
                "ln_out": jnp.ones((d,), cfg.dtype)}

    return jax.jit(build)(key)


def mamba_inputs(layer: Params, cfg: JambaConfig, h: jax.Array,
                 rows: jax.Array):
    """What the recurrence reads of h [..., S, dim] (normalised) continuing the
    conv's kept inputs ``rows`` [..., d_conv - 1, d_inner] (zeros at a
    sequence's start): ``(x, dt, B, C [..., S, .] float32, z, x_ext [..., d_conv
    - 1 + S, d_inner])``, ``x_ext`` the kept rows then the chunk's own, of which
    the caller keeps the ``d_conv - 1`` it wants.  The same expression in a
    chunk and in a decode step."""
    di, N, R, K = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
    S = h.shape[-2]
    f32 = jnp.float32
    xz = h @ layer["w_in"]
    x_ext = jnp.concatenate([rows.astype(h.dtype), xz[..., :di]], axis=-2)
    w = layer["conv_w"].astype(f32)                             # [d_inner, K]
    c = sum(w[:, j] * x_ext[..., j: j + S, :].astype(f32) for j in range(K))
    x = jax.nn.silu(c + layer["conv_b"].astype(f32)).astype(h.dtype)
    rbc = x @ layer["w_x"]
    r = rmsnorm(rbc[..., :R], layer["dt_norm"], cfg.norm_eps)
    B = rmsnorm(rbc[..., R: R + N], layer["b_norm"], cfg.norm_eps)
    C = rmsnorm(rbc[..., R + N:], layer["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(jnp.dot(r, layer["w_dt"], preferred_element_type=f32)
                         + layer["b_dt"])
    return x.astype(f32), dt, B.astype(f32), C.astype(f32), xz[..., di:], x_ext


def mamba_out(layer: Params, x: jax.Array, y: jax.Array, z: jax.Array):
    """``(y + D x) * SiLU(z)`` through ``W_out``: x, y float32, z the
    activations' type."""
    y = (y + layer["d_skip"] * x).astype(z.dtype)
    return (y * jax.nn.silu(z)) @ layer["w_out"]


def _split_state(cfg: JambaConfig, state: jax.Array):
    """A layer's width ``[..., width]`` as ``(s [..., d_state, d_inner], conv
    rows [..., d_conv - 1, d_inner])``."""
    (N, di), (K1, _) = cfg.state_parts
    lead = state.shape[:-1]
    return (state[..., : N * di].reshape(lead + (N, di)),
            state[..., N * di:].reshape(lead + (K1, di)))


def _join_state(s: jax.Array, rows: jax.Array) -> jax.Array:
    lead = s.shape[:-2]
    return jnp.concatenate([s.reshape(lead + (-1,)),
                            rows.astype(s.dtype).reshape(lead + (-1,))], -1)


def _qkv(layer: Params, cfg: JambaConfig, h: jax.Array):
    """h [B, S, dim] -> q [B, S, H, D], k and v [B, S, H_kv, D]: the three
    products by head, and nothing else (no bias, no norm, no rotation)."""
    B, S, _ = h.shape
    return ((h @ layer["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim),
            (h @ layer["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim),
            (h @ layer["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim))


def _ffn(layer: Params, cfg: JambaConfig, x: jax.Array) -> jax.Array:
    return x + _mlp(layer, rmsnorm(x, layer["ln_mlp"], cfg.norm_eps))


def _head(params: Params, cfg: JambaConfig, x: jax.Array) -> jax.Array:
    x = rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["embed"])


def _walk_stack(cfg: JambaConfig, params: Params, mamba_layer, attn_layer,
                carry, one_body: bool):
    """The stack's layers over ``carry``: ``mamba_layer(carry, ci, layer) ->
    carry`` for Mamba layer ``ci`` (``layer`` its leaves, sliced out of the
    stack inside the loop: XLA fuses the slice into the products that read
    it) and ``attn_layer(ai, carry) -> carry`` for attention layer ``ai``
    (static), in the stack's order.

    ``one_body``: ONE ``lax.scan`` over every Mamba layer, an attention layer
    under a ``lax.switch`` after the Mamba layer it follows: one Mamba body a
    program and one call of the scan's kernel, at the price of a conditional a
    layer.  Else a ``lax.scan`` a RUN of Mamba layers between attention layers:
    a body a run (three at the published sizes) and no conditional.  Read on
    the chip (PERF.md, PR 46): under the conditional a decode step is 12.5 ms
    at 2 rows and 16.1 at 8 where the runs take 8.7 and 9.4; a prefill chunk
    of 512 is 21.2 / 24.3 ms (no prefix / 16k) against 21.1 / 24.0 and
    compiles in 2.5 s against 2.8-4.3: so the decode scan walks runs and the
    prefill chunk one body (``ONE_BODY_*``)."""
    stack = params["mamba"]
    follows = cfg.attn_follows

    def body(carry, ci):
        return mamba_layer(carry, ci, jax.tree.map(lambda w: w[ci], stack)), None

    if one_body:
        after = [lambda carry: carry] + [
            partial(attn_layer, ai) for ai in range(len(cfg.page_layers))]

        def step(carry, xs):
            carry, _ = body(carry, xs[0])
            return jax.lax.switch(xs[1], after, carry), None

        return jax.lax.scan(step, carry, (jnp.arange(len(follows)),
                                          np.asarray(follows, np.int32)))[0]
    lo = 0
    for hi, ai in [(i + 1, f - 1) for i, f in enumerate(follows) if f] + [
            (len(follows), None)]:
        if hi > lo:
            carry, _ = jax.lax.scan(body, carry, jnp.arange(lo, hi))
        if ai is not None:
            carry = attn_layer(ai, carry)
        lo = hi
    return carry


def jamba_prefill_forward(
    params: Params,
    cfg: JambaConfig,
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
    layers, 2, 1, S, H_kv, D], state)).

    The attention layers keep the contract of ``models.llama.prefill_forward``
    over THEIR layers alone: ``prefix_kv`` [attention layers, 2, 1, P, H_kv, D]
    is the reused prefix's K and V (exact, or a padded buffer of which
    ``prefix_len`` rows are valid), the returned rows cover the new tokens.
    The Mamba layers read and write ``conv`` [slots, Mamba layers, width / 128,
    128] float32 (the engine's name for the donated slots), donated: the chunk
    starts from slot ``slot``'s state and leaves there the state after the
    first ``n_valid`` positions (the rest pad a last chunk to whole pages and
    enter no state).  ``head`` / ``head_row``: where the norm and the head run
    (``llama.head_logits``)."""
    B, S = tokens.shape
    assert B == 1, "a prefill chunk is one row's: its state is one slot's"
    P = 0 if prefix_kv is None else prefix_kv.shape[3]
    start = P if prefix_len is None else prefix_len
    q_pos = jnp.arange(S) + start
    valid = (jnp.arange(S) < n_valid)[:, None]
    K1 = cfg.d_conv - 1
    x = params["embed"][tokens]

    def attn_layer(ai, carry):
        x, conv, kvs = carry
        layer = params["attn"][ai]
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _qkv(layer, cfg, h)
        kvs = kvs.at[ai].set(jnp.stack([k, v], axis=0))
        with jax.named_scope("istpu.attn.full"):
            k_pos, k_valid = q_pos, None
            if prefix_kv is not None:
                k = jnp.concatenate([prefix_kv[ai, 0], k], axis=1)
                v = jnp.concatenate([prefix_kv[ai, 1], v], axis=1)
                k_pos = jnp.concatenate([jnp.arange(P), q_pos])
                if prefix_len is not None:
                    k_valid = jnp.concatenate(
                        [jnp.arange(P) < prefix_len, jnp.ones((S,), bool)])
            attn = grouped_chunk_attention(q, k, v, q_pos, k_pos, k_valid)
        x = _ffn(layer, cfg, x + attn.reshape(B, S, -1) @ layer["wo"])
        return x, conv, kvs

    def mamba_layer(carry, ci, layer):
        x, conv, kvs = carry
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        with jax.named_scope("istpu.ssm.chunk"):
            at = (slot, ci, 0, 0)
            s0, rows = _split_state(cfg, jax.lax.dynamic_slice(
                conv, at, (1, 1) + conv.shape[2:]).reshape(-1))
            xs_, dt, Bm, Cm, z, x_ext = mamba_inputs(layer, cfg, h[0], rows)
            # a padded position is the identity of the recurrence
            y, s1 = selective_scan(xs_, jnp.where(valid, dt, 0.0), Bm, Cm,
                                   -jnp.exp(layer["a_log"]), s0)
            # rows [n_valid, n_valid + K - 1) of the kept rows and the
            # chunk's: the K - 1 before the first position not computed
            kept = jax.lax.dynamic_slice_in_dim(x_ext, n_valid, K1, axis=0)
            conv = jax.lax.dynamic_update_slice(
                conv, _join_state(s1, kept).reshape((1, 1) + conv.shape[2:]),
                at)
            op = mamba_out(layer, xs_, y, z)[None]
        return _ffn(layer, cfg, x + op), conv, kvs

    kvs = jnp.zeros((len(cfg.page_layers), 2, B, S) + cfg.kv_page[1:], cfg.dtype)
    x, conv, kvs = _walk_stack(cfg, params, mamba_layer, attn_layer,
                               (x, conv, kvs), ONE_BODY_PREFILL)
    return head_logits(x, head, head_row, partial(_head, params, cfg)
                       ), (kvs, conv)


def jamba_decode_forward(
    params: Params,
    cfg: JambaConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: Tuple[jax.Array, jax.Array],
    block_table: Tuple[jax.Array, jax.Array],
    seq_lens: jax.Array,
    slot_block_ids: Tuple[jax.Array, jax.Array],
    slot_ids: jax.Array,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token decode under the engine's scan; the contract of
    ``models.lfm2_moe.lfm2_moe_decode_forward``: ``cache`` is ``(pages, state)``
    and ``block_table`` ``(the attention layers' table [B, width], each row's
    state slot [B, 1])``.  An attention layer writes the token's K and V and
    reads the row's live pages; a Mamba layer moves each row's state by this
    token and writes it back.  A pad row names a page past the pool and a
    slot past the slots: both writes are dropped.  ``positions`` enter
    nothing: no layer of this family embeds a position."""
    from ..kv.cache import write_token_kv

    del positions
    pages, conv = cache
    table, rows = block_table[0], block_table[1][:, 0]
    B = tokens.shape[0]
    x = params["embed"][tokens][:, None, :]

    def attn_layer(ai, carry):
        x, pages, conv = carry
        layer = params["attn"][ai]
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _qkv(layer, cfg, h)
        pages = write_token_kv(pages, ai, slot_block_ids[0], slot_ids,
                               k[:, 0], v[:, 0])
        with jax.named_scope("istpu.attn.full"):
            attn = paged_decode_attention(q[:, 0], pages, ai, table, seq_lens)
        x = _ffn(layer, cfg, x + (attn.reshape(B, -1) @ layer["wo"])[:, None, :])
        return x, pages, conv

    def mamba_layer(carry, ci, layer):
        x, pages, conv = carry
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        with jax.named_scope("istpu.ssm.step"):
            s0, kept = _split_state(cfg, conv[rows, ci].reshape(B, -1))
            xs_, dt, Bm, Cm, z, x_ext = mamba_inputs(layer, cfg, h, kept)
            y, s1 = selective_step(s0, xs_[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0],
                                   -jnp.exp(layer["a_log"]))
            conv = conv.at[rows, ci].set(
                _join_state(s1, x_ext[:, 1:]).reshape((B,) + conv.shape[2:]),
                mode="drop")
            op = mamba_out(layer, xs_, y[:, None], z)
        return _ffn(layer, cfg, x + op), pages, conv

    x, pages, conv = _walk_stack(cfg, params, mamba_layer, attn_layer,
                                 (x, pages, conv), ONE_BODY_DECODE)
    return _head(params, cfg, x[:, 0]), (pages, conv)


FAMILY = Family(name="jamba", config_cls=JambaConfig,
                config_from_file=config_from_file, init=init_jamba_params,
                prefill_fn=jamba_prefill_forward, decode_fn=jamba_decode_forward)
