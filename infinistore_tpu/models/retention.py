"""Power-retention layers in Qwen3's block (the ``brumby`` model type): a
decoder whose attention keeps NO key or value per token.  A sequence's whole
past in one layer is a state of fixed size, and the cache tier holds it by
checkpoint (kv/cache.py ``StateCacheConfig``, engine/state_engine.py).

The block around the attention is models/llama.py's, imported and not
copied: RMSNorm, Q/K RMSNorm by head, the rotation (``_attn_qkv``), SwiGLU
(``_mlp``), the untied head.  The attention's core is power retention at
power 2 (arXiv:2507.04239), per key/value head ``c`` and its group of query
heads:

* gate, float32: ``log g_t = logsigmoid(h_t W_g + b_g)``, ``G_t`` its running
  sum;
* attention form: ``w_tj = exp(G_t - G_j) (q_t . k_j)^2`` for ``j <= t``,
  ``y_t = sum_j w_tj v_j / sum_j w_tj`` (every weight is non-negative; a
  scale on ``q . k`` cancels and none is applied);
* recurrent form, equal to it: ``phi(u)`` the symmetric square of ``u``
  (``symmetric_square``: 128 x 129 / 2 = 8256 values with ``phi(q) . phi(k) =
  (q . k)^2``), ``S_t = g_t S_{t-1} + phi(k_t) v_t^T``, ``z_t = g_t z_{t-1} +
  phi(k_t)``, ``y_t = phi(q_t)^T S_t / phi(q_t)^T z_t``.

The program runs the recurrent form.  A prefill chunk computes the attention
form over its own keys, adds ``exp(G_t - G_start) phi(q_t)^T S_start`` from the
state it started with, and moves the state to the chunk's end
(``retention_chunk``); a decode step is one step of the recurrence a row
(``retention_step``).  ``S`` and ``z`` are float32 and live in the engine's
slots ``[slots, layers, H_kv, F, D]`` / ``[slots, layers, H_kv, F]``: both
forwards take the whole arrays and a row's slot index, read that slot layer
by layer and write it back in place (the arrays are donated).

The forwards' contracts differ from ``models.llama``'s where the cache does:
no pages come back from a prefill and no block table goes into a decode (the
engine's scan hands the slot ids in the table's place).  No verify step, no
LoRA and no mesh path: ``serve`` refuses them at start-up.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .llama import Family, Params, _attn_qkv, _layer, _mlp, head_logits, rmsnorm


@dataclass(frozen=True)
class RetentionConfig:
    """Sizes under the names ``models.llama``'s shared parts read
    (``_attn_qkv`` takes this config as it takes a ``LlamaConfig``);
    ``FAMILY_KEYS`` maps the source's ``config.json`` onto them."""

    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_dim: int = 17408
    norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    dtype: Any = jnp.bfloat16
    # what ``_attn_qkv`` asks of a config, fixed for this family
    qk_norm: bool = True
    attn_bias: bool = False
    rope_scaling: Any = None
    query_pre_attn_scalar: Any = None
    # what a sequence keeps (kv/cache.py ``cache_kind``): a state a layer,
    # no pages
    cache_kind = "state"

    @property
    def state_dim(self) -> int:
        """Width of the symmetric square of a key: D (D + 1) / 2."""
        return self.head_dim * (self.head_dim + 1) // 2

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        """(key/value heads, symmetric-square width, value width) of one
        layer's ``S``; its ``z`` is the first two."""
        return (self.n_kv_heads, self.state_dim, self.head_dim)

    @property
    def kv_page(self) -> Tuple[int, int, int]:
        """What ``serve`` reads to refuse int8 pages: one plane, no K|V."""
        return (1, self.n_kv_heads, self.head_dim)


FAMILY_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "ffn_dim", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
}
# what the equations here assume of the source
FAMILY_FIXED = {
    "model_type": "brumby", "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "rope_scaling": None,
}
FAMILY_OTHER = ("max_position_embeddings", "architectures", "torch_dtype",
                "attention_dropout", "initializer_range", "use_cache",
                "sliding_window", "use_sliding_window", "max_window_layers",
                "bos_token_id", "eos_token_id")


def config_from_file(path: str, spec: dict) -> Tuple[str, RetentionConfig, int]:
    """``(model_id, cfg, seed)`` from a ``--model`` file of this family:
    ``{"family": "brumby", "published": {config.json's keys}, "reduced":
    {"num_hidden_layers": n}, "seed": s}``.  Every size is stated and none is
    overridden; ``reduced`` may cut the depth only."""
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
    reduced = spec.get("reduced", {})
    if set(reduced) - {"num_hidden_layers"}:
        raise ValueError(f"{path}: 'reduced' may change num_hidden_layers "
                         f"only, got {sorted(reduced)}")
    cfg = RetentionConfig(**{f: pub[k] for k, f in FAMILY_KEYS.items()})
    if cfg.n_heads % cfg.n_kv_heads:
        raise ValueError(f"{path}: {cfg.n_heads} query heads do not group "
                         f"over {cfg.n_kv_heads} key/value heads")
    n_layers = reduced.get("num_hidden_layers", cfg.n_layers)
    if not (isinstance(n_layers, int) and 1 <= n_layers <= cfg.n_layers):
        raise ValueError(f"{path}: num_hidden_layers must be in "
                         f"[1, {cfg.n_layers}]")
    seed = spec.get("seed", 0)
    if not (isinstance(seed, int) and seed >= 0):
        raise ValueError(f"{path}: seed must be a non-negative integer")
    name = spec.get("name", "brumby")
    widths = "-".join(str(pub[k]) for k in sorted(FAMILY_KEYS)
                      if k != "num_hidden_layers")
    tag = hashlib.sha256(widths.encode()).hexdigest()[:8]
    return (f"{name}-{tag}-l{n_layers}-seed{seed}",
            replace(cfg, n_layers=n_layers), seed)


# the seeded gate: a head forgets over ``-1 / log g`` tokens, drawn
# log-uniformly between these two, so that a document's state still weighs
# after a tail of hundreds of tokens (drawn like another weight, ``g`` would
# sit near 1/2 and a state would be worth 2^-64 after 64 tokens: no check
# could tell a loaded checkpoint from zeros)
GATE_HORIZON = (64.0, 16384.0)
GATE_W_STD = 0.25       # std of h W_g: small beside b_g (4.2 to 9.7)


def gate_bias(u: jax.Array) -> jax.Array:
    """``b_g`` for uniform ``u`` in [0, 1): ``logsigmoid(b_g) = -1 / tau``,
    ``tau = 64 x 256^u`` tokens."""
    lo, hi = GATE_HORIZON
    tau = lo * (hi / lo) ** u
    return -jnp.log(jnp.expm1(1.0 / tau))


def init_retention_params(cfg: RetentionConfig, key: jax.Array) -> Params:
    """Random weights from ``key``, one jitted program, leaves stacked over
    layers as ``models.llama.init_params`` stacks them.  Layer ``li`` draws
    from ``split(split(key, L + 2)[li], 10)``: 0-3 wq wk wv wo, 4-6 the
    SwiGLU, 7 the gate's matrix (float32, normal x 0.25 / sqrt(dim)), 8 the
    gate's bias (``gate_bias`` of a uniform draw a head); normal /
    sqrt(fan_in) otherwise."""
    L, d, hd = cfg.n_layers, cfg.dim, cfg.head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(cfg.dtype)

    def build(key):
        keys = jax.random.split(key, L + 2)
        lk = jax.vmap(lambda k: jax.random.split(k, 10))(keys[:L])

        def stacked(i, shape, fan_in):
            return jax.vmap(lambda k: dense(k, shape, fan_in))(lk[:, i])

        layers = {
            "wq": stacked(0, (d, nq), d), "wk": stacked(1, (d, nkv), d),
            "wv": stacked(2, (d, nkv), d), "wo": stacked(3, (nq, d), nq),
            "w_gate": stacked(4, (d, cfg.ffn_dim), d),
            "w_up": stacked(5, (d, cfg.ffn_dim), d),
            "w_down": stacked(6, (cfg.ffn_dim, d), cfg.ffn_dim),
            "wg": jax.vmap(lambda k: jax.random.normal(
                k, (d, cfg.n_kv_heads), jnp.float32)
                * (GATE_W_STD / np.sqrt(d)))(lk[:, 7]),
            "bg": jax.vmap(lambda k: gate_bias(jax.random.uniform(
                k, (cfg.n_kv_heads,), jnp.float32)))(lk[:, 8]),
            "ln_attn": jnp.ones((L, d), cfg.dtype),
            "ln_mlp": jnp.ones((L, d), cfg.dtype),
            "q_norm": jnp.ones((L, hd), cfg.dtype),
            "k_norm": jnp.ones((L, hd), cfg.dtype),
        }
        return {"embed": dense(keys[-2], (cfg.vocab_size, d), d),
                "layers": layers, "ln_out": jnp.ones((d,), cfg.dtype),
                "lm_head": dense(keys[-1], (d, cfg.vocab_size), d)}

    return jax.jit(build)(key)


def _offset_table(D: int) -> np.ndarray:
    """[D, (D/2 + 1) D] of 0 and 1: column ``r D + a`` picks ``u_{a + r mod D}``,
    so that ``u @ table`` is ``u`` rolled by every offset 0 .. D/2 at once."""
    a = np.arange(D)
    table = np.zeros((D, D // 2 + 1, D), np.float32)
    for r in range(D // 2 + 1):
        table[(a + r) % D, r, a] = 1.0
    return table.reshape(D, -1)


def symmetric_square(u: jax.Array) -> jax.Array:
    """``phi(u)``: [..., D] -> [..., D (D + 1) / 2] float32 with ``phi(q) .
    phi(k) = (q . k)^2``.  The pairs are laid out by cyclic offset: ``u_a
    u_{a+r mod D}`` for ``r = 0 .. D/2``; offset 0 is the squares, each other
    offset names every unordered pair ``{a, a + r}`` once (times sqrt 2), and
    offset D/2 names each of its pairs twice, so its first D/2 entries are
    kept: D + (D/2 - 1) D + D/2 = D (D + 1) / 2.  The rolled copies come out
    of ONE product with a table of 0 and 1 (exact: one term a column), not
    of D/2 rolls or a gather by index pairs."""
    D = u.shape[-1]
    assert D % 2 == 0, D
    rolled = jnp.dot(u, jnp.asarray(_offset_table(D), u.dtype),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    coef = np.full((D // 2 + 1, 1), np.sqrt(2.0), np.float32)
    coef[0] = 1.0
    pairs = (rolled.reshape(u.shape[:-1] + (D // 2 + 1, D))
             * u.astype(jnp.float32)[..., None, :] * coef)
    return pairs.reshape(u.shape[:-1] + (-1,))[..., : D * (D + 1) // 2]


def gate_log(layer: Params, h: jax.Array) -> jax.Array:
    """``log g`` [..., H_kv] float32 from the normalised residual: the
    gate's matrix at full precision (its sum runs over thousands of
    tokens)."""
    x = jnp.dot(h.astype(jnp.float32), layer["wg"],
                precision=jax.lax.Precision.HIGHEST)
    return jax.nn.log_sigmoid(x + layer["bg"])


def retention_chunk(q, k, v, logg, valid, S0, z0):
    """One key/value head over one prefill chunk.

    q [C, G, D], k [C, D], v [C, D] (the group's query heads, rotated);
    ``logg`` [C] float32; ``valid`` [C] bool (false on the pad behind a
    prompt's last token: a pad neither decays nor enters the state);
    ``S0`` [F, D], ``z0`` [F] the state the chunk starts with.
    Returns ``(y [C, G, D] float32, S1, z1)``."""
    C = k.shape[0]
    with jax.named_scope("istpu.retention.intra"):
        Gc = jnp.cumsum(jnp.where(valid, logg, 0.0))        # G_t - G_start
        t = jnp.arange(C)
        seen = (t[None, :] <= t[:, None]) & valid[None, :]
        decay = jnp.exp(jnp.where(seen, Gc[:, None] - Gc[None, :], -jnp.inf))
        s = jnp.einsum("tgd,jd->gtj", q, k,
                       preferred_element_type=jnp.float32)
        w = s * s * decay                                   # [G, C, C]
        num = jnp.einsum("gtj,jd->tgd", w, v.astype(jnp.float32))
        den = jnp.sum(w, axis=-1).T                         # [C, G]
    with jax.named_scope("istpu.retention.inter"):
        # in the activations' type: [C, G, F] is written out and read back
        # (85 MB a head in float32), and the matrix unit rounds a float32
        # operand to bfloat16 anyway
        pq = symmetric_square(q).astype(q.dtype)
        carried = jnp.exp(Gc)[:, None]
        num = num + carried[..., None] * jnp.einsum("tgf,fd->tgd", pq, S0)
        den = den + carried * jnp.einsum("tgf,f->tg", pq, z0)
    with jax.named_scope("istpu.retention.update"):
        to_end = jnp.where(valid, jnp.exp(Gc[-1] - Gc), 0.0)
        pk = symmetric_square(k) * to_end[:, None]          # [C, F]
        S1 = jnp.exp(Gc[-1]) * S0 + jnp.einsum(
            "jf,jd->fd", pk, v.astype(jnp.float32))
        z1 = jnp.exp(Gc[-1]) * z0 + jnp.sum(pk, axis=0)
    return num / den[..., None], S1, z1


def retention_step(pq, pk, v, logg, S0, z0):
    """One step of the recurrence for one row.  ``pq`` [H_kv, G, F] and
    ``pk`` [H_kv, F] the symmetric squares of the row's queries and keys, v
    [H_kv, D], ``logg`` [H_kv] float32, ``S0`` [H_kv, F, D], ``z0`` [H_kv, F].
    Returns ``(y [H_kv, G, D] float32, S1, z1)``."""
    with jax.named_scope("istpu.retention.step"):
        g = jnp.exp(logg)
        S1 = g[:, None, None] * S0 + pk[..., None] * v.astype(
            jnp.float32)[:, None, :]
        z1 = g[:, None] * z0 + pk
        num = jnp.einsum("hgf,hfd->hgd", pq, S1)
        den = jnp.einsum("hgf,hf->hg", pq, z1)
    return num / den[..., None], S1, z1


def _layer_state(S_all, z_all, slot, li):
    """Layer ``li`` of slot ``slot``: ``(S [H_kv, F, D], z [H_kv, F])``, sliced
    out as exactly that (a slice of the whole slot first is a copy of every
    layer's state)."""
    at = (slot, li, 0, 0, 0)
    return (jax.lax.dynamic_slice(S_all, at, (1, 1) + S_all.shape[2:])[0, 0],
            jax.lax.dynamic_slice(z_all, at[:-1], (1, 1) + z_all.shape[2:])[0, 0])


def _set_layer_state(S_all, z_all, slot, li, S1, z1):
    at = (slot, li, 0, 0, 0)
    return (jax.lax.dynamic_update_slice(S_all, S1[None, None], at),
            jax.lax.dynamic_update_slice(z_all, z1[None, None], at[:-1]))


def _step_rows(S_all, z_all, li: int, rows, q, k, v, logg):
    """``retention_step`` for every row of a decode batch, one row at a time:
    each row's state of layer ``li`` is sliced out of the slots, moved one
    step and written back in place, so that what is live beside the slots is
    ONE row's state of one layer (gathered for all rows at once it is B of
    them, and the compiler keeps several layers' in flight: 4 GB at 8 rows).
    A pad row (its slot past the slots) is skipped: a slice would clamp onto
    the last slot and the write-back would land there.  q [B, H_kv, G, D]."""
    n_slots = S_all.shape[0]
    with jax.named_scope("istpu.retention.step"):
        pq, pk = symmetric_square(q), symmetric_square(k)   # every row's at once

    def one(b, carry):
        S_all, z_all, y = carry
        slot = rows[b]

        def live(S_all, z_all, y):
            S0, z0 = _layer_state(S_all, z_all, slot, li)
            yb, S1, z1 = retention_step(pq[b], pk[b], v[b], logg[b], S0, z0)
            return (*_set_layer_state(S_all, z_all, slot, li, S1, z1),
                    jax.lax.dynamic_update_index_in_dim(y, yb, b, 0))

        return jax.lax.cond(slot < n_slots, live, lambda *c: c,
                            S_all, z_all, y)

    y0 = jnp.zeros(q.shape, jnp.float32)
    return jax.lax.fori_loop(0, q.shape[0], one, (S_all, z_all, y0))


def _head(params: Params, cfg: RetentionConfig, x: jax.Array) -> jax.Array:
    return rmsnorm(x, params["ln_out"], cfg.norm_eps) @ params["lm_head"]


def retention_prefill_forward(
    params: Params,
    cfg: RetentionConfig,
    tokens: jax.Array,
    cache: Tuple[jax.Array, jax.Array],
    slot: jax.Array,
    start: jax.Array,
    n_valid: jax.Array,
    head: str = "all",
    head_row: jax.Array | None = None,
) -> Tuple[jax.Array | None, Tuple[jax.Array, jax.Array]]:
    """One prefill chunk of one row.  tokens [1, C] at positions ``start ..``
    of which the first ``n_valid`` are the prompt's (the rest pad a last
    chunk to whole pages); ``cache`` the state slots ``(S [slots, L, H_kv,
    F, D], z [slots, L, H_kv, F])``, donated; ``slot`` the row's.  The
    chunk starts from the slot's state and leaves the state after its last
    valid token there.  Returns (logits [1, C, V], cache); ``head`` /
    ``head_row``: where the norm and the head run, as in
    ``llama.prefill_forward`` (``llama.head_logits``)."""
    S_all, z_all = cache
    C = tokens.shape[1]
    G = cfg.n_heads // cfg.n_kv_heads
    positions = (jnp.arange(C) + start)[None]
    valid = jnp.arange(C) < n_valid
    x = params["embed"][tokens]

    # the layers as a SCAN over the stacked leaves: one layer's program
    # compiled once (unrolled, a chunk shape takes 16-24 s to compile and a
    # cell has five of them); a layer's weights are sliced out of the stack
    # once a chunk, 0.7 GB beside 2.7 TFLOP
    def one_layer(carry, xs):
        x, S_all, z_all = carry
        li, layer = xs
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _attn_qkv(layer, cfg, h, positions)
        logg = gate_log(layer, h[0])                        # [C, H_kv]
        # one key/value head at a time: phi of a chunk's queries is
        # [C, G, F] a head, 8 times that at once
        y, S1, z1 = jax.lax.map(
            lambda a: retention_chunk(*a[:4], valid, *a[4:]),
            (q[0].reshape(C, cfg.n_kv_heads, G, -1).transpose(1, 0, 2, 3),
             k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2), logg.T,
             *_layer_state(S_all, z_all, slot, li)))
        S_all, z_all = _set_layer_state(S_all, z_all, slot, li, S1, z1)
        y = y.transpose(1, 0, 2, 3).reshape(1, C, -1).astype(x.dtype)
        x = x + y @ layer["wo"]
        x = x + _mlp(layer, rmsnorm(x, layer["ln_mlp"], cfg.norm_eps))
        return (x, S_all, z_all), None

    (x, S_all, z_all), _ = jax.lax.scan(
        one_layer, (x, S_all, z_all),
        (jnp.arange(cfg.n_layers), params["layers"]))
    return head_logits(x, head, head_row, partial(_head, params, cfg)
                       ), (S_all, z_all)


def retention_decode_forward(
    params: Params,
    cfg: RetentionConfig,
    tokens: jax.Array,
    positions: jax.Array,
    cache: Tuple[jax.Array, jax.Array],
    block_table: jax.Array,
    seq_lens: jax.Array,
    slot_block_ids: jax.Array,
    slot_ids: jax.Array,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token decode under the engine's scan
    (``InferenceEngine._decode_many``): ``block_table`` [B, 1] holds each
    row's STATE SLOT where a paged family's holds page ids (a pad row's is
    past the slots: it is skipped); ``seq_lens``, ``slot_block_ids`` and
    ``slot_ids`` have no meaning here.  Each layer moves each row's slot one
    step in place (``_step_rows``)."""
    del seq_lens, slot_block_ids, slot_ids
    S_all, z_all = cache
    B = tokens.shape[0]
    G = cfg.n_heads // cfg.n_kv_heads
    rows = block_table[:, 0]
    x = params["embed"][tokens][:, None, :]
    pos = positions[:, None]
    for li in range(cfg.n_layers):
        layer = _layer(li)(params["layers"])
        h = rmsnorm(x, layer["ln_attn"], cfg.norm_eps)
        q, k, v = _attn_qkv(layer, cfg, h, pos)
        S_all, z_all, y = _step_rows(
            S_all, z_all, li, rows,
            q[:, 0].reshape(B, cfg.n_kv_heads, G, -1), k[:, 0], v[:, 0],
            gate_log(layer, h[:, 0]))
        x = x + (y.reshape(B, -1).astype(x.dtype) @ layer["wo"])[:, None, :]
        x = x + _mlp(layer, rmsnorm(x, layer["ln_mlp"], cfg.norm_eps))
    return _head(params, cfg, x[:, 0]), (S_all, z_all)


FAMILY = Family(name="brumby", config_cls=RetentionConfig,
                config_from_file=config_from_file, init=init_retention_params,
                prefill_fn=retention_prefill_forward, decode_fn=retention_decode_forward)
