"""The plain reference of the pre-norm decoder whose window layers (a sink in
their softmax, 8 key/value heads) stand beside full layers (4 key/value
heads), keys wider than values, with one chip's share of its routed experts
(``model_type`` ``mimo_v2_flash``): the forward pass in straightforward
jax.numpy, float32, matmuls at "highest" precision, no cache, no paging, no
batching, no grouped kernels.  Written from the equations of the source's
config; it imports nothing of infinistore_tpu.

Per layer, residual ``x``: ``h = x + Attn(RMSNorm(x))``, ``x <- h +
FFN(RMSNorm(h))`` (a weight, no bias, ``layernorm_epsilon``).

* ``Attn`` of a layer of kind ``hybrid_layer_pattern[l]`` (0 full, 1 window):
  ``q = a W_q`` (H heads of hd), ``k = a W_k`` (H_kv heads of hd), ``v =
  value_scale * a W_v`` (H_kv heads of vd < hd); H_kv is
  ``num_key_value_heads`` in a full layer and ``swa_num_key_value_heads`` in a
  window layer; query head i reads key/value head ``i // (H / H_kv)``.  The
  first ``rot`` = int(partial_rotary_factor * hd) dimensions of every q and k
  head are rotated, dimension i with i + rot / 2, theta by kind; the rest are
  not.  Scores over ``sqrt(hd)``, causal; a window layer sees ``i - W < j <=
  i`` and its softmax has a SINK: ``p_ij = exp(s_ij) / (exp(b_h) + sum_j'
  exp(s_ij'))``, one ``b_h`` a query head, no value.  ``out = [o_1..o_H] W_o``
  (H x vd -> d).
* ``FFN``: a layer with ``moe_layer_freq[l]`` 0 is a SwiGLU of
  ``intermediate_size``.  Else ``s = sigmoid(a W_r)`` (float32) over ALL
  experts of the source; the k largest of ``s + bias`` chosen; ``w_e = s_e /
  sum of the chosen s``.  THE SHARE: the weights hold experts ``0 .. E_held -
  1`` only; ``y = sum over the chosen e that are held of w_e SwiGLU_e(a)``.
  What the absent experts would add is left out, here as in the program.
* After the last layer ``RMSNorm``, then the held slice of the untied head.

Departures, each forced by what it is compared with: a layer runs one block of
512 rows at a time (attention one key/value head's group of query heads at a
time), so that a probe of 16,384 + 128 tokens fits beside the weights; the
held experts are a loop with a MASK; only the last ``n_last`` positions go
through the head; the choice of experts is discrete, so the reference gives,
beside its own answer, the answer of every choice within NEAR_TIE of its own,
and the comparison takes the nearest (the rule and its distance are
``reference/latent_moe.py``'s, PERF.md section 2).

Weights are data, drawn from the seed by ``draw_weights``: the same keys and
draws as the program's ``init_mimo_v2_params`` (a test holds the two
together), in the type they are served in (bfloat16; the router and the sink
float32).

``precision="int8"`` is the control: every bfloat16 matrix rounded to int8
per output channel, every matmul input to int8 per token (W8A8), accumulated
exactly; the router and the sink stay float32.  It has to come out as not
correct.
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


QUERY_BLOCK = 512
SINK_MEAN = 4.0


def draw_weights(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``li`` from ``split(split(key, L + 2)[li], 12)``: 0 wq, 1 wk,
    2 wv, 3 wo, 4-6 the dense FFN, 7 the router, 8-10 the held experts, 11 the
    sink (``SINK_MEAN`` + normal, float32); the embedding from ``split(key,
    L + 2)[L]``, the head from ``[L + 1]``; normal / sqrt(fan_in); the
    selection bias zeros."""
    L, d, H, hd, vd = s["L"], s["d"], s["H"], s["hd"], s["vd"]
    E, Eh, f, fd = s["E"], s["Eh"], s["f"], s["fd"]

    def dense(key, shape, fan_in, dt=dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def build(key):
        keys = jax.random.split(key, L + 2)
        layers = []
        for li in range(L):
            k = jax.random.split(keys[li], 12)
            Hkv = s["Hkv_w"] if s["windowed"][li] else s["Hkv"]
            lw = {"wq": dense(k[0], (d, H * hd), d), "wk": dense(k[1], (d, Hkv * hd), d),
                  "wv": dense(k[2], (d, Hkv * vd), d), "wo": dense(k[3], (H * vd, d), H * vd),
                  "ln_attn": jnp.ones((d,), dtype), "ln_mlp": jnp.ones((d,), dtype)}
            if s["windowed"][li]:
                lw["sink"] = SINK_MEAN + jax.random.normal(k[11], (H,), jnp.float32)
            if s["moe"][li]:
                lw.update(router=dense(k[7], (d, E), d, jnp.float32),
                          router_bias=jnp.zeros((E,), jnp.float32),
                          w_gate=dense(k[8], (Eh, d, f), d), w_up=dense(k[9], (Eh, d, f), d),
                          w_down=dense(k[10], (Eh, f, d), f))
            else:
                lw.update(w_gate=dense(k[4], (d, fd), d), w_up=dense(k[5], (d, fd), d),
                          w_down=dense(k[6], (fd, d), fd))
            layers.append(lw)
        return {"embed": dense(keys[L], (s["V"], d), d), "layers": tuple(layers),
                "ln_out": jnp.ones((d,), dtype),
                "lm_head": dense(keys[L + 1], (d, s["V"]), d)}

    return jax.jit(build)(jax.random.PRNGKey(seed))


def int8_round(w: jax.Array, axis: int = -2) -> jax.Array:
    """Symmetric int8 with one scale per slice along ``axis`` (-2: per output
    channel of a weight; -1: per token of an activation), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, theta, rot, first=0):
    """x: [S, ..., D] at positions first..first+S-1: the leading ``rot``
    dimensions rotated, dimension i with i + rot / 2; the rest pass."""
    S, half = x.shape[0], rot // 2
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = (first + jnp.arange(S)).astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], -1)


def _rotate_at(x, p, theta, rot):
    """x [..., D] at the one position p."""
    half = rot // 2
    ang = p.astype(jnp.float32) / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang), x[..., rot:]], -1)


def _mat(w, int8):
    w = w.astype(jnp.float32)
    return int8_round(w) if int8 else w


def swiglu(x, w_gate, w_up, w_down, act, int8):
    g, u = x @ _mat(w_gate, int8), x @ _mat(w_up, int8)
    return act(jax.nn.silu(g) * u) @ _mat(w_down, int8)


def _theta(s, windowed):
    return s["theta_w"] if windowed else s["theta"]


def softmax_sink(sc, sink):
    """Softmax over the last axis with ``exp(sink)`` in the denominator
    besides (``sink`` broadcastable to ``sc[..., 0]``); None: plain."""
    if sink is None:
        return jax.nn.softmax(sc, axis=-1)
    m = jnp.maximum(jnp.max(sc, axis=-1), sink)
    e = jnp.exp(sc - m[..., None])
    return e / (jnp.exp(sink - m) + jnp.sum(e, axis=-1))[..., None]


def keys_values(a, lw, *, s, windowed, int8):
    """The K (its leading dimensions rotated) [S, H_kv, hd] and the scaled V
    [S, H_kv, vd] of every position of a layer's normalised input a [S, d]."""
    S = a.shape[0]
    k = (a @ _mat(lw["wk"], int8)).reshape(S, -1, s["hd"])
    v = (a @ _mat(lw["wv"], int8)).reshape(S, -1, s["vd"]) * s["vscale"]
    return rotate(k, _theta(s, windowed), s["rot"]), v


def attention(a, first, k, v, lw, *, s, windowed, int8):
    """One block of queries: a [B, d] (normalised) at positions first..
    against every position's K and V -> [B, d].  One key/value head's group
    of query heads at a time, so that a 16k-token probe's scores ([G, B, S]
    float32) fit beside the weights."""
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    B = a.shape[0]
    H, hd, Hkv = s["H"], s["hd"], k.shape[1]
    G = H // Hkv
    q = rotate((a @ _mat(lw["wq"], int8)).reshape(B, H, hd), _theta(s, windowed),
               s["rot"], first)
    q_pos, k_pos = first + jnp.arange(B), jnp.arange(k.shape[0])
    seen = q_pos[:, None] >= k_pos[None, :]
    if windowed:
        seen &= k_pos[None, :] > q_pos[:, None] - s["W"]
    sink = lw["sink"].reshape(Hkv, G) if windowed else jnp.zeros((Hkv, G), jnp.float32)

    def one_group(args):
        qg, kh, vh, b = args                    # [G, B, hd] [S, hd] [S, vd] [G]
        sc = jnp.where(seen, jnp.einsum("gbd,sd->gbs", qg, kh) / np.sqrt(hd), -jnp.inf)
        return softmax_sink(sc, b[:, None] if windowed else None) @ vh

    o = jax.lax.map(one_group, (
        q.reshape(B, Hkv, G, hd).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2), sink))     # [Hkv, G, B, vd]
    o = o.transpose(2, 0, 1, 3).reshape(B, H * s["vd"])
    return act(o) @ _mat(lw["wo"], int8)


def route(a, lw, *, s):
    """Scores over all experts of the source, the k chosen by score + bias,
    their weights (their own scores over their sum)."""
    scores = jax.nn.sigmoid(a @ lw["router"])                  # float32
    _, idx = jax.lax.top_k(scores + lw["router_bias"], s["k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return scores, idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def ffn(a, lw, *, s, moe, int8):
    """a [S, d] (normalised) -> the dense SwiGLU, or the held experts' terms."""
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    aa = act(a)
    if not moe:
        return swiglu(aa, lw["w_gate"], lw["w_up"], lw["w_down"], act, int8)
    scores, idx, w = route(a, lw, s=s)
    gate = jnp.zeros_like(scores).at[jnp.arange(a.shape[0])[:, None], idx].set(w)

    def one_expert(y, e):          # every token through held expert e, masked
        wg, wu, wd, g = e
        return y + g[:, None] * swiglu(aa, wg, wu, wd, act, int8), None

    held = lw["w_gate"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(a),
                        (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T[:held]))
    return y


def layer(x, n_blocks, lw, *, s, windowed, moe, int8):
    """One block on x [S, d], S a multiple of QUERY_BLOCK: every position's K
    and V first, then QUERY_BLOCK rows at a time through the attention and
    the FFN (the arithmetic is a row's own).  Only the first ``n_blocks``
    blocks of rows are computed (a traced count: every probe of a run is
    padded to ONE length, so a run compiles one program a layer kind, and a
    shorter probe pays for its own rows; the rows beyond come back as they
    went in and, the attention being causal, no computed row reads them)."""
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    ln1, ln2 = lw["ln_attn"].astype(jnp.float32), lw["ln_mlp"].astype(jnp.float32)
    k, v = keys_values(act(rmsnorm(x, ln1, s["eps"])), lw, s=s,
                       windowed=windowed, int8=int8)

    def one_block(i, out):
        first = i * QUERY_BLOCK
        xb = jax.lax.dynamic_slice_in_dim(x, first, QUERY_BLOCK, 0)
        h = xb + attention(act(rmsnorm(xb, ln1, s["eps"])), first, k, v, lw, s=s,
                           windowed=windowed, int8=int8)
        y = h + ffn(rmsnorm(h, ln2, s["eps"]), lw, s=s, moe=moe, int8=int8)
        return jax.lax.dynamic_update_slice_in_dim(out, y, first, 0)

    return jax.lax.fori_loop(0, n_blocks, one_block, x)


def layer_rows(x, lw, *, s, windowed):
    a = rmsnorm(x, lw["ln_attn"].astype(jnp.float32), s["eps"])
    return keys_values(a, lw, s=s, windowed=windowed, int8=False)


def head(x, ln_out, lm_head, *, s, int8):
    w = lm_head.astype(jnp.float32)
    x = rmsnorm(x, ln_out.astype(jnp.float32), s["eps"])
    if int8:
        w, x = int8_round(w), int8_round(x, -1)
    return jax.nn.log_softmax(x @ w, axis=-1)


# -- one position again, with the choice of experts given ----------------------
#
# The choice of 8 of 128 experts is discrete: where a token's 8th and 9th
# largest scores lie closer than bfloat16 arithmetic moves them, a sound
# bfloat16 program may choose another set, and the token's output then
# differs by a whole expert's term (or, where both are absent from this
# share, by the other seven's weights), not by a rounding.  Every such
# choice is a correct answer.  So for each compared position the reference
# gives the answer of EVERY set that differs from its own only among the
# experts within NEAR_TIE of the boundary, layer after layer, and the
# comparison holds the program to the nearest.  The rule, its bounds and the
# distance are reference/latent_moe.py's: a sigmoid score moves by at most a
# quarter of its logit's error, the logit is a 4096-term product of a
# bfloat16-rounded input (2**-9 relative a term: 0.001-0.002 a layer, more
# with depth), and the control (int8) moves the scores several times
# NEAR_TIE, leaves these sets, and is not forgiven.
NEAR_TIE = 0.012
SETS_A_LAYER = 6
MAX_LEAVES = 96
BETWEEN = 6             # at most so many experts a layer are "between"


def near_tie_sets(select: np.ndarray, k: int) -> list:
    """The reference's own k experts, then every other set of k that keeps
    the experts more than NEAR_TIE above the (k+1)-th value, drops those
    more than NEAR_TIE below the k-th, and fills up from the ones between:
    ``[(experts [k], crossed)]``, ``crossed`` = by how much the set breaks
    the reference's order (0 for its own), smallest first."""
    order = np.argsort(-select, kind="stable")[:k + 4]
    v = select[order]
    own = [int(e) for e in order[:k]]
    if v[k - 1] - v[k] >= NEAR_TIE:              # no tie at the boundary
        return [(own, 0.0)]
    firm = [int(e) for e in order[:k] if select[e] > v[k] + NEAR_TIE]
    between = [int(e) for e in order if e not in firm
               and select[e] >= v[k - 1] - NEAR_TIE]
    # of those, the nearest to the boundary
    mid = (v[k - 1] + v[k]) / 2
    between = sorted(sorted(between, key=lambda e: abs(select[e] - mid))[:BETWEEN],
                     key=lambda e: -select[e])
    firm = [e for e in own if e not in between]
    sets = []
    for fill in itertools.combinations(between, k - len(firm)):
        chosen = firm + list(fill)
        if set(chosen) == set(own):
            continue
        left_out = max(select[e] for e in between if e not in fill)
        sets.append((chosen, float(left_out - min(select[e] for e in fill))))
    return [(own, 0.0)] + sorted(sets, key=lambda t: t[1])[:SETS_A_LAYER - 1]




def leaves_pre(xs, p, rows, lw, *, s, windowed, moe):
    """Position p's residuals ``xs`` [leaves, d] (one a choice of experts so
    far) up to the choice: the residual after the attention (each leaf's own
    key and value computed from its residual, every earlier position's taken
    from ``rows`` = (K, V)), the FFN's normalised input and the selection
    values (scores + bias).  A dense layer has no choice: its FFN is added and
    the values are None."""
    H, hd, vd = s["H"], s["hd"], s["vd"]
    K, V = rows
    n, Hkv = xs.shape[0], K.shape[1]
    G = H // Hkv
    f32 = lambda k: lw[k].astype(jnp.float32)
    theta = _theta(s, windowed)
    a = rmsnorm(xs, f32("ln_attn"), s["eps"])
    q = _rotate_at((a @ f32("wq")).reshape(n, Hkv, G, hd), p, theta, s["rot"])
    k_own = _rotate_at((a @ f32("wk")).reshape(n, Hkv, hd), p, theta, s["rot"])
    v_own = (a @ f32("wv")).reshape(n, Hkv, vd) * s["vscale"]
    k_pos = jnp.arange(K.shape[0])
    sc = jnp.einsum("lhgd,shd->lhgs", q, K)
    sc = jnp.where(k_pos == p, jnp.einsum("lhgd,lhd->lhg", q, k_own)[..., None], sc)
    seen = k_pos <= p
    if windowed:
        seen &= k_pos > p - s["W"]
    pr = softmax_sink(jnp.where(seen, sc / np.sqrt(hd), -jnp.inf),
                      lw["sink"].reshape(Hkv, G) if windowed else None)
    o = jnp.einsum("lhgs,shd->lhgd", pr, V)
    o = o + jnp.take(pr, p, axis=-1)[..., None] * (
        v_own - jnp.take(V, p, axis=0))[:, :, None, :]
    h = xs + o.reshape(n, H * vd) @ f32("wo")
    a2 = rmsnorm(h, f32("ln_mlp"), s["eps"])
    if not moe:
        return h + swiglu(a2, lw["w_gate"], lw["w_up"], lw["w_down"],
                          lambda t: t, False), a2, None
    scores = jax.nn.sigmoid(a2 @ lw["router"])
    return h, a2, (scores, scores + lw["router_bias"])


def leaves_post(h, a, scores, union, mask, lw):
    """h + the held experts each leaf chose.  The choices are GIVEN:
    ``union`` [U] names every expert some leaf chose and ``mask`` [leaves, U]
    says which leaf chose which; the weights are the chosen scores over their
    sum, the terms of absent experts left out."""
    su = jnp.take(scores, union, axis=1) * mask
    w = su / jnp.sum(su, axis=1, keepdims=True)
    held = lw["w_gate"].shape[0]
    w = w * (union < held)

    def one_expert(y, e):
        idx, we = e
        up = lambda k: lw[k][jnp.minimum(idx, held - 1)].astype(jnp.float32)
        g = jax.nn.silu(a @ up("w_gate")) * (a @ up("w_up"))
        return y + we[:, None] * (g @ up("w_down")), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (union, w.T))
    return h + y


def make_forward(s: dict, precision: str = "f32"):
    """``forward(params, tokens, n_last, pad_to=None)``: tokens [S] (python
    ints) -> log-probabilities [n_last, V] of the token after each of the
    last ``n_last`` positions, the reference's own choice of experts
    throughout.  ``forward.answers(params, tokens, n_last, pad_to=None)``: per
    position, the answers [leaves, V] of every choice within a near-tie of
    the reference's own (leaf 0 is its own) and by how much each leaf broke
    its order; float32 only.  Tokens are padded on the right to a multiple of
    QUERY_BLOCK, or to ``pad_to`` rows (causal: the padding changes nothing
    before it, and its rows are not computed: ``layer``)."""
    assert precision in ("f32", "int8"), precision
    skey = {k: s[k] for k in ("H", "hd", "vd", "rot", "k", "W", "eps", "theta",
                              "theta_w", "vscale")}
    int8 = precision == "int8"
    kinds = tuple((bool(w), bool(m)) for w, m in zip(s["windowed"], s["moe"]))
    layer_j = {wm: jax.jit(partial(layer, s=skey, windowed=wm[0], moe=wm[1], int8=int8))
               for wm in set(kinds)}
    rows_j = {w: jax.jit(partial(layer_rows, s=skey, windowed=w))
              for w in {w for w, _ in kinds}}
    pre_j = {wm: jax.jit(partial(leaves_pre, s=skey, windowed=wm[0], moe=wm[1]))
             for wm in set(kinds)}
    post_j = jax.jit(leaves_post)
    head_j = jax.jit(partial(head, s=skey, int8=int8))
    k = s["k"]

    def embed(params, tokens, pad_to):
        """The padded rows, and the blocks of them that hold a token."""
        blocks = -(-len(tokens) // QUERY_BLOCK)
        rows = max(pad_to or 0, blocks * QUERY_BLOCK)
        ids = jnp.asarray(list(tokens) + [0] * (rows - len(tokens)), jnp.int32)
        return (params["embed"][ids].astype(jnp.float32),
                jnp.asarray(blocks, jnp.int32))

    def forward(params, tokens, n_last, pad_to=None):
        S = len(tokens)
        with jax.default_matmul_precision("highest"):
            x, nb = embed(params, tokens, pad_to)
            for lw, wm in zip(params["layers"], kinds):
                x = layer_j[wm](x, nb, lw)
            return head_j(x[S - n_last:S], params["ln_out"], params["lm_head"])

    def answers(params, tokens, n_last, pad_to=None):
        S = len(tokens)
        with jax.default_matmul_precision("highest"):
            x, nb = embed(params, tokens, pad_to)
            x0 = x
            rows = []
            for lw, wm in zip(params["layers"], kinds):
                rows.append(rows_j[wm[0]](x, lw))
                x = layer_j[wm](x, nb, lw)
            out = []
            for pos in range(S - n_last, S):
                p = jnp.asarray(pos, jnp.int32)
                # MAX_LEAVES residuals side by side (a fixed shape: one
                # program a layer kind); the first ``len(crossed)`` are alive
                xs = jnp.broadcast_to(x0[pos], (MAX_LEAVES, x0.shape[1]))
                crossed = [0.0]        # by how much each leaf broke the order, summed
                for lw, r, wm in zip(params["layers"], rows, kinds):
                    h, a, sel = pre_j[wm](xs, p, r, lw)
                    if sel is None:    # a dense layer: no choice, the leaves go on
                        xs = h
                        continue
                    scores, select = sel
                    select = np.asarray(select)
                    grown = [(leaf, chosen, crossed[leaf] + by)
                             for leaf in range(len(crossed))
                             for chosen, by in near_tie_sets(select[leaf], k)]
                    # its own choice first; of the rest, the nearest ties
                    grown = [grown[0]] + sorted(grown[1:], key=lambda g: g[2])[:MAX_LEAVES - 1]
                    crossed = [c for _, _, c in grown]
                    grown += [grown[0]] * (MAX_LEAVES - len(grown))
                    parent = jnp.asarray([g[0] for g in grown], jnp.int32)
                    # every expert some leaf chose, padded to a multiple of
                    # 16 (a few shapes); the padding is chosen by no leaf
                    union = sorted({e for _, chosen, _ in grown for e in chosen})
                    union += [union[0]] * ((-len(union)) % 16)
                    mask = np.zeros((MAX_LEAVES, len(union)), np.float32)
                    for leaf, (_, chosen, _) in enumerate(grown):
                        mask[leaf, [union.index(e) for e in chosen]] = 1.0
                    xs = post_j(h[parent], a[parent], scores[parent],
                                jnp.asarray(union, jnp.int32), jnp.asarray(mask), lw)
                lp = head_j(xs[:len(crossed)], params["ln_out"], params["lm_head"])
                out.append((np.asarray(lp), crossed))
            return out

    forward.answers = answers if precision == "f32" else None
    return forward


def reference_logprobs(forward, params, probes):
    """For each probe and each of its n generated positions, the reference's
    answers ``(log-probabilities [leaves, V], margins [leaves])``: leaf 0 its
    own choice of experts, the others every choice within a near-tie of it;
    the prompt plus the tokens the server chose being given (teacher-forced).
    The control's forward gives its own choice alone.  Every probe is padded
    to the longest one's rows: one program a layer kind a run, where a length
    of its own each compiled the reference three times over (150 s of a cold
    call's 187, my chip run, PR 51)."""
    asked = [(list(p["prompt"]) + list(p["ids"][:-1]), len(p["ids"])) for p in probes]
    rows = max(-(-len(tokens) // QUERY_BLOCK) for tokens, _ in asked) * QUERY_BLOCK
    out = []
    for tokens, n in asked:
        if forward.answers is not None:
            out.append(forward.answers(params, tokens, n, pad_to=rows))
        else:
            lp = np.asarray(forward(params, tokens, n, pad_to=rows))
            out.append([(lp[i:i + 1], [0.0]) for i in range(n)])
    return out


def compare(answers, ref_lps) -> dict:
    """``answers``: per probe {"ids": [chosen], "top": [{id: lp} per
    position]} as the system under test gave them; ``ref_lps`` from
    ``reference_logprobs``.  At each position the system is held to the
    NEAREST of the reference's answers (least sum of squares over the
    position's top-k ids).  The statistic is the RMS, over every top-k id of
    every position, of the system's log-probability minus that answer's for
    the same token; apart, how many chosen tokens are not among that answer's
    top 5, at how many positions an answer other than the reference's own
    choice was the nearest (``resolved``) and the margins those crossed."""
    diffs, misses, rows, resolved, margins, leaves = [], 0, [], 0, [], 0
    for ans, ref in zip(answers, ref_lps):
        d_probe = []
        for pos, top in enumerate(ans["top"]):
            lps, crossed = ref[pos]
            ids = [int(t) for t in top]
            d = np.asarray([float(v) for v in top.values()])[None, :] - lps[:, ids]
            best = int(np.argmin(np.sum(d * d, axis=1)))
            if int(ans["ids"][pos]) not in set(np.argsort(lps[best])[-5:].tolist()):
                misses += 1
            if best:
                resolved += 1
                margins.append(float(crossed[best]))
            leaves += len(crossed)
            d_probe += d[best].tolist()
        diffs += d_probe
        rows.append(float(np.sqrt(np.mean(np.square(d_probe)))))
    return {"n_values": len(diffs),
            "rms": float(np.sqrt(np.mean(np.square(diffs)))),
            "max_abs": float(np.max(np.abs(diffs))),
            "chosen_not_in_ref_top5": misses, "per_probe_rms": rows,
            "resolved": resolved, "resolved_margins": margins,
            "answers_per_position": leaves / max(1, sum(len(a["top"]) for a in answers))}


def control_answers(low_lps, answers):
    """The control's answers: the lower-precision reference put in the
    program's place (its own choice of experts), read at the same token ids."""
    out = []
    for ref, ans in zip(low_lps, answers):
        lp = [ref[pos][0][0] for pos in range(len(ans["ids"]))]
        out.append({"ids": [int(np.argmax(lp[pos])) for pos in range(len(ans["ids"]))],
                    "top": [{t: float(lp[pos][int(t)]) for t in top}
                            for pos, top in enumerate(ans["top"])]})
    return out
