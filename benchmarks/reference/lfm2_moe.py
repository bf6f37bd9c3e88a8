"""The plain reference of the short-convolution / attention decoder with routed
experts (``model_type`` ``lfm2_moe``): the forward pass in straightforward
jax.numpy, float32, matmuls at "highest" precision, no cache, no chunks, no
paging, no batching, no grouped kernels.  Written from the row's ``config`` and
the family's published modelling code; it imports nothing of infinistore_tpu.

A block is ``x = x + Op(RMSNorm(x))`` then ``x = x + FFN(RMSNorm(x))``:

* ``conv`` layers: ``[B | C | u] = h W_in``, ``v = B * u``, ``c_t = sum_{j<K} w[:,
  j] v_{t-(K-1)+j}`` written as that sum over the WHOLE sequence (``v`` before
  its start is zero), ``y = C * c``, ``out = y W_out``.  No state exists here:
  the program's two carried rows are held to this.
* ``full_attention`` layers: grouped-query attention, RMSNorm over each head of
  Q and of K, rotary embedding, causal softmax at ``1 / sqrt(head)``, ``W_o``.
* the first ``nd`` layers' FFN a SwiGLU; every later layer's routed experts:
  ``s = sigmoid(h W_r)`` (float32); the k largest of ``s + b`` (``b`` chooses and
  does not weigh); weights ``scaling * s_e / (sum of the chosen s + 1e-6)``; no
  shared expert.  No token is dropped, no capacity is set.
* one more RMSNorm, then the TIED embedding as the head.

Departures from the published code, each forced by what it is compared with:

* Rotary pairs are (2i, 2i+1), the column order the program's ``apply_rope``
  rotates; Hugging Face pairs (i, i + 32): the same function after a fixed
  permutation of a head's columns, which seeded weights do not need.
* Attention runs one key/value head and one block of 512 queries at a time,
  so a 16k-token probe's scores fit beside the weights; the arithmetic is
  unchanged.
* The experts are a loop with a MASK: every expert runs on every token, one
  expert at a time, and the gate (zero off the chosen) weighs it.
* The position-wise parts (a layer's FFN) run in blocks of 2,048 tokens, so
  that one compiled program serves every probe length; the operators run
  over the whole sequence at once.
* Only the last ``n_last`` positions go through the head, and ABOVE THE LAST
  ATTENTION LAYER only the positions those can read are carried: a conv
  layer reaches ``K - 1`` positions back, so ``n_last + (K - 1) x`` (conv layers
  above) rows; every row below them would be computed and thrown away (the
  experts of every token, in four of this cut's eight expert layers).
* The choice of experts is discrete, so the reference gives, beside its own
  answer, the answer of every choice within a near-tie of its own, and the
  comparison takes the nearest ("one position again", below).
* The selection bias is SEEDED (normal x 0.02; ``assumed`` in the
  configuration's file): with zeros no check tells a bias that chooses from
  one that also weighs.

Weights are data, drawn from the seed by ``draw_weights``: the same keys and
the same draws as the program's ``init_lfm2_moe_params`` (a test holds the two
together), in the type they are served in (bfloat16; the router and its bias
float32), upcast one layer, and one expert, at a time.

``precision="int8"`` is the control: the same reference with every bfloat16
matrix rounded to int8 per output channel and every matmul input rounded to
int8 per token (W8A8, dynamic scales), accumulated exactly.  The router and
the convolution's three taps stay float32.  It has to come out as not correct.
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
FFN_BLOCK = 2048
NORM_TOPK_EPS = 1e-6
BIAS_STD = 0.02


def draw_weights(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``li`` from ``split(split(key, L + 1)[li], 12)``: a conv operator
    0 w_in, 1 conv_w (fan-in K), 2 w_out; an attention operator 0-3 wq wk wv
    wo; 4-6 the dense FFN; 7 the router, 8 the selection bias (normal x 0.02),
    9-11 the experts; the embedding from ``split(key, L + 1)[L]``; normal /
    sqrt(fan_in) but the bias."""
    L, d, hd, K, E, f = s["L"], s["d"], s["hd"], s["K"], s["E"], s["f"]
    nq, nkv = s["H"] * hd, s["kv"] * hd

    def dense(key, shape, fan_in, dt=dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def build(key):
        keys = jax.random.split(key, L + 1)
        layers = []
        for li in range(L):
            k = jax.random.split(keys[li], 12)
            lw = {"ln_attn": jnp.ones((d,), dtype), "ln_mlp": jnp.ones((d,), dtype)}
            if s["types"][li] == "conv":
                lw.update(w_in=dense(k[0], (d, 3 * d), d),
                          conv_w=dense(k[1], (d, K), K),
                          w_out=dense(k[2], (d, d), d))
            else:
                lw.update(wq=dense(k[0], (d, nq), d), wk=dense(k[1], (d, nkv), d),
                          wv=dense(k[2], (d, nkv), d), wo=dense(k[3], (nq, d), nq),
                          q_norm=jnp.ones((hd,), dtype), k_norm=jnp.ones((hd,), dtype))
            if li < s["nd"]:
                lw.update(w_gate=dense(k[4], (d, s["f_dense"]), d),
                          w_up=dense(k[5], (d, s["f_dense"]), d),
                          w_down=dense(k[6], (s["f_dense"], d), s["f_dense"]))
            else:
                lw.update(router=dense(k[7], (d, E), d, jnp.float32),
                          router_bias=BIAS_STD * jax.random.normal(
                              k[8], (E,), jnp.float32),
                          w_gate=dense(k[9], (E, d, f), d),
                          w_up=dense(k[10], (E, d, f), d),
                          w_down=dense(k[11], (E, f, d), f))
            layers.append(lw)
        return {"embed": dense(keys[L], (s["V"], d), d), "layers": tuple(layers),
                "ln_out": jnp.ones((d,), dtype)}

    return jax.jit(build)(jax.random.PRNGKey(seed))


def int8_round(w: jax.Array, axis: int = -2) -> jax.Array:
    """Symmetric int8 with one scale per slice along ``axis`` (-2: per output
    channel of a weight; -1: per token of an activation), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, pos, theta):
    """x: [S, ..., D] at positions ``pos`` [S]; pairs (2i, 2i+1)."""
    S, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def _mat(w, int8):
    w = w.astype(jnp.float32)
    return int8_round(w) if int8 else w


def _act(int8):
    return (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)


def swiglu(x, w_gate, w_up, w_down, act, int8):
    g, u = x @ _mat(w_gate, int8), x @ _mat(w_up, int8)
    return act(jax.nn.silu(g) * u) @ _mat(w_down, int8)


def conv_v(a, lw, *, s, int8):
    """The rows the convolution sums, and its output gate: ``(v, C)`` [S, d]."""
    d = s["d"]
    bcu = _act(int8)(a) @ _mat(lw["w_in"], int8)
    return bcu[:, :d] * bcu[:, 2 * d:], bcu[:, d: 2 * d]


def conv_op(a, lw, *, s, int8):
    """The gated short convolution over the whole sequence a [S, d]."""
    S, K = a.shape[0], s["K"]
    v, C = conv_v(a, lw, s=s, int8=int8)
    w = lw["conv_w"].astype(jnp.float32)                       # [d, K]
    vp = jnp.pad(v, ((K - 1, 0), (0, 0)))                      # zeros before the start
    c = sum(w[:, j] * vp[j: j + S] for j in range(K))
    return _act(int8)(C * c) @ _mat(lw["w_out"], int8)


def qkv(a, pos, lw, *, s, int8):
    """a [n, d] at positions ``pos`` -> q [n, H, hd], k and v [n, kv, hd], q
    and k normalised by head and rotated."""
    n = a.shape[0]
    a = _act(int8)(a)
    f32 = lambda k: lw[k].astype(jnp.float32)
    q = (a @ _mat(lw["wq"], int8)).reshape(n, s["H"], s["hd"])
    k = (a @ _mat(lw["wk"], int8)).reshape(n, s["kv"], s["hd"])
    v = (a @ _mat(lw["wv"], int8)).reshape(n, s["kv"], s["hd"])
    q = rotate(rmsnorm(q, f32("q_norm"), s["eps"]), pos, s["theta"])
    k = rotate(rmsnorm(k, f32("k_norm"), s["eps"]), pos, s["theta"])
    return q, k, v


def attention_op(a, lw, *, s, int8):
    S = a.shape[0]
    H, kv, hd = s["H"], s["kv"], s["hd"]
    G = H // kv
    q, k, v = qkv(a, jnp.arange(S), lw, s=s, int8=int8)
    # one key/value head and one block of QUERY_BLOCK queries at a time, so
    # that a 16k-token probe's scores ([G, block, S] float32) fit
    nb = -(-S // QUERY_BLOCK)
    rows = nb * QUERY_BLOCK
    qb = jnp.pad(q, ((0, rows - S), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, kv, G, hd).transpose(2, 0, 3, 1, 4).reshape(
        kv * nb, G, QUERY_BLOCK, hd)
    q_pos = jnp.tile(jnp.arange(rows).reshape(nb, QUERY_BLOCK), (kv, 1))
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)         # [kv, S, hd]
    k_pos = jnp.arange(S)

    def one_block(args):
        qg, pos, h = args                   # [G, block, hd] [block] []
        sc = jnp.einsum("gqd,kd->gqk", qg, kh[h]) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(pos[None, :, None] >= k_pos[None, None, :],
                                     sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vh[h])

    o = jax.lax.map(one_block, (qb, q_pos, jnp.repeat(jnp.arange(kv), nb)))
    o = o.reshape(kv, nb, G, QUERY_BLOCK, hd).transpose(1, 3, 0, 2, 4).reshape(
        rows, H * hd)[:S]
    return _act(int8)(o) @ _mat(lw["wo"], int8)


def experts(m, lw, *, s, int8):
    """m [S, d] (normalised) -> the routed experts' weighted sum."""
    act = _act(int8)
    scores = jax.nn.sigmoid(m @ lw["router"])                  # float32
    _, idx = jax.lax.top_k(scores + lw["router_bias"], s["k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    w = s["scaling"] * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                                 + NORM_TOPK_EPS)
    gate = jnp.zeros_like(scores).at[jnp.arange(m.shape[0])[:, None], idx].set(w)
    ma = act(m)

    def one_expert(y, e):          # every token through expert e, masked
        wg, wu, wd, g = e
        return y + g[:, None] * swiglu(ma, wg, wu, wd, act, int8), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                        (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T))
    return y


def op(x, lw, *, s, int8):
    """A layer's operator over the WHOLE sequence x [S, d]: ``(x + Op(RMSNorm(x)),
    rows)``; ``lw`` is the layer's leaves in the served type, upcast where they
    are used, and which operator it holds is its leaves' structure.  ``rows``
    is what the operator reads of EARLIER positions, for every position: a
    conv layer's ``v`` [S, d]; an attention layer's rotated keys and its
    values side by side [S, kv, 2 hd] (the one-position pass reads them)."""
    a = rmsnorm(x, lw["ln_attn"].astype(jnp.float32), s["eps"])
    if "conv_w" in lw:
        return x + conv_op(a, lw, s=s, int8=int8), conv_v(a, lw, s=s, int8=False)[0]
    _, k, v = qkv(a, jnp.arange(x.shape[0]), lw, s=s, int8=False)
    return x + attention_op(a, lw, s=s, int8=int8), jnp.concatenate([k, v], axis=-1)


def ffn(x, lw, *, s, int8):
    """A layer's FFN on a block of positions x [n, d] (position by position:
    the block is no more than a shape)."""
    m = rmsnorm(x, lw["ln_mlp"].astype(jnp.float32), s["eps"])
    if "router" in lw:
        return x + experts(m, lw, s=s, int8=int8)
    act = _act(int8)
    return x + swiglu(act(m), lw["w_gate"], lw["w_up"], lw["w_down"], act, int8)


def layer(x, lw, *, s, int8):
    """One decoder layer on x [S, d], whole: the tests' form."""
    return ffn(op(x, lw, s=s, int8=int8)[0], lw, s=s, int8=int8)


def layer_rows(x, lw, *, s):
    return op(x, lw, s=s, int8=False)[1]


def head(x, ln_out, embed, *, s, int8):
    w = embed.astype(jnp.float32).T
    x = rmsnorm(x, ln_out.astype(jnp.float32), s["eps"])
    if int8:
        w, x = int8_round(w), int8_round(x, -1)
    return jax.nn.log_softmax(x @ w, axis=-1)


# -- one position again, with the choice of experts given ----------------------
#
# The choice of experts is discrete: where a token's k-th and (k+1)-th largest
# selection values lie closer than bfloat16 arithmetic moves them, a sound
# bfloat16 program may choose another set, and the token's output then differs
# by a whole expert's, not by a rounding; the difference carries into the
# choices of the layers above.  Every such choice is a correct answer.  So for
# each compared position the reference gives the answer of EVERY set of
# experts that differs from its own only among the experts within NEAR_TIE of
# the boundary, layer after layer, and the comparison holds the program to the
# nearest.  A position is taken through the layers again alone (float32, the
# other positions' rows as the full pass left them), branching at each
# near-tie: at most SETS_A_LAYER sets a layer and MAX_LEAVES answers a
# position, those kept whose choices break the reference's order by the least
# in sum.  The control (int8) moves the selection values several times as
# far, leaves these sets, and is not forgiven.
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


def leaves_op(xs, p, rows, lw, *, s):
    """Position p's residuals ``xs`` [leaves, d] (one a choice of experts so
    far) through a layer's operator: each leaf's own row computed from its
    residual, every earlier position's taken from ``rows`` (``op``'s of the
    full pass; ``p`` counts from ``rows``' first position, which is the
    sequence's own but above the last attention layer)."""
    n = xs.shape[0]
    f32 = lambda k: lw[k].astype(jnp.float32)
    a = rmsnorm(xs, f32("ln_attn"), s["eps"])
    if "conv_w" in lw:
        K = s["K"]
        v, C = conv_v(a, lw, s=s, int8=False)
        w = f32("conv_w")
        # v at positions p - (K - 1) .. p - 1 of the full pass, zero before 0
        c = w[:, K - 1] * v
        for j in range(K - 1):
            q = p - (K - 1) + j
            row = jnp.where(q >= 0, jnp.take(rows, jnp.maximum(q, 0), axis=0), 0.0)
            c = c + w[:, j] * row
        return xs + (C * c) @ f32("w_out")
    H, kv, hd = s["H"], s["kv"], s["hd"]
    G = H // kv
    q, k_own, v_own = qkv(a, jnp.broadcast_to(p, (n,)), lw, s=s, int8=False)
    q = q.reshape(n, kv, G, hd)
    k_pos = jnp.arange(rows.shape[0])
    sc = jnp.einsum("lhgd,shd->lhgs", q, rows[..., :hd])
    own = jnp.einsum("lhgd,lhd->lhg", q, k_own)
    sc = jnp.where(k_pos == p, own[..., None], sc)
    pr = jax.nn.softmax(jnp.where(k_pos <= p, sc / np.sqrt(hd), -jnp.inf), axis=-1)
    o = jnp.einsum("lhgs,shd->lhgd", pr, rows[..., hd:])
    o = o + jnp.take(pr, p, axis=-1)[..., None] * (
        v_own[:, :, None, :] - jnp.take(rows, p, axis=0)[None, :, None, hd:])
    return xs + o.reshape(n, H * hd) @ f32("wo")


def row_scores(x, lw, *, s):
    m = rmsnorm(x, lw["ln_mlp"].astype(jnp.float32), s["eps"])
    scores = jax.nn.sigmoid(m @ lw["router"])
    return m, scores, scores + lw["router_bias"]


def leaves_experts(xs, m, scores, union, mask, lw, *, s):
    """xs + the routed experts each leaf chose.  The choices are GIVEN, not
    made here: ``union`` [U] names every expert some leaf chose and ``mask``
    [leaves, U] says which leaf chose which.  One expert at a time over all
    leaves, the gate (zero where a leaf did not choose it) weighing it."""
    su = jnp.take(scores, union, axis=1) * mask
    w = s["scaling"] * su / (jnp.sum(su, axis=1, keepdims=True) + NORM_TOPK_EPS)

    def one_expert(y, e):
        idx, we = e
        up = lambda k: lw[k][idx].astype(jnp.float32)
        h = jax.nn.silu(m @ up("w_gate")) * (m @ up("w_up"))
        return y + we[:, None] * (h @ up("w_down")), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(xs), (union, w.T))
    return xs + y


def row_dense(x, lw, *, s):
    m = rmsnorm(x, lw["ln_mlp"].astype(jnp.float32), s["eps"])
    return x + swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], lambda t: t, False)


def make_forward(s: dict, precision: str = "f32"):
    """``forward(params, tokens, n_last)``: tokens [S] (python ints) ->
    log-probabilities [n_last, V] of the token after each of the last
    ``n_last`` positions, the reference's own choice of experts throughout.
    ``forward.answers(params, tokens, n_last)``: per position, the answers
    [leaves, V] of every choice within a near-tie of the reference's own
    (leaf 0 is its own) and by how much each leaf broke its order; float32 only
    (the control is read at its own choice).  Tokens are padded on the right
    to a multiple of QUERY_BLOCK (causal: the padding changes nothing before
    it), so that probes of eight lengths compile few programs."""
    assert precision in ("f32", "int8"), precision
    skey = {k: s[k] for k in ("d", "H", "kv", "hd", "K", "k", "scaling", "eps",
                              "theta")}
    kw = dict(s=skey, int8=precision == "int8")
    op_j = jax.jit(partial(op, **kw))
    ffn_j = jax.jit(partial(ffn, **kw))
    head_j = jax.jit(partial(head, **kw))
    # the one-position functions over MAX_LEAVES residuals, one at a time
    leaf_op_j = jax.jit(partial(leaves_op, s=skey))
    scores_j = jax.jit(jax.vmap(partial(row_scores, s=skey), in_axes=(0, None)))
    experts_j = jax.jit(partial(leaves_experts, s=skey))
    dense_j = jax.jit(jax.vmap(partial(row_dense, s=skey), in_axes=(0, None)))
    k = s["k"]

    def embed(params, tokens):
        pad = (-len(tokens)) % QUERY_BLOCK
        ids = jnp.asarray(list(tokens) + [0] * pad, jnp.int32)
        return params["embed"][ids].astype(jnp.float32)

    def ffn_blocks(x, lw):
        n = x.shape[0]
        x = jnp.pad(x, ((0, (-n) % FFN_BLOCK), (0, 0)))
        return jnp.concatenate([ffn_j(x[i: i + FFN_BLOCK], lw)
                                for i in range(0, x.shape[0], FFN_BLOCK)])[:n]

    def full_pass(params, tokens, n_last):
        """Every layer over the sequence: ``(x of the last n_last positions,
        [(rows, first position) a layer])``.  Above the last attention layer
        only the rows the last ``n_last`` positions can read are carried."""
        S, layers = len(tokens), params["layers"]
        last_attn = max((i for i, lw in enumerate(layers) if "conv_w" not in lw),
                        default=-1)
        reach = n_last + (s["K"] - 1) * (len(layers) - 1 - last_attn)
        x, first, rows = embed(params, tokens), 0, []
        for li, lw in enumerate(layers):
            x, r = op_j(x, lw)
            rows.append((r, first))
            if li == last_attn and S - reach > 0:
                x, first = x[S - reach: S], S - reach
            x = ffn_blocks(x, lw)
        return x[S - first - n_last: S - first], rows

    def forward(params, tokens, n_last):
        with jax.default_matmul_precision("highest"):
            x, _ = full_pass(params, tokens, n_last)
            return head_j(x, params["ln_out"], params["embed"])

    def answers(params, tokens, n_last):
        S = len(tokens)
        with jax.default_matmul_precision("highest"):
            x0 = embed(params, tokens)
            _, rows = full_pass(params, tokens, n_last)
            out = []
            for pos in range(S - n_last, S):
                # MAX_LEAVES residuals side by side (a fixed shape: one
                # program a layer kind); the first ``len(crossed)`` are alive
                xs = jnp.broadcast_to(x0[pos], (MAX_LEAVES, x0.shape[1]))
                crossed = [0.0]        # by how much each leaf broke the order, summed
                for lw, (r, first) in zip(params["layers"], rows):
                    xs = leaf_op_j(xs, jnp.asarray(pos - first, jnp.int32), r, lw)
                    if "router" not in lw:
                        xs = dense_j(xs, lw)
                        continue
                    m, scores, select = scores_j(xs, lw)
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
                    xs = experts_j(xs[parent], m[parent], scores[parent],
                                   jnp.asarray(union, jnp.int32), jnp.asarray(mask), lw)
                lp = head_j(xs[:len(crossed)], params["ln_out"], params["embed"])
                out.append((np.asarray(lp), crossed))
            return out

    forward.answers = answers if precision == "f32" else None
    return forward


def reference_logprobs(forward, params, probes):
    """For each probe and each of its n generated positions, the reference's
    answers ``(log-probabilities [leaves, V], margins [leaves])``: leaf 0 its
    own choice of experts, the others every choice within a near-tie of it;
    the prompt plus the tokens the server chose being given (teacher-forced).
    The control's forward gives its own choice alone."""
    out = []
    for p in probes:
        tokens, n = list(p["prompt"]) + list(p["ids"][:-1]), len(p["ids"])
        if forward.answers is not None:
            out.append(forward.answers(params, tokens, n))
        else:
            lp = np.asarray(forward(params, tokens, n))
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
