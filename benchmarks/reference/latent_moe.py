"""The plain reference of the latent-attention, routed-expert decoder
(``model_type`` ``deepseek_v3``): the forward pass in straightforward
jax.numpy, float32, matmuls at "highest" precision, no cache, no paging, no
batching, no grouped kernels.  Written from the published equations; it
imports nothing of infinistore_tpu.

With ``h' = RMSNorm(h)``:

* attention: ``q = h' W_q`` -> H heads of ``[q_n (nope); q_r (rope)]``, ``q_r``
  rotated; ``[c (R); k_r (rope)] = h' W_kva``, ``c <- RMSNorm(c)``, ``k_r``
  rotated, one for all heads; ``[k_n,i; v_i] = c W_kvb,i``, ``k_i = [k_n,i;
  k_r]``; causal softmax of ``q_i . k_i / sqrt(nope + rope)``; ``o_i = sum p
  v_i``; ``out = [o_1..o_H] W_o``.  Always EXPANDED: the program's absorbed
  decode path is held to this.
* expert layer: ``s = sigmoid(h' W_r)`` (float32); the k largest of ``s + b``;
  weights ``scaling * s_e / sum of the chosen s``; ``y = sum_e w_e SwiGLU_e(h')
  + SwiGLU_shared(h')``.  The leading ``nd`` layers are a dense SwiGLU.  No
  token is dropped, no capacity is set.

Departures from the papers, each forced by what it is compared with:

* Rotary pairs are (2i, 2i+1): the published checkpoints' interleaved pairing
  and the column order the program's weights are drawn in (Hugging Face
  permutes to rotate-half; the same function after a fixed permutation).
* Attention runs one head and one block of 512 queries at a time, so a
  16k-token probe's scores fit beside the weights; the arithmetic is unchanged.
* The experts are a loop with a MASK: every expert runs on every token, one
  expert at a time, and the gate (zero off the chosen six) weighs it.
* Only the last ``n_last`` positions go through the lm_head.
* The choice of experts is discrete, so the reference gives, beside its own
  answer, the answer of every choice within a near-tie of its own, and the
  comparison takes the nearest ("one position again", below).
* The selection bias is zeros (``assumed`` in the configuration's file).

Weights are data, drawn from the seed by ``draw_weights``: the same keys and
the same draws as the program's ``init_mla_moe_params`` (a test holds the two
together), in the type they are served in (bfloat16; the router float32),
upcast one layer, and one expert, at a time.

``precision="int8"`` is the control: the same reference with every bfloat16
matrix rounded to int8 per output channel and every matmul input rounded to
int8 per token (W8A8, dynamic scales), accumulated exactly.  The router
stays float32, as the configuration states it.  It has to come out as not
correct.
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


QUERY_BLOCK = 512


def draw_weights(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``li`` from ``split(split(key, L + 2)[li], 12)``: 0 wq, 1 w_kva,
    2 w_kvb, 3 wo, 4-6 the dense FFN, 7 the router, 8-10 the experts,
    ``split([11], 3)`` the shared experts; normal / sqrt(fan_in)."""
    L, nd, d, H, E = s["L"], s["nd"], s["d"], s["H"], s["E"]
    R, W = s["R"], s["R"] + s["rope"]
    f, fs = s["f"], s["ns"] * s["f"]

    def dense(key, shape, fan_in, dt=dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def build(key):
        keys = jax.random.split(key, L + 2)
        layers = []
        for li in range(L):
            k = jax.random.split(keys[li], 12)
            lw = {
                "wq": dense(k[0], (d, H * (s["nope"] + s["rope"])), d),
                "w_kva": dense(k[1], (d, W), d),
                "w_kvb": dense(k[2], (R, H * (s["nope"] + s["v"])), R),
                "wo": dense(k[3], (H * s["v"], d), H * s["v"]),
                "kv_norm": jnp.ones((R,), dtype),
                "ln_attn": jnp.ones((d,), dtype), "ln_mlp": jnp.ones((d,), dtype),
            }
            if li < nd:
                lw.update(w_gate=dense(k[4], (d, s["f_dense"]), d),
                          w_up=dense(k[5], (d, s["f_dense"]), d),
                          w_down=dense(k[6], (s["f_dense"], d), s["f_dense"]))
            else:
                ks = jax.random.split(k[11], 3)
                lw.update(router=dense(k[7], (d, E), d, jnp.float32),
                          router_bias=jnp.zeros((E,), jnp.float32),
                          w_gate=dense(k[8], (E, d, f), d),
                          w_up=dense(k[9], (E, d, f), d),
                          w_down=dense(k[10], (E, f, d), f),
                          ws_gate=dense(ks[0], (d, fs), d),
                          ws_up=dense(ks[1], (d, fs), d),
                          ws_down=dense(ks[2], (fs, d), fs))
            layers.append(lw)
        return {"embed": dense(keys[-2], (s["V"], d), d), "layers": tuple(layers),
                "ln_out": jnp.ones((d,), dtype),
                "lm_head": dense(keys[-1], (d, s["V"]), d)}

    return jax.jit(build)(jax.random.PRNGKey(seed))


def int8_round(w: jax.Array, axis: int = -2) -> jax.Array:
    """Symmetric int8 with one scale per slice along ``axis`` (-2: per output
    channel of a weight; -1: per token of an activation), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, theta):
    """x: [S, ..., D] at positions 0..S-1; pairs (2i, 2i+1)."""
    S, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def _mat(w, int8):
    w = w.astype(jnp.float32)
    return int8_round(w) if int8 else w


def swiglu(x, w_gate, w_up, w_down, act, int8):
    g, u = x @ _mat(w_gate, int8), x @ _mat(w_up, int8)
    return act(jax.nn.silu(g) * u) @ _mat(w_down, int8)


def attention(x, lw, *, s, int8):
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    S = x.shape[0]
    H, R, nope, rope, v = s["H"], s["R"], s["nope"], s["rope"], s["v"]
    a = act(rmsnorm(x, lw["ln_attn"].astype(jnp.float32), s["eps"]))
    q = (a @ _mat(lw["wq"], int8)).reshape(S, H, nope + rope)
    q_n, q_r = q[..., :nope], rotate(q[..., nope:], s["theta"])
    ckr = a @ _mat(lw["w_kva"], int8)
    c = rmsnorm(ckr[:, :R], lw["kv_norm"].astype(jnp.float32), s["eps"])
    k_r = rotate(ckr[:, R:], s["theta"])                       # [S, rope]
    kv = (act(c) @ _mat(lw["w_kvb"], int8)).reshape(S, H, nope + v)
    # one head and one block of QUERY_BLOCK queries at a time, so that a
    # 16k-token probe's scores ([block, S] float32) fit beside the weights
    nb = -(-S // QUERY_BLOCK)
    rows = nb * QUERY_BLOCK

    def blocks(t):                          # [S, H, x] -> [H * nb, block, x]
        t = jnp.pad(t, ((0, rows - S), (0, 0), (0, 0)))
        return t.reshape(nb, QUERY_BLOCK, H, -1).transpose(2, 0, 1, 3).reshape(
            H * nb, QUERY_BLOCK, -1)

    k_n, v_h = kv[..., :nope].transpose(1, 0, 2), kv[..., nope:].transpose(1, 0, 2)
    q_pos = jnp.tile(jnp.arange(rows).reshape(nb, QUERY_BLOCK), (H, 1))
    k_pos = jnp.arange(S)

    def one_block(args):
        qn, qr, pos, h = args            # [block, nope] [block, rope] [block] []
        sc = (qn @ k_n[h].T + qr @ k_r.T) / np.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(pos[:, None] >= k_pos[None, :], sc, -jnp.inf),
                           axis=-1)
        return p @ v_h[h]

    o = jax.lax.map(one_block, (blocks(q_n), blocks(q_r), q_pos,
                                jnp.repeat(jnp.arange(H), nb)))   # [H * nb, block, v]
    o = o.reshape(H, rows, v)[:, :S].transpose(1, 0, 2).reshape(S, H * v)
    return act(o) @ _mat(lw["wo"], int8)


def experts(m, lw, *, s, int8):
    """m [S, d] (normalised) -> the routed sum plus the shared experts."""
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    scores = jax.nn.sigmoid(m @ lw["router"])                  # float32
    _, idx = jax.lax.top_k(scores + lw["router_bias"], s["k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    w = s["scaling"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    gate = jnp.zeros_like(scores).at[jnp.arange(m.shape[0])[:, None], idx].set(w)
    ma = act(m)

    def one_expert(y, e):          # every token through expert e, masked
        wg, wu, wd, g = e
        return y + g[:, None] * swiglu(ma, wg, wu, wd, act, int8), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                        (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T))
    return y + swiglu(ma, lw["ws_gate"], lw["ws_up"], lw["ws_down"], act, int8)


def layer(x, lw, *, s, int8):
    """One decoder layer on x [S, d]; ``lw`` is that layer's leaves in the
    served type, upcast where they are used."""
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    x = x + attention(x, lw, s=s, int8=int8)
    m = rmsnorm(x, lw["ln_mlp"].astype(jnp.float32), s["eps"])
    if "router" in lw:
        return x + experts(m, lw, s=s, int8=int8)
    return x + swiglu(act(m), lw["w_gate"], lw["w_up"], lw["w_down"], act, int8)


def latent_rows(x, lw, *, s):
    """The rows a layer's attention reads, [S, R + rope]: the normalised
    latent and the rotated shared key of every position of its input x."""
    a = rmsnorm(x, lw["ln_attn"].astype(jnp.float32), s["eps"])
    ckr = a @ lw["w_kva"].astype(jnp.float32)
    c = rmsnorm(ckr[:, :s["R"]], lw["kv_norm"].astype(jnp.float32), s["eps"])
    return jnp.concatenate([c, rotate(ckr[:, s["R"]:], s["theta"])], axis=-1)


def head(x, ln_out, lm_head, *, s, int8):
    w = lm_head.astype(jnp.float32)
    x = rmsnorm(x, ln_out.astype(jnp.float32), s["eps"])
    if int8:
        w, x = int8_round(w), int8_round(x, -1)
    return jax.nn.log_softmax(x @ w, axis=-1)


# -- one position again, with the choice of experts given ----------------------
#
# The choice of experts is discrete: where a token's k-th and (k+1)-th largest
# selection values lie closer than bfloat16 arithmetic moves them (0.002 a
# layer at these widths, more with depth), a sound bfloat16 program may choose
# another set, and the token's output then differs by a whole expert's (a
# third of a nat here), not by a rounding; the difference carries into the
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


def _rotate_at(x, p, theta):
    """x [..., D] at the one position p; pairs (2i, 2i+1)."""
    D = x.shape[-1]
    ang = p.astype(jnp.float32) / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)], -1).reshape(x.shape)


def leaves_attention(xs, p, rows, lw, *, s):
    """Position p's residuals ``xs`` [leaves, d] (one a choice of experts so
    far) through a layer's attention: each leaf's own row computed from its
    residual, every earlier position's taken from ``rows`` [S, R + rope].
    Written in the absorbed form (the query carried to the latent): the same
    function as ``attention``, for one query position.  The scores against
    ``rows`` are one product for all leaves; the own row's score and its
    share of the weighted sum are put in place of row p's."""
    H, R, nope, rope, v = s["H"], s["R"], s["nope"], s["rope"], s["v"]
    n = xs.shape[0]
    f32 = lambda k: lw[k].astype(jnp.float32)
    a = rmsnorm(xs, f32("ln_attn"), s["eps"])
    q = (a @ f32("wq")).reshape(n, H, nope + rope)
    ckr = a @ f32("w_kva")
    own = jnp.concatenate([rmsnorm(ckr[:, :R], f32("kv_norm"), s["eps"]),
                           _rotate_at(ckr[:, R:], p, s["theta"])], axis=-1)
    w = f32("w_kvb").reshape(R, H, nope + v)
    qq = jnp.concatenate([jnp.einsum("lhn,rhn->lhr", q[..., :nope], w[..., :nope]),
                          _rotate_at(q[..., nope:], p, s["theta"])], axis=-1)
    k_pos = jnp.arange(rows.shape[0])
    sc = jnp.einsum("lhw,sw->lhs", qq, rows)
    sc = jnp.where(k_pos == p, jnp.einsum("lhw,lw->lh", qq, own)[..., None], sc)
    pr = jax.nn.softmax(jnp.where(k_pos <= p, sc / np.sqrt(nope + rope), -jnp.inf),
                        axis=-1)
    o_lat = jnp.einsum("lhs,sr->lhr", pr, rows[:, :R])
    o_lat = o_lat + jnp.take(pr, p, axis=-1)[..., None] * (
        own[:, None, :R] - jnp.take(rows, p, axis=0)[:R])
    o = jnp.einsum("lhr,rhv->lhv", o_lat, w[..., nope:])
    return xs + o.reshape(n, H * v) @ f32("wo")


def row_scores(x, lw, *, s):
    m = rmsnorm(x, lw["ln_mlp"].astype(jnp.float32), s["eps"])
    scores = jax.nn.sigmoid(m @ lw["router"])
    return m, scores, scores + lw["router_bias"]


def leaves_experts(xs, m, scores, union, mask, lw, *, s):
    """xs + the routed experts each leaf chose + the shared experts.  The
    choices are GIVEN, not made here: ``union`` [U] names every expert some
    leaf chose and ``mask`` [leaves, U] says which leaf chose which.  One
    expert at a time over all leaves (its matrices are upcast once), the
    gate (zero where a leaf did not choose it) weighing it: ``experts``'
    loop with a mask, over the leaves of one position."""
    su = jnp.take(scores, union, axis=1) * mask
    w = s["scaling"] * su / jnp.sum(su, axis=1, keepdims=True)

    def one_expert(y, e):
        idx, we = e
        up = lambda k: lw[k][idx].astype(jnp.float32)
        h = jax.nn.silu(m @ up("w_gate")) * (m @ up("w_up"))
        return y + we[:, None] * (h @ up("w_down")), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(xs), (union, w.T))
    ident = lambda t: t
    return xs + y + swiglu(m, lw["ws_gate"], lw["ws_up"], lw["ws_down"], ident, False)


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
    it), so that probes of eight lengths compile three programs."""
    assert precision in ("f32", "int8"), precision
    skey = {k: s[k] for k in ("H", "R", "nope", "rope", "v", "k", "scaling",
                              "eps", "theta")}
    kw = dict(s=skey, int8=precision == "int8")
    layer_j = jax.jit(partial(layer, **kw))
    head_j = jax.jit(partial(head, **kw))
    rows_j = jax.jit(partial(latent_rows, s=skey))
    # the one-position functions over MAX_LEAVES residuals, one at a time
    attn_j = jax.jit(partial(leaves_attention, s=skey))
    scores_j = jax.jit(jax.vmap(partial(row_scores, s=skey), in_axes=(0, None)))
    experts_j = jax.jit(partial(leaves_experts, s=skey))
    dense_j = jax.jit(jax.vmap(partial(row_dense, s=skey), in_axes=(0, None)))
    k = s["k"]

    def embed(params, tokens):
        pad = (-len(tokens)) % QUERY_BLOCK
        ids = jnp.asarray(list(tokens) + [0] * pad, jnp.int32)
        return params["embed"][ids].astype(jnp.float32)

    def forward(params, tokens, n_last):
        S = len(tokens)
        with jax.default_matmul_precision("highest"):
            x = embed(params, tokens)
            for lw in params["layers"]:
                x = layer_j(x, lw)
            return head_j(x[S - n_last:S], params["ln_out"], params["lm_head"])

    def answers(params, tokens, n_last):
        S = len(tokens)
        with jax.default_matmul_precision("highest"):
            x = x0 = embed(params, tokens)
            rows = []
            for lw in params["layers"]:
                rows.append(rows_j(x, lw))
                x = layer_j(x, lw)
            out = []
            for pos in range(S - n_last, S):
                p = jnp.asarray(pos, jnp.int32)
                # MAX_LEAVES residuals side by side (a fixed shape: one
                # program a layer kind); the first ``len(crossed)`` are alive
                xs = jnp.broadcast_to(x0[pos], (MAX_LEAVES, x0.shape[1]))
                crossed = [0.0]        # by how much each leaf broke the order, summed
                for lw, r in zip(params["layers"], rows):
                    xs = attn_j(xs, p, r, lw)
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
