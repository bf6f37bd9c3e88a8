"""The plain reference of the parallel-block decoder with sliding-window and
full layers and one chip's share of its routed experts (``model_type``
``cohere2_moe``): the forward pass in straightforward jax.numpy, float32,
matmuls at "highest" precision, no cache, no paging, no batching, no grouped
kernels.  Written from the equations of the source's config; it imports
nothing of infinistore_tpu.

Per layer, residual ``x``: ``h = LayerNorm(x)`` (mean subtracted, variance +
eps, a weight, no bias), ONCE; ``x <- x + Attn(h) + FFN(h)``.

* ``Attn``: ``q = h W_q`` (H heads of hd), ``k, v = h W_k, h W_v`` (H_kv heads);
  query head i reads key/value head ``i // (H / H_kv)``.  A
  ``sliding_attention`` layer rotates q and k over the whole head, pairs (2i,
  2i+1), and a key at j is visible to a query at i iff ``i - W < j <= i``; a
  ``full_attention`` layer rotates NOTHING and sees ``j <= i``.  Scores over
  ``sqrt(hd)``; ``out = [o_1..o_H] W_o``.
* ``FFN``: ``s = sigmoid(h W_r)`` (float32) over ALL experts of the source;
  the k largest chosen; ``w_e = s_e / sum of the chosen s``.  THE SHARE: the
  weights hold experts ``0 .. E_held - 1`` only; ``y = sum over the chosen e
  that are held of w_e SwiGLU_e(h) + (1 / n_s) sum_s SwiGLU_s(h)``.  What
  the absent experts would add is left out, here as in the program, and the
  partial result goes on to the next layer.
* After the last layer ``LayerNorm``, then ``logit_scale * (x E^T)`` over the
  held slice of the tied embedding.

Departures, each forced by what it is compared with: a layer runs one block of
512 rows at a time (attention one key/value head's group of query heads at a
time), so that a probe of 16,384 + 128 tokens fits beside the weights; the
held experts are a loop with a
MASK (every held expert on every token, the gate zero off the chosen); only
the last ``n_last`` positions go through the head; the choice of experts is
discrete, so the reference gives, beside its own answer, the answer of every
choice within NEAR_TIE of its own, and the comparison takes the nearest ("one
position again", below; the rule and its distance are
``reference/latent_moe.py``'s, PERF.md section 2).

Weights are data, drawn from the seed by ``draw_weights``: the same keys and
draws as the program's ``init_cohere2_moe_params`` (a test holds the two
together), in the type they are served in (bfloat16; the router float32).

``precision="int8"`` is the control: every bfloat16 matrix rounded to int8
per output channel, every matmul input to int8 per token (W8A8), accumulated
exactly; the router stays float32.  It has to come out as not correct.
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


QUERY_BLOCK = 512


def draw_weights(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``li`` from ``split(split(key, L + 1)[li], 9)``: 0 wq, 1 wk,
    2 wv, 3 wo, 4 the router, 5-7 the held experts, ``split([8], 3)`` the
    shared experts; the embedding from ``split(key, L + 1)[L]``; normal /
    sqrt(fan_in)."""
    L, d, H, Hkv, hd = s["L"], s["d"], s["H"], s["Hkv"], s["hd"]
    E, Eh, f, fs = s["E"], s["Eh"], s["f"], s["ns"] * s["f"]

    def dense(key, shape, fan_in, dt=dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def build(key):
        keys = jax.random.split(key, L + 1)
        layers = []
        for li in range(L):
            k = jax.random.split(keys[li], 9)
            ks = jax.random.split(k[8], 3)
            layers.append({
                "wq": dense(k[0], (d, H * hd), d), "wk": dense(k[1], (d, Hkv * hd), d),
                "wv": dense(k[2], (d, Hkv * hd), d),
                "wo": dense(k[3], (H * hd, d), H * hd), "ln": jnp.ones((d,), dtype),
                "router": dense(k[4], (d, E), d, jnp.float32),
                "w_gate": dense(k[5], (Eh, d, f), d), "w_up": dense(k[6], (Eh, d, f), d),
                "w_down": dense(k[7], (Eh, f, d), f),
                "ws_gate": dense(ks[0], (d, fs), d), "ws_up": dense(ks[1], (d, fs), d),
                "ws_down": dense(ks[2], (fs, d), fs)})
        return {"embed": dense(keys[L], (s["V"], d), d), "layers": tuple(layers),
                "ln_out": jnp.ones((d,), dtype)}

    return jax.jit(build)(jax.random.PRNGKey(seed))


def int8_round(w: jax.Array, axis: int = -2) -> jax.Array:
    """Symmetric int8 with one scale per slice along ``axis`` (-2: per output
    channel of a weight; -1: per token of an activation), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale



def layernorm(x, w, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, theta, first=0):
    """x: [S, ..., D] at positions first..first+S-1; pairs (2i, 2i+1)."""
    S, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = (first + jnp.arange(S)).astype(jnp.float32)[:, None] * freqs
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


def keys_values(a, lw, *, s, windowed, int8):
    """The K (rotated in a window layer) and V [S, H_kv, hd] of every
    position of a layer's normalised input a [S, d]."""
    S = a.shape[0]
    k = (a @ _mat(lw["wk"], int8)).reshape(S, s["Hkv"], s["hd"])
    v = (a @ _mat(lw["wv"], int8)).reshape(S, s["Hkv"], s["hd"])
    return (rotate(k, s["theta"]) if windowed else k), v


def attention(a, first, k, v, lw, *, s, windowed, int8):
    """One block of queries: a [B, d] (normalised) at positions first..
    against every position's K and V [S, H_kv, hd] -> [B, d].  One key/value
    head's group of query heads at a time, so that a 16k-token probe's
    scores ([G, B, S] float32) fit beside the weights."""
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    B = a.shape[0]
    H, Hkv, hd = s["H"], s["Hkv"], s["hd"]
    q = (a @ _mat(lw["wq"], int8)).reshape(B, H, hd)
    if windowed:
        q = rotate(q, s["theta"], first)
    q_pos, k_pos = first + jnp.arange(B), jnp.arange(k.shape[0])
    seen = q_pos[:, None] >= k_pos[None, :]
    if windowed:
        seen &= k_pos[None, :] > q_pos[:, None] - s["W"]

    def one_group(args):
        qg, kh, vh = args                       # [G, B, hd] [S, hd] [S, hd]
        sc = jnp.einsum("gbd,sd->gbs", qg, kh) / np.sqrt(hd)
        return jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1) @ vh

    o = jax.lax.map(one_group, (
        q.reshape(B, Hkv, H // Hkv, hd).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))           # [Hkv, G, B, hd]
    o = o.transpose(2, 0, 1, 3).reshape(B, H * hd)
    return act(o) @ _mat(lw["wo"], int8)


def route(a, lw, *, s):
    """Scores over all experts of the source, the k chosen, their weights."""
    scores = jax.nn.sigmoid(a @ lw["router"])                  # float32
    chosen, idx = jax.lax.top_k(scores, s["k"])
    return scores, idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def experts(a, lw, *, s, int8):
    """a [S, d] (normalised) -> the held experts' terms plus the shared
    experts' mean."""
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    scores, idx, w = route(a, lw, s=s)
    gate = jnp.zeros_like(scores).at[jnp.arange(a.shape[0])[:, None], idx].set(w)
    aa = act(a)

    def one_expert(y, e):          # every token through held expert e, masked
        wg, wu, wd, g = e
        return y + g[:, None] * swiglu(aa, wg, wu, wd, act, int8), None

    held = lw["w_gate"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(a),
                        (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T[:held]))
    return y + swiglu(aa, lw["ws_gate"], lw["ws_up"], lw["ws_down"], act,
                      int8) / s["ns"]


def layer(x, lw, *, s, windowed, int8):
    """One parallel block on x [S, d], S a multiple of QUERY_BLOCK: every
    position's K and V first, then QUERY_BLOCK rows at a time through the
    attention and the experts (the arithmetic is a row's own)."""
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    ln = lw["ln"].astype(jnp.float32)
    k, v = keys_values(act(layernorm(x, ln, s["eps"])), lw, s=s,
                       windowed=windowed, int8=int8)

    def one_block(args):
        xb, first = args
        a = layernorm(xb, ln, s["eps"])
        return (xb + attention(act(a), first, k, v, lw, s=s, windowed=windowed,
                               int8=int8) + experts(a, lw, s=s, int8=int8))

    nb = x.shape[0] // QUERY_BLOCK
    return jax.lax.map(one_block, (x.reshape(nb, QUERY_BLOCK, -1),
                                   jnp.arange(nb) * QUERY_BLOCK)).reshape(x.shape)


def layer_rows(x, lw, *, s, windowed):
    a = layernorm(x, lw["ln"].astype(jnp.float32), s["eps"])
    return keys_values(a, lw, s=s, windowed=windowed, int8=False)


def head(x, ln_out, embed, *, s, int8):
    w = embed.astype(jnp.float32).T
    x = layernorm(x, ln_out.astype(jnp.float32), s["eps"])
    if int8:
        w, x = int8_round(w), int8_round(x, -1)
    return jax.nn.log_softmax(s["logit_scale"] * (x @ w), axis=-1)


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




def _rotate_at(x, p, theta):
    """x [..., D] at the one position p; pairs (2i, 2i+1)."""
    D = x.shape[-1]
    ang = p.astype(jnp.float32) / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)], -1).reshape(x.shape)


def leaves_pre(xs, p, rows, lw, *, s, windowed):
    """Position p's residuals ``xs`` [leaves, d] (one a choice of experts so
    far) up to the choice: the normalised input, the attention's output
    (each leaf's own key and value computed from its residual, every earlier
    position's taken from ``rows`` = (K, V) [S, H_kv, hd]) and the scores."""
    H, Hkv, hd = s["H"], s["Hkv"], s["hd"]
    n, G = xs.shape[0], s["H"] // s["Hkv"]
    f32 = lambda k: lw[k].astype(jnp.float32)
    a = layernorm(xs, f32("ln"), s["eps"])
    q = (a @ f32("wq")).reshape(n, Hkv, G, hd)
    k_own = (a @ f32("wk")).reshape(n, Hkv, hd)
    v_own = (a @ f32("wv")).reshape(n, Hkv, hd)
    if windowed:
        q, k_own = _rotate_at(q, p, s["theta"]), _rotate_at(k_own, p, s["theta"])
    K, V = rows
    k_pos = jnp.arange(K.shape[0])
    sc = jnp.einsum("lhgd,shd->lhgs", q, K)
    sc = jnp.where(k_pos == p, jnp.einsum("lhgd,lhd->lhg", q, k_own)[..., None], sc)
    seen = k_pos <= p
    if windowed:
        seen &= k_pos > p - s["W"]
    pr = jax.nn.softmax(jnp.where(seen, sc / np.sqrt(hd), -jnp.inf), axis=-1)
    o = jnp.einsum("lhgs,shd->lhgd", pr, V)
    o = o + jnp.take(pr, p, axis=-1)[..., None] * (
        v_own - jnp.take(V, p, axis=0))[:, :, None, :]
    return a, o.reshape(n, H * hd) @ f32("wo"), jax.nn.sigmoid(a @ lw["router"])


def leaves_post(xs, a, attn, scores, union, mask, lw, *, s):
    """xs + attention + the held experts each leaf chose + the shared mean.
    The choices are GIVEN: ``union`` [U] names every expert some leaf chose
    and ``mask`` [leaves, U] says which leaf chose which; the weights are
    normalised over all a leaf chose, the terms of absent experts left out."""
    su = jnp.take(scores, union, axis=1) * mask
    w = su / jnp.sum(su, axis=1, keepdims=True)
    held = lw["w_gate"].shape[0]
    w = w * (union < held)

    def one_expert(y, e):
        idx, we = e
        up = lambda k: lw[k][jnp.minimum(idx, held - 1)].astype(jnp.float32)
        h = jax.nn.silu(a @ up("w_gate")) * (a @ up("w_up"))
        return y + we[:, None] * (h @ up("w_down")), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(xs), (union, w.T))
    shared = swiglu(a, lw["ws_gate"], lw["ws_up"], lw["ws_down"], lambda t: t, False)
    return xs + attn + y + shared / s["ns"]


def make_forward(s: dict, precision: str = "f32"):
    """``forward(params, tokens, n_last)``: tokens [S] (python ints) ->
    log-probabilities [n_last, V] of the token after each of the last
    ``n_last`` positions, the reference's own choice of experts throughout.
    ``forward.answers(params, tokens, n_last)``: per position, the answers
    [leaves, V] of every choice within a near-tie of the reference's own
    (leaf 0 is its own) and by how much each leaf broke its order; float32
    only.  Tokens are padded on the right to a multiple of QUERY_BLOCK
    (causal: the padding changes nothing before it)."""
    assert precision in ("f32", "int8"), precision
    skey = {k: s[k] for k in ("H", "Hkv", "hd", "k", "ns", "W", "eps", "theta",
                              "logit_scale")}
    int8 = precision == "int8"
    kinds = tuple(bool(w) for w in s["windowed"])
    layer_j = {w: jax.jit(partial(layer, s=skey, windowed=w, int8=int8))
               for w in set(kinds)}
    rows_j = {w: jax.jit(partial(layer_rows, s=skey, windowed=w)) for w in set(kinds)}
    pre_j = {w: jax.jit(partial(leaves_pre, s=skey, windowed=w)) for w in set(kinds)}
    post_j = jax.jit(partial(leaves_post, s=skey))
    head_j = jax.jit(partial(head, s=skey, int8=int8))
    k = s["k"]

    def embed(params, tokens):
        pad = (-len(tokens)) % QUERY_BLOCK
        ids = jnp.asarray(list(tokens) + [0] * pad, jnp.int32)
        return params["embed"][ids].astype(jnp.float32)

    def forward(params, tokens, n_last):
        S = len(tokens)
        with jax.default_matmul_precision("highest"):
            x = embed(params, tokens)
            for lw, w in zip(params["layers"], kinds):
                x = layer_j[w](x, lw)
            return head_j(x[S - n_last:S], params["ln_out"], params["embed"])

    def answers(params, tokens, n_last):
        S = len(tokens)
        with jax.default_matmul_precision("highest"):
            x = x0 = embed(params, tokens)
            rows = []
            for lw, w in zip(params["layers"], kinds):
                rows.append(rows_j[w](x, lw))
                x = layer_j[w](x, lw)
            out = []
            for pos in range(S - n_last, S):
                p = jnp.asarray(pos, jnp.int32)
                # MAX_LEAVES residuals side by side (a fixed shape: one
                # program a layer kind); the first ``len(crossed)`` are alive
                xs = jnp.broadcast_to(x0[pos], (MAX_LEAVES, x0.shape[1]))
                crossed = [0.0]        # by how much each leaf broke the order, summed
                for lw, r, w in zip(params["layers"], rows, kinds):
                    a, attn, scores = pre_j[w](xs, p, r, lw)
                    select = np.asarray(scores)
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
                    xs = post_j(xs[parent], a[parent], attn[parent], scores[parent],
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
