"""The plain reference of the power-retention decoder (``model_type``
``brumby``): the ATTENTION form in straightforward jax.numpy, float32, matmuls
at "highest" precision, no state, no chunks, no cache, no batching.  It shares
no code with infinistore_tpu/models/retention.py, which runs the RECURRENT
form: two algorithms for one function, which is the point of the comparison.

Per layer, residual ``x``, position ``t``, query head ``a`` in the group of
key/value head ``c``:

* ``h = RMSNorm(x)``; ``q = RMSNorm_head(h W_q)``, ``k = RMSNorm_head(h W_k)``,
  ``v = h W_v``; rotary embedding on q and k, theta 1e6, pairs (2i, 2i+1) (the
  column order the program's weights are drawn in; Hugging Face pairs (i,
  i + D/2), the same function after a fixed permutation of a head's columns);
* the gate, one per key/value head: ``log g_t = logsigmoid(h_t W_g + b_g)``,
  ``G_t = sum_{s<=t} log g_s``;
* ``w_tj = exp(G_t - G_j) (q_t . k_j)^2`` for ``j <= t``;
  ``y_t = sum_j w_tj v_j / sum_j w_tj`` (no scale on ``q . k``: it cancels);
* ``x <- x + concat(y) W_o``; ``x <- x + SwiGLU(RMSNorm(x))``; after the last
  layer RMSNorm and the untied head.

Queries go through in blocks of ``QUERY_BLOCK`` so that a 16k-token probe's
weights [group, block, S] fit the chip; the arithmetic is unchanged.

Two named precisions below it, the controls that `correct` must refuse:

* ``"int8"``: every matrix rounded to int8 per output channel and every
  matmul input per token (W8A8), as reference/dense.py has it;
* ``"statebf16"``: the RECURRENT form written out token by token, ``S_t = g_t
  S_{t-1} + phi(k_t) v_t^T`` and ``z_t`` held and accumulated in bfloat16 (the
  state one precision below the float32 the configuration states), everything
  else as ``"f32"``.

Weights are data, drawn from the seed by ``draw_weights`` with the same keys
and draws as the program's ``init_retention_params`` (a test holds the two
together), the seeded gate included: ``b_g`` is set so that a head forgets
over ``-1 / log g`` = 64 x 256^u tokens, u uniform.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "wg")
QUERY_BLOCK = 512
LENGTH_BUCKET = 2048
GATE_HORIZON = (64.0, 16384.0)
GATE_W_STD = 0.25


def draw_weights(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Stacked leaves [L, ...] drawn as the program draws them: layer ``li``
    from ``split(split(key, L + 2)[li], 10)``; 7 is the gate's matrix
    (float32, normal x 0.25 / sqrt(d)), 8 its bias."""
    L, d, hd, f, V, kv = s["L"], s["d"], s["hd"], s["f"], s["V"], s["kv"]
    nq, nkv = s["h"] * hd, kv * hd
    lo, hi = GATE_HORIZON

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def bias(key):
        tau = lo * (hi / lo) ** jax.random.uniform(key, (kv,), jnp.float32)
        return -jnp.log(jnp.expm1(1.0 / tau))

    def build(key):
        keys = jax.random.split(key, L + 2)
        lk = jax.vmap(lambda k: jax.random.split(k, 10))(keys[:L])

        def stacked(i, shape, fan_in):
            return jax.vmap(lambda k: dense(k, shape, fan_in))(lk[:, i])

        layers = {
            "wq": stacked(0, (d, nq), d), "wk": stacked(1, (d, nkv), d),
            "wv": stacked(2, (d, nkv), d), "wo": stacked(3, (nq, d), nq),
            "w_gate": stacked(4, (d, f), d), "w_up": stacked(5, (d, f), d),
            "w_down": stacked(6, (f, d), f),
            "wg": jax.vmap(lambda k: jax.random.normal(k, (d, kv), jnp.float32)
                           * (GATE_W_STD / np.sqrt(d)))(lk[:, 7]),
            "bg": jax.vmap(bias)(lk[:, 8]),
            "ln_attn": jnp.ones((L, d), dtype), "ln_mlp": jnp.ones((L, d), dtype),
            "q_norm": jnp.ones((L, hd), dtype), "k_norm": jnp.ones((L, hd), dtype),
        }
        return {"embed": dense(keys[-2], (V, d), d), "layers": layers,
                "ln_out": jnp.ones((d,), dtype),
                "lm_head": dense(keys[-1], (d, V), d)}

    return jax.jit(build)(jax.random.PRNGKey(seed))


def int8_round(w: jax.Array, axis: int = -2) -> jax.Array:
    """Symmetric int8 with one scale per slice along ``axis`` (-2: per output
    channel of a weight; -1: per token of an activation), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, theta):
    """x: [S, H, D] at positions 0..S-1; pairs (2i, 2i+1): ``(a, b) -> (a cos -
    b sin, b cos + a sin)``.  The partner of each column comes from a product
    with a signed permutation (exact), not from a split into [..., D/2, 2]: a
    trailing axis of 2 is laid out 64 times its size on the chip."""
    S, _, D = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.repeat(jnp.arange(S, dtype=jnp.float32)[:, None] * freqs, 2, axis=-1)
    swap = np.zeros((D, D), np.float32)
    swap[np.arange(1, D, 2), np.arange(0, D, 2)] = -1.0     # out[2i] = -x[2i+1]
    swap[np.arange(0, D, 2), np.arange(1, D, 2)] = 1.0      # out[2i+1] = x[2i]
    return x * jnp.cos(ang)[:, None, :] + (x @ swap) * jnp.sin(ang)[:, None, :]


def attention_form(q, k, v, G):
    """One key/value head: q [S, g, D], k, v [S, D], G [S] the running sum of
    log g.  ``y_t = sum_{j<=t} w_tj v_j / sum_{j<=t} w_tj`` with ``w_tj =
    exp(G_t - G_j) (q_t . k_j)^2``, a block of queries at a time."""
    S = k.shape[0]
    j = jnp.arange(S)

    def block(args):
        qb, Gb, tb = args               # [B, g, D], [B], [B] (positions)
        sc = jnp.einsum("tgd,jd->gtj", qb, k)
        seen = j[None, :] <= tb[:, None]
        w = sc * sc * jnp.exp(jnp.where(seen, Gb[:, None] - G[None, :], -jnp.inf))
        return jnp.einsum("gtj,jd->tgd", w, v) / jnp.sum(w, -1).T[..., None]

    nb = S // QUERY_BLOCK
    y = jax.lax.map(block, (q.reshape((nb, QUERY_BLOCK) + q.shape[1:]),
                            G.reshape(nb, QUERY_BLOCK), j.reshape(nb, QUERY_BLOCK)))
    return y.reshape(q.shape)


def symmetric_square(u):
    """phi(u) [..., D (D + 1) / 2]: u_a u_b over a <= b, the off-diagonal
    pairs times sqrt 2, so that phi(q) . phi(k) = (q . k)^2."""
    a, b = np.triu_indices(u.shape[-1])
    return u[..., a] * u[..., b] * jnp.asarray(np.where(a == b, 1.0, np.sqrt(2.0)),
                                               jnp.float32)


def recurrent_form_bf16(q, k, v, logg):
    """Every key/value head, token by token, the state in bfloat16: q [S, kv,
    g, D], k, v [S, kv, D], logg [S, kv]."""
    kv, D = k.shape[1], k.shape[2]
    F = D * (D + 1) // 2

    def step(carry, x):
        S, z = carry
        qt, kt, vt, lg = x
        pk, g = symmetric_square(kt), jnp.exp(lg)
        S = (g[:, None, None] * S.astype(jnp.float32)
             + pk[..., None] * vt[:, None, :]).astype(jnp.bfloat16)
        z = (g[:, None] * z.astype(jnp.float32) + pk).astype(jnp.bfloat16)
        pq = symmetric_square(qt)
        num = jnp.einsum("hgf,hfd->hgd", pq, S.astype(jnp.float32))
        den = jnp.einsum("hgf,hf->hg", pq, z.astype(jnp.float32))
        return (S, z), num / den[..., None]

    # a block of queries at a time, so that what the compiler computes ahead
    # of the steps (it takes phi of every token out of the loop: 0.6 MB a
    # token) is a block's worth
    def block(carry, xs):
        return jax.lax.scan(step, carry, xs)

    init = (jnp.zeros((kv, F, D), jnp.bfloat16), jnp.zeros((kv, F), jnp.bfloat16))
    blocks = jax.tree.map(
        lambda a: a.reshape((-1, QUERY_BLOCK) + a.shape[1:]), (q, k, v, logg))
    return jax.lax.scan(block, init, blocks)[1].reshape(q.shape)    # [S, kv, g, D]


def layer(x, lw, *, s, precision):
    """One decoder layer on x [S, d], S a multiple of QUERY_BLOCK; ``lw`` is
    that layer's leaves in the served type, upcast here."""
    int8 = precision == "int8"
    w = {k: v.astype(jnp.float32) for k, v in lw.items()}
    if int8:
        w.update({k: int8_round(w[k]) for k in MATRICES})
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    S = x.shape[0]
    h, kv, hd = s["h"], s["kv"], s["hd"]
    a = act(rmsnorm(x, w["ln_attn"], s["eps"]))
    q = rmsnorm((a @ w["wq"]).reshape(S, h, hd), w["q_norm"], s["eps"])
    k = rmsnorm((a @ w["wk"]).reshape(S, kv, hd), w["k_norm"], s["eps"])
    v = (a @ w["wv"]).reshape(S, kv, hd)
    q, k = rotate(q, s["theta"]), rotate(k, s["theta"])
    logg = jax.nn.log_sigmoid(a @ w["wg"] + w["bg"])            # [S, kv]
    qg = q.reshape(S, kv, h // kv, hd)
    if precision == "statebf16":
        o = recurrent_form_bf16(qg, k, v, logg)
    else:
        o = jax.lax.map(lambda args: attention_form(*args), (
            qg.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            jnp.cumsum(logg, axis=0).T)).transpose(1, 0, 2, 3)
    x = x + act(o.reshape(S, h * hd)) @ w["wo"]

    def mlp(xb):
        m = act(rmsnorm(xb, w["ln_mlp"], s["eps"]))
        return xb + act(jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]

    return jax.lax.map(mlp, x.reshape(S // QUERY_BLOCK, QUERY_BLOCK, -1)).reshape(x.shape)


def head(x, ln_out, lm_head, *, s, precision):
    w = lm_head.astype(jnp.float32)
    x = rmsnorm(x, ln_out.astype(jnp.float32), s["eps"])
    if precision == "int8":
        w, x = int8_round(w), int8_round(x, -1)
    return jax.nn.log_softmax(x @ w, axis=-1)


def make_forward(s: dict, precision: str = "f32"):
    """tokens [S] (python ints) -> log-probabilities [n_last, V] of the token
    after each of the last ``n_last`` positions.  The tokens are padded
    behind to a whole number of query blocks, long ones to a multiple of
    ``LENGTH_BUCKET`` so that eight probes of eight lengths compile three
    programs and not eight (a pad is after every real position, so no real
    position attends to it)."""
    assert precision in ("f32", "int8", "statebf16"), precision
    skey = {k: s[k] for k in ("h", "kv", "hd", "eps", "theta")}
    layer_j = jax.jit(partial(layer, s=skey, precision=precision))
    head_j = jax.jit(partial(head, s=skey, precision=precision))

    def forward(params, tokens, n_last):
        n = len(tokens)
        padded = list(tokens) + [0] * (-n % (LENGTH_BUCKET if n > LENGTH_BUCKET
                                             else QUERY_BLOCK))
        with jax.default_matmul_precision("highest"):
            x = params["embed"][jnp.asarray(padded, jnp.int32)].astype(jnp.float32)
            for li in range(s["L"]):
                x = layer_j(x, {k: v[li] for k, v in params["layers"].items()})
            return head_j(x[n - n_last:n], params["ln_out"], params["lm_head"])

    return forward


def reference_logprobs(forward, params, probes):
    """For each probe, the reference's log-probabilities [n, V] at the n
    generated positions, the prompt plus the tokens the server chose being
    given (teacher-forced)."""
    return [np.asarray(forward(params, list(p["prompt"]) + list(p["ids"][:-1]),
                               len(p["ids"]))) for p in probes]


def compare(answers, ref_lps) -> dict:
    """``answers``: per probe {"ids": [chosen], "top": [{id: lp} per
    position]} as the system under test gave them; ``ref_lps`` from
    ``reference_logprobs``.  The RMS, over every top-k id of every position,
    of the system's log-probability minus the reference's for the same token;
    and, apart, how many chosen tokens are not among the reference's top 5."""
    diffs, misses, rows = [], 0, []
    for ans, lp in zip(answers, ref_lps):
        d_probe = []
        for pos, top in enumerate(ans["top"]):
            if int(ans["ids"][pos]) not in set(np.argsort(lp[pos])[-5:].tolist()):
                misses += 1
            d_probe += [float(v) - float(lp[pos, int(t)]) for t, v in top.items()]
        diffs += d_probe
        rows.append(float(np.sqrt(np.mean(np.square(d_probe)))))
    return {"n_values": len(diffs),
            "rms": float(np.sqrt(np.mean(np.square(diffs)))),
            "max_abs": float(np.max(np.abs(diffs))),
            "chosen_not_in_ref_top5": misses, "per_probe_rms": rows}


def control_answers(low_lps, answers):
    """The control's answers: the lower-precision reference put in the
    program's place, read at the same token ids."""
    out = []
    for lp, ans in zip(low_lps, answers):
        out.append({"ids": [int(np.argmax(lp[pos])) for pos in range(len(ans["ids"]))],
                    "top": [{t: float(lp[pos, int(t)]) for t in top}
                            for pos, top in enumerate(ans["top"])]})
    return out
