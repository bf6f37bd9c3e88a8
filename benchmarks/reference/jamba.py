"""The plain reference of the Mamba-1 / attention decoder (``model_type``
``jamba`` at ``num_experts`` 1): the forward pass in straightforward jax.numpy,
float32, matmuls at "highest" precision, no cache, no chunks, no paging, no
batching, no kernel.  Written from the row's ``config`` and the family's
published modelling code (``JambaMambaMixer``, ``JambaAttentionDecoderLayer``);
it imports nothing of infinistore_tpu.

A block is ``x = x + Mixer(RMSNorm(x))`` then ``x = x + FFN(RMSNorm(x))``:

* Mamba layers (``i % attn_layer_period != attn_layer_offset``): ``[x | z] = h
  W_in``; ``x = SiLU(sum_{j<K} w[:, j] x_{t-(K-1)+j} + b)`` written as that sum
  over the WHOLE sequence (``x`` before its start is zero); ``[r | B | C] = x
  W_x``, each through an RMSNorm with a learned weight; ``dt = softplus(r W_dt +
  b_dt)``; ``A = -exp(A_log)``; then the recurrence TOKEN BY TOKEN over the
  whole sequence, the state ``[d_inner, d_state]`` as published: ``s_t = exp(dt_t
  A) s_{t-1} + dt_t x_t B_t``, ``y_t = s_t C_t + D x_t``; ``out = (y * SiLU(z))
  W_out``.  No state is carried in or out: the program's slots are held to this.
* attention layers: 20 query heads over the key/value heads, no bias, NO
  position embedding, causal softmax at ``1 / sqrt(head)``, ``W_o``.
* every layer's FFN one SwiGLU; one more RMSNorm, then the TIED embedding.

Departures from the published code, each forced by what it is compared with:

* Attention runs one key/value head and one block of 512 queries at a time,
  so a 16k-token probe's scores fit; the arithmetic is unchanged.
* The position-wise parts (a layer's FFN) run in blocks of 2,048 tokens and
  the sequence is padded on the right (causal: the padding changes nothing
  before it) to 2,048 past a multiple of 8,192, so that two programs a layer
  kind serve every probe of a mix of 8-16k documents with tails.
* Only the last ``n_last`` positions go through the head.
* ``A_log`` and ``b_dt`` are SEEDED AS THE FAMILY INITIALISES THEM (``A[c, n] =
  -(n + 1)``; ``b_dt`` the inverse softplus of a step drawn log-uniformly in
  [0.001, 0.1]; ``W_dt`` normal x 0.5 / sqrt(dt_rank)): drawn like another
  weight, every channel would forget within two tokens and no check could tell
  a loaded checkpoint from zeros.

Weights are data, drawn from the seed by ``draw_weights``: the same keys and
the same draws as the program's ``init_jamba_params`` (a test holds the two
together), in the type they are served in (bfloat16; ``b_dt``, ``A_log`` and ``D``
float32), upcast one layer at a time.

Two named precisions below float32, the controls that ``correct`` must refuse:

* ``"int8"``: every bfloat16 matrix rounded to int8 per output channel and
  every matmul input rounded to int8 per token (W8A8, dynamic scales),
  accumulated exactly; the conv's taps, the norms and the recurrence float32.
* ``"statebf16"``: the recurrence's state held in bfloat16 between tokens (one
  precision below the float32 the configuration states), everything else as
  the float32 reference.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
FFN_BLOCK = 2048
# a long probe is padded to a document of DOC_PAD's multiple and TAIL_PAD (a
# document of 8, 12 or 16 thousand tokens and its tail: two shapes), one of
# at most TAIL_PAD tokens to a multiple of PAD_SHORT: few programs to compile
DOC_PAD, TAIL_PAD, PAD_SHORT = 8192, 2048, 256
DT_MIN, DT_MAX = 0.001, 0.1
DT_PROJ_STD = 0.5


def draw_weights(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``li`` from ``split(split(key, L + 1)[li], 9)``: a Mamba mixer 0
    w_in, 1 conv_w (fan-in K), 2 w_x, 3 w_dt (normal x 0.5 / sqrt(dt_rank)), 4
    the step (log-uniform in [0.001, 0.1]; b_dt its inverse softplus), 8 w_out;
    an attention mixer 0-3 wq wk wv wo; 5-7 the SwiGLU; the embedding from
    ``split(key, L + 1)[L]``; normal / sqrt(fan_in) but w_dt and b_dt; A_log =
    log(n + 1), D ones, the conv's bias zeros, every norm ones."""
    L, d, hd, di = s["L"], s["d"], s["hd"], s["di"]
    N, K, R, f = s["N"], s["K"], s["R"], s["f"]
    nq, nkv = s["H"] * hd, s["kv"] * hd
    f32 = jnp.float32

    def dense(key, shape, fan_in, std=1.0):
        return (jax.random.normal(key, shape, f32) * (std / np.sqrt(fan_in))
                ).astype(dtype)

    def common(k):
        return {"ln_attn": jnp.ones((d,), dtype), "ln_mlp": jnp.ones((d,), dtype),
                "w_gate": dense(k[5], (d, f), d), "w_up": dense(k[6], (d, f), d),
                "w_down": dense(k[7], (f, d), f)}

    @jax.jit
    def mamba(key):
        k = jax.random.split(key, 9)
        step = jnp.exp(jax.random.uniform(k[4], (di,), f32)
                       * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN))
        return common(k) | dict(
            w_in=dense(k[0], (d, 2 * di), d),
            conv_w=dense(k[1], (di, K), K),
            conv_b=jnp.zeros((di,), dtype),
            w_x=dense(k[2], (di, R + 2 * N), di),
            dt_norm=jnp.ones((R,), dtype), b_norm=jnp.ones((N,), dtype),
            c_norm=jnp.ones((N,), dtype),
            w_dt=dense(k[3], (R, di), R, std=DT_PROJ_STD),
            b_dt=step + jnp.log(-jnp.expm1(-step)),
            # as published: [d_inner, d_state]
            a_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=f32)), (di, N)),
            d_skip=jnp.ones((di,), f32),
            w_out=dense(k[8], (di, d), di))

    @jax.jit
    def attention(key):
        k = jax.random.split(key, 9)
        return common(k) | dict(
            wq=dense(k[0], (d, nq), d), wk=dense(k[1], (d, nkv), d),
            wv=dense(k[2], (d, nkv), d), wo=dense(k[3], (nq, d), nq))

    # one layer at a time: two small programs, not one of every layer's draws
    keys = jax.random.split(jax.random.PRNGKey(seed), L + 1)
    layers = tuple((mamba if s["types"][li] == "mamba" else attention)(keys[li])
                   for li in range(L))
    return {"embed": jax.jit(lambda k: dense(k, (s["V"], d), d))(keys[L]),
            "layers": layers, "ln_out": jnp.ones((d,), dtype)}


def int8_round(w: jax.Array, axis: int = -2) -> jax.Array:
    """Symmetric int8 with one scale per slice along ``axis`` (-2: per output
    channel of a weight; -1: per token of an activation), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mat(w, int8):
    w = w.astype(jnp.float32)
    return int8_round(w) if int8 else w


def _act(int8):
    return (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)


def silu(x):
    return x * jax.nn.sigmoid(x)


def mamba_op(a, lw, *, s, precision):
    """The Mamba mixer over the whole sequence a [S, d] (normalised)."""
    int8 = precision == "int8"
    act = _act(int8)
    S = a.shape[0]
    di, N, R, K = s["di"], s["N"], s["R"], s["K"]
    f32 = lambda k: lw[k].astype(jnp.float32)
    xz = act(a) @ _mat(lw["w_in"], int8)
    x, z = xz[:, :di], xz[:, di:]
    w = f32("conv_w")                                           # [d_inner, K]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))                       # zeros before the start
    x = silu(sum(w[:, j] * xp[j: j + S] for j in range(K)) + f32("conv_b"))
    rbc = act(x) @ _mat(lw["w_x"], int8)
    r = rmsnorm(rbc[:, :R], f32("dt_norm"), s["eps"])
    B = rmsnorm(rbc[:, R: R + N], f32("b_norm"), s["eps"])
    C = rmsnorm(rbc[:, R + N:], f32("c_norm"), s["eps"])
    dt = jax.nn.softplus(act(r) @ _mat(lw["w_dt"], int8) + lw["b_dt"])
    A = -jnp.exp(lw["a_log"])                                   # [d_inner, N]
    held = jnp.bfloat16 if precision == "statebf16" else jnp.float32

    def token(st, t):
        x_t, dt_t, B_t, C_t = t
        st = (jnp.exp(dt_t[:, None] * A) * st.astype(jnp.float32)
              + (dt_t * x_t)[:, None] * B_t[None, :])
        st = st.astype(held)
        return st, st.astype(jnp.float32) @ C_t

    _, y = jax.lax.scan(token, jnp.zeros((di, N), held), (x, dt, B, C))
    y = y + lw["d_skip"] * x
    return act(y * silu(z)) @ _mat(lw["w_out"], int8)


def attention_op(a, lw, *, s, precision):
    int8 = precision == "int8"
    act = _act(int8)
    S = a.shape[0]
    H, kv, hd = s["H"], s["kv"], s["hd"]
    G = H // kv
    a = act(a)
    q = (a @ _mat(lw["wq"], int8)).reshape(S, H, hd)
    k = (a @ _mat(lw["wk"], int8)).reshape(S, kv, hd)
    v = (a @ _mat(lw["wv"], int8)).reshape(S, kv, hd)
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
    return act(o) @ _mat(lw["wo"], int8)


def op(x, lw, *, s, precision):
    """x + Mixer(RMSNorm(x)) over the WHOLE sequence x [S, d]; which mixer a
    layer holds is its leaves' structure."""
    a = rmsnorm(x, lw["ln_attn"].astype(jnp.float32), s["eps"])
    mixer = mamba_op if "w_in" in lw else attention_op
    return x + mixer(a, lw, s=s, precision=precision)


def ffn(x, lw, *, s, precision):
    """A layer's FFN on a block of positions x [n, d]."""
    int8 = precision == "int8"
    act = _act(int8)
    m = act(rmsnorm(x, lw["ln_mlp"].astype(jnp.float32), s["eps"]))
    g, u = m @ _mat(lw["w_gate"], int8), m @ _mat(lw["w_up"], int8)
    return x + act(silu(g) * u) @ _mat(lw["w_down"], int8)


def layer(x, lw, *, s, precision="f32"):
    """One decoder layer on x [S, d], whole: the tests' form."""
    return ffn(op(x, lw, s=s, precision=precision), lw, s=s, precision=precision)


def head(x, ln_out, embed, *, s, precision):
    w = embed.astype(jnp.float32).T
    x = rmsnorm(x, ln_out.astype(jnp.float32), s["eps"])
    if precision == "int8":
        w, x = int8_round(w), int8_round(x, -1)
    return jax.nn.log_softmax(x @ w, axis=-1)


def make_forward(s: dict, precision: str = "f32"):
    """``forward(params, tokens, n_last)``: tokens [S] (python ints) ->
    log-probabilities [n_last, V] of the token after each of the last
    ``n_last`` positions."""
    assert precision in ("f32", "int8", "statebf16"), precision
    skey = {k: s[k] for k in ("d", "H", "kv", "hd", "di", "N", "K", "R", "eps")}
    kw = dict(s=skey, precision=precision)
    op_j = jax.jit(partial(op, **kw))
    ffn_j = jax.jit(partial(ffn, **kw))
    head_j = jax.jit(partial(head, **kw))

    def forward(params, tokens, n_last):
        S = len(tokens)
        if S <= TAIL_PAD:
            pad = (-S) % PAD_SHORT
        else:
            pad = -(-(S - TAIL_PAD) // DOC_PAD) * DOC_PAD + TAIL_PAD - S
        ids = jnp.asarray(list(tokens) + [0] * pad, jnp.int32)
        with jax.default_matmul_precision("highest"):
            x = params["embed"][ids].astype(jnp.float32)
            block = min(FFN_BLOCK, x.shape[0])
            for lw in params["layers"]:
                x = op_j(x, lw)
                x = jnp.concatenate([ffn_j(x[i: i + block], lw)
                                     for i in range(0, x.shape[0], block)])
            return head_j(x[S - n_last: S], params["ln_out"], params["embed"])

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
    ``reference_logprobs``.  The statistic is the RMS, over every top-k id of
    every position, of the system's log-probability minus the reference's for
    the same token; and, apart, how many chosen tokens are not among the
    reference's top 5 (sampled tokens are never compared for equality).  As
    ``reference/dense.py`` defines it: nothing is routed, so no near-tie is
    forgiven."""
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
