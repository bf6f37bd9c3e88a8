"""The plain reference: a dense decoder's forward pass in straightforward
jax.numpy, float32, matmuls at "highest" precision, no cache, no paging, no
batching.  Written from the published descriptions (RMSNorm, rotary
embeddings with theta 1e6, grouped-query attention, SwiGLU, Qwen3's per-head
RMSNorm on Q and K before the rotation, Qwen2.5's QKV biases); it shares no
forward code with infinistore_tpu/models/llama.py.

Departures from the papers, each forced by what it is compared with:

* Rotary pairs are (2i, 2i+1), RoFormer's own pairing and the column order
  the program's weights are drawn in.  Hugging Face checkpoints pair (i,
  i + D/2); that is the same function after a fixed permutation of each
  head's columns, which random weights do not need.
* Attention runs one KV head (with its group of query heads) at a time, so
  an 8k-token probe's scores fit the chip; the arithmetic is unchanged.
* Only the last ``n_last`` positions go through the lm_head.

Weights are data, drawn from the seed by ``draw_weights``: the same keys and
the same draws as the program's ``init_params`` (a test holds the two
together), in the type they are served in (bfloat16), upcast one layer at a
time.  Nothing the server computed enters here.

``precision="int8"`` is the control of "How correct is decided": the same
reference computed in int8, the nearest precision below the bfloat16 the
configurations state, as the chip's int8 matrix unit would be used: every
matrix rounded to int8 per output channel and every matmul input rounded to
int8 per token (W8A8, dynamic scales), accumulated exactly.  It has to come
out as not correct.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def draw_weights(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Stacked leaves [L, ...] drawn as the program draws them: layer ``li``
    from ``split(split(key, L + 2)[li], 10)``, normal / sqrt(fan_in)."""
    L, d, hd, f, V = s["L"], s["d"], s["hd"], s["f"], s["V"]
    nq, nkv = s["h"] * hd, s["kv"] * hd

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

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
            "ln_attn": jnp.ones((L, d), dtype),
            "ln_mlp": jnp.ones((L, d), dtype),
        }
        if s["bias"]:
            layers["bq"] = stacked(7, (nq,), d)
            layers["bk"] = stacked(8, (nkv,), d)
            layers["bv"] = stacked(9, (nkv,), d)
        if s["qk_norm"]:
            layers["q_norm"] = jnp.ones((L, hd), dtype)
            layers["k_norm"] = jnp.ones((L, hd), dtype)
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
    """x: [S, H, D] at positions 0..S-1; pairs (2i, 2i+1)."""
    S, _, D = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def layer(x, lw, *, s, int8):
    """One decoder layer on x [S, d]; ``lw`` is that layer's leaves in the
    served type, upcast here."""
    w = {k: v.astype(jnp.float32) for k, v in lw.items()}
    if int8:
        w.update({k: int8_round(w[k]) for k in MATRICES})
    act = (lambda t: int8_round(t, -1)) if int8 else (lambda t: t)
    S = x.shape[0]
    h, kv, hd = s["h"], s["kv"], s["hd"]
    a = act(rmsnorm(x, w["ln_attn"], s["eps"]))
    q, k, v = a @ w["wq"], a @ w["wk"], a @ w["wv"]
    if s["bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k, v = q.reshape(S, h, hd), k.reshape(S, kv, hd), v.reshape(S, kv, hd)
    if s["qk_norm"]:
        q = rmsnorm(q, w["q_norm"], s["eps"])
        k = rmsnorm(k, w["k_norm"], s["eps"])
    q, k = rotate(q, s["theta"]), rotate(k, s["theta"])
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_group(args):  # the query heads that share one KV head
        qg, kg, vg = args                       # [S, g, D], [S, D], [S, D]
        sc = jnp.einsum("sgd,td->gst", qg, kg) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", p, vg)

    qg = q.reshape(S, kv, h // kv, hd).transpose(1, 0, 2, 3)
    o = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    x = x + act(o.transpose(1, 0, 2, 3).reshape(S, h * hd)) @ w["wo"]
    m = act(rmsnorm(x, w["ln_mlp"], s["eps"]))
    return x + act(jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


def head(x, ln_out, lm_head, *, s, int8):
    w = lm_head.astype(jnp.float32)
    x = rmsnorm(x, ln_out.astype(jnp.float32), s["eps"])
    if int8:
        w, x = int8_round(w), int8_round(x, -1)
    logits = x @ w
    return jax.nn.log_softmax(logits, axis=-1)


def make_forward(s: dict, precision: str = "f32"):
    """tokens [S] (python ints) -> log-probabilities [n_last, V] of the token
    after each of the last ``n_last`` positions.  One small program per layer
    shape, run L times."""
    assert precision in ("f32", "int8"), precision
    skey = {k: s[k] for k in ("h", "kv", "hd", "eps", "theta", "bias", "qk_norm")}
    kw = dict(s=skey, int8=precision == "int8")
    layer_j = jax.jit(partial(layer, **kw))
    head_j = jax.jit(partial(head, **kw))

    def forward(params, tokens, n_last):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
            for li in range(s["L"]):
                lw = {k: v[li] for k, v in params["layers"].items()}
                x = layer_j(x, lw)
            return head_j(x[-n_last:], params["ln_out"], params["lm_head"])

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
    reference's top 5 (sampled tokens are never compared for equality)."""
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
