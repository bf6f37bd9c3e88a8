"""Prefill attention (``causal_attention``) against a plain float32 loop over
heads, in its three forms: a whole prompt, a chunk at a static ``q_offset``
over its prefix, and a chunk over a padded prefix buffer of which
``prefix_len`` rows are valid.  The reference below shares no code with
``models/attention.py``: it builds each form's mask from the positions and
pairs query head ``h`` with KV head ``h // G`` by indexing.

And the guard that keeps a second attention path out of the package.
"""

import ast
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.models.attention import causal_attention

B, D = 2, 16
SQ, OFFSET, PAD, PLEN, WINDOW = 12, 20, 32, 19, 7
HEADS = [(8, 4), (4, 7), (2, 1)]
FORMS = ["whole", "chunk", "padded"]
# bfloat16: the program rounds the scores and the probabilities to bf16 and
# the float32 reference does not; a wrong pairing or mask is off by 0.3-1
TOL = {"float32": dict(rtol=2e-5, atol=2e-5),
       "bfloat16": dict(rtol=2**-5, atol=2**-5)}


def _form(form, sq, window):
    """(number of key rows, kwargs for causal_attention, mask [sq, sk])."""
    i = np.arange(sq)[:, None]
    if form == "whole":
        sk, kw = sq, {}
        q_pos, k_pos = i, np.arange(sk)[None, :]
        mask = k_pos <= q_pos
    elif form == "chunk":
        sk, kw = OFFSET + sq, {"q_offset": OFFSET}
        q_pos, k_pos = OFFSET + i, np.arange(sk)[None, :]
        mask = k_pos <= q_pos
    else:
        sk = PAD + sq
        kw = {"q_offset": PAD, "prefix_pad": PAD,
              "prefix_len": jnp.asarray(PLEN, jnp.int32)}
        row = np.arange(sk)[None, :]
        # a buffer row sits at its own index; the chunk's rows follow the
        # VALID prefix, so the slack between PLEN and PAD has no position
        q_pos = PLEN + i
        k_pos = np.where(row < PAD, row, PLEN + row - PAD)
        mask = np.where(row < PAD, row < PLEN, row - PAD <= i)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    return sk, kw, mask


def _reference(q, k, v, mask, softcap):
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    H, G = q.shape[2], q.shape[2] // k.shape[2]
    out = np.zeros(q.shape[:3] + (v.shape[-1],), np.float32)
    for b in range(q.shape[0]):
        for h in range(H):
            s = q[b, :, h] @ k[b, :, h // G].T / np.sqrt(q.shape[-1])
            if softcap is not None:
                s = softcap * np.tanh(s / softcap)
            s = np.where(mask, s, -np.inf)
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            out[b, :, h] = (p / p.sum(axis=-1, keepdims=True)) @ v[b, :, h // G]
    return out


CASES = [
    pytest.param(hkv, g, dtype, form, SQ, None, None,
                 id=f"kv{hkv}x{g}-{dtype}-{form}")
    for hkv, g in HEADS for dtype in ("float32", "bfloat16") for form in FORMS
] + [
    pytest.param(4, 7, dtype, form, SQ, WINDOW, None,
                 id=f"kv4x7-{dtype}-{form}-window")
    for dtype in ("float32", "bfloat16") for form in ("chunk", "padded")
] + [
    pytest.param(4, 7, "float32", form, SQ, None, 5.0,
                 id=f"kv4x7-float32-{form}-softcap") for form in FORMS
] + [
    pytest.param(4, 7, "float32", form, 1, None, None,
                 id=f"kv4x7-float32-{form}-one-row") for form in FORMS
]


@pytest.mark.parametrize("hkv,g,dtype,form,sq,window,softcap", CASES)
def test_causal_attention_matches_float32_reference(hkv, g, dtype, form, sq,
                                                    window, softcap):
    sk, kw, mask = _form(form, sq, window)
    rng = np.random.default_rng(hkv * 100 + g * 10 + FORMS.index(form))
    q = jnp.asarray(rng.standard_normal((B, sq, hkv * g, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, sk, hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, sk, hkv, D)), dtype)
    got = causal_attention(q, k, v, window=window, softcap=softcap, **kw)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _reference(q, k, v, mask, softcap)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **TOL[dtype])


def test_the_package_has_one_attention_path():
    """No switch, mesh argument or engine option selects another attention
    implementation; ``use_pallas`` is a parameter of the two dense forwards
    (two files under benchmarks/ still pass it) and nothing reads it.  The
    two hand-written attention kernels (``paged_decode_kernel.py``,
    ``chunk_attention_kernel.py``) are chosen by shapes when a TPU's program
    is lowered, one attention a program."""
    pkg = pathlib.Path(__file__).resolve().parents[1] / "infinistore_tpu"
    takes, reads = [], []
    for path in sorted(pkg.rglob("*.py")):
        text = path.read_text()
        for word in ("PALLAS", "tp_mesh", "pallas_tp", "allow_pallas"):
            assert word not in text, f"{word} in {path}"
        for node in ast.walk(ast.parse(text)):
            if isinstance(node, ast.FunctionDef):
                a = node.args
                if any(x.arg == "use_pallas"
                       for x in a.posonlyargs + a.args + a.kwonlyargs):
                    takes.append(f"{path.name}:{node.name}")
            elif isinstance(node, ast.Name) and node.id == "use_pallas":
                reads.append(f"{path.name}:{node.lineno}")
            elif isinstance(node, ast.keyword) and node.arg == "use_pallas":
                reads.append(f"{path.name}:{node.lineno}")
    assert sorted(takes) == ["llama.py:decode_forward",
                             "llama.py:prefill_forward"]
    assert reads == []
    assert not (pkg / "ops").exists()
    assert sorted(p.name for p in (pkg / "models").glob("*_kernel.py")) == [
        "chunk_attention_kernel.py", "paged_decode_kernel.py"]
