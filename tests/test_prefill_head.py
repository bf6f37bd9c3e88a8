"""A prefill chunk's output head runs where a row of its logits is kept
(models/llama.py ``head_logits``; ``prefill_forward(head=, head_row=)`` of the
four served families; ``InferenceEngine._prefill`` / ``_prefill_chunk``,
``StateEngine._prefill_chunk``): the one-row form against the whole form's
row, the form with no head against the whole form's KV or state, and the
engine's choice between them, which it makes from whether another chunk
follows.  CPU toys; nothing here is a device number."""

import dataclasses
import functools
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.engine import InferenceEngine, stepprof
from infinistore_tpu.engine.state_engine import StateEngine
from infinistore_tpu.kv import PagedCacheConfig
from infinistore_tpu.kv.cache import StateCacheConfig, init_cache
from infinistore_tpu.models import (
    TINY,
    family_of,
    init_params,
    load_config_file,
    prefill_forward,
)
from infinistore_tpu.utils.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "harness"))
import family  # noqa: E402

T, C = 16, 32
# two float32 products of the same terms, added in another order
F32_SUMS = 1e-5
FAMILIES = {"llama": None, "deepseek_v3": "latent-moe-toy.json",
            "cohere2_moe": "cohere2-moe-toy.json",
            "brumby": "retention-toy.json",
            "mimo_v2_flash": "mimo-v2-toy.json"}


@functools.cache
def toy(name, f32=True):
    """A family's CPU toy as ``serve --model`` loads it (the dense family:
    the tiny preset); in float32, the program's arithmetic alone."""
    if FAMILIES[name] is None:
        cfg, fns = TINY, {"prefill_fn": prefill_forward}
        params = init_params(cfg, jax.random.PRNGKey(0))
    else:
        import tempfile

        spec = json.load(open(os.path.join(BENCH, "configs", FAMILIES[name])))
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "model.json")
            with open(path, "w") as f:
                json.dump(family.model_file(spec, 7), f)
            _, cfg, seed = load_config_file(path)
        fam = family_of(cfg)
        fns, params = fam["fns"], fam["init"](cfg, jax.random.PRNGKey(seed))
    if f32:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    return types.SimpleNamespace(name=name, cfg=cfg, params=params, fns=fns)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 500, size=n).tolist()


def chunk_cases(t):
    """``(case, call)``: ``call(**head)`` runs the family's prefill program
    on one chunk.  A first chunk; a chunk on top of what the first left (a
    padded prefix buffer of which ``prefix_len`` rows are valid; the state
    family: the slot's state); a tail shorter than the chunk."""
    fn = jax.jit(functools.partial(t.fns["prefill_fn"], cfg=t.cfg),
                 static_argnames=("head",))
    first = jnp.asarray(tokens(C, 1), jnp.int32)[None]
    second = jnp.asarray(tokens(C, 2), jnp.int32)[None]
    tail = jnp.asarray(tokens(T, 3), jnp.int32)[None]
    if t.name == "brumby":
        pc = StateCacheConfig.for_model(t.cfg, 64, T, 4 * C, max_rows=2)

        def run(toks, cache, start, n_valid):
            return functools.partial(
                fn, t.params, tokens=toks, cache=cache,
                slot=jnp.asarray(1, jnp.int32),
                start=jnp.asarray(start, jnp.int32),
                n_valid=jnp.asarray(n_valid, jnp.int32))

        zero = init_cache(pc)
        _, after = run(first, zero, 0, C)()
        return [("first", run(first, zero, 0, C)),
                ("on_prefix", run(second, after, C, C)),
                ("tail", run(tail, after, C, T - 5))]
    _, kv = fn(t.params, tokens=first)
    pad = [(0, 0)] * 6
    pad[3] = (0, C)                     # capacity 2C, C rows valid
    plen = jnp.asarray(C, jnp.int32)
    if isinstance(kv, tuple):
        # one buffer a pool: the full layers' padded, the window layers' the
        # rows that END where the next chunk starts (all C of them here)
        buf = (jnp.pad(kv[0], pad), kv[1])
    else:
        buf = jnp.pad(kv, pad)
    return [("first", functools.partial(fn, t.params, tokens=first)),
            ("on_prefix", functools.partial(
                fn, t.params, tokens=second, prefix_kv=buf, prefix_len=plen)),
            ("tail", functools.partial(
                fn, t.params, tokens=tail, prefix_kv=buf, prefix_len=plen))]


def same(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("case", ["first", "on_prefix", "tail"])
@pytest.mark.parametrize("name", list(FAMILIES))
def test_one_row_equals_the_whole_forms_row_and_no_head_keeps_the_kv(
        name, case):
    """In float32 on the CPU the one-row form's logits are the whole form's
    row at the same index, at the last position and at one inside the
    chunk, to the order of float32 sums: a ``[1, D] x [D, V]`` product where
    a row of a ``[S, D] x [D, V]`` one stood is another routine of the
    backend's, which adds the same terms in another order (1.4e-6 at most
    here, on logits of order 1: NOT bit for bit).  Every form returns the
    same KV / latent rows / state, bit for bit; the form with no head
    returns no logits."""
    t = toy(name)
    call = dict(chunk_cases(t))[case]
    whole, kv = call()
    assert whole.ndim == 3 and whole.shape[0] == 1
    S = whole.shape[1]
    for row in (S - 1, S - 6):
        one, kv_row = call(head="row", head_row=jnp.asarray([row], jnp.int32))
        assert one.shape == (1, whole.shape[-1])
        np.testing.assert_allclose(np.asarray(one[0]),
                                   np.asarray(whole[0, row]), rtol=0,
                                   atol=F32_SUMS)
        assert same(kv_row, kv)
    none, kv_none = call(head="none")
    assert none is None and same(kv_none, kv)


@pytest.mark.parametrize("name", list(FAMILIES))
def test_one_row_in_the_served_type_is_within_the_familys_tolerance(name):
    """bfloat16 weights and activations: the row's product is accumulated in
    another order than the chunk's, so equality is not promised; what is
    left is far inside what the families' checks allow (RMS 0.02-0.03 of
    log-probabilities against the float32 reference)."""
    t = toy(name, f32=False)
    for _, call in chunk_cases(t):
        whole, _ = call()
        row = whole.shape[1] - 1
        one, _ = call(head="row", head_row=jnp.asarray([row], jnp.int32))
        lp = lambda x: np.asarray(  # noqa: E731
            jax.nn.log_softmax(jnp.asarray(x, jnp.float32)))
        d = lp(one[0]) - lp(whole[0, row])
        assert float(np.sqrt(np.mean(d * d))) < 0.005


def test_head_logits_names_one_position_a_row_and_refuses_an_unknown_head():
    """A batch: row ``b`` of the ``n <= B`` named keeps ``x[b, head_row[b]]``
    (the batched prefill's ``len(p) - 1``; the batch's pad rows keep none);
    ``"none"`` projects nothing."""
    from infinistore_tpu.models.llama import head_logits

    x = jnp.arange(4 * 6 * 2, dtype=jnp.float32).reshape(4, 6, 2)
    double = lambda v: 2 * v  # noqa: E731
    out = head_logits(x, "row", jnp.asarray([5, 0, 3], jnp.int32), double)
    np.testing.assert_array_equal(
        np.asarray(out),
        2 * np.asarray(jnp.stack([x[0, 5], x[1, 0], x[2, 3]])))
    np.testing.assert_array_equal(
        np.asarray(head_logits(x, "all", None, double)), 2 * np.asarray(x))
    assert head_logits(x, "none", None, double) is None
    with pytest.raises(ValueError, match="prefill head"):
        head_logits(x, "last", None, double)


# -- the engine's choice ----------------------------------------------------

def _prof():
    return stepprof.StepProfiler(metrics=MetricsRegistry(), sample=10**9)


def whole_form_only(fn):
    """``fn`` as a custom family that predates the keyword brings it: the
    whole form and nothing else (the engine as it was before: every chunk's
    head over every position, the row picked out of it)."""
    def prefill_forward_whole(params, cfg, tokens, prefix_kv=None,
                              prefix_len=None):
        return fn(params, cfg, tokens, prefix_kv=prefix_kv,
                  prefix_len=prefix_len)

    return prefill_forward_whole


WHOLE = whole_form_only(prefill_forward)


def paged_engine(t, **kw):
    pc = PagedCacheConfig.for_model(t.cfg, 64, T)
    kw.setdefault("prefill_fn", t.fns["prefill_fn"])
    fns = {k: v for k, v in t.fns.items() if k != "prefill_fn"}
    return InferenceEngine(t.params, t.cfg, pc, prefill_chunk=C,
                           kv_quant=None, **fns, **kw)


def top5(logits):
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))
    idx = np.argsort(lp)[-5:]
    return idx, lp[idx]


@pytest.mark.parametrize("n_chunks", [1, 2, 5])
def test_chunked_prefill_keeps_the_first_token_and_counts_one_head_a_prompt(
        n_chunks):
    """A prompt of 1, 2 and 5 chunks (the last a tail shorter than the
    chunk): no logits after any chunk but the last, then one row; the first
    token and the top-5 log-probabilities are those of the engine that runs
    the whole form on every chunk (a custom ``prefill_fn`` without the
    keyword, which still serves), to the order of float32 sums;
    ``summary.prefill`` counts 1 head chunk of
    ``n`` for the one and ``n`` of ``n`` for the other."""
    t = toy("llama")
    prompt = tokens((n_chunks - 1) * C + T + 3, seed=n_chunks)
    rows = {}
    for form, kw in (("kept", {}), ("whole", {"prefill_fn": WHOLE})):
        eng = paged_engine(t, **kw)
        assert eng._prefill_heads == (form == "kept")
        prof = _prof()
        with prof.step() as rec:
            pp = eng.prefill_start(prompt)
            for i in range(n_chunks):
                assert not pp.finished
                state = eng.prefill_step(pp)
                assert (pp.logits is None) == (i < n_chunks - 1)
            assert pp.finished and state is not None
        assert state.last_logits.ndim == 1
        heads = 1 if form == "kept" else n_chunks
        assert (rec["prefill"]["chunks"], rec["prefill"]["head_chunks"]) == (
            n_chunks, heads)
        assert prof.summary()["prefill"]["head_chunks"] == heads
        rows[form] = state.last_logits
        first = eng.decode(state, 1)
        rows[form + "_token"] = first
    assert rows["kept_token"] == rows["whole_token"]
    (ia, la), (ib, lb) = top5(rows["kept"]), top5(rows["whole"])
    np.testing.assert_array_equal(ia, ib)
    np.testing.assert_allclose(la, lb, rtol=0, atol=F32_SUMS)


def test_the_three_forms_are_prefill_programs_by_name():
    """``benchmarks/trace/programs.json`` finds the prefill programs by
    ``prefill_forward`` in their name, and ``model.prefill_ms_per_ktok``
    divides their time by their executions: the one-row form and the form
    with no head are named as the whole form is."""
    eng = paged_engine(toy("llama"))
    toks = jnp.zeros((1, C), jnp.int32)
    for jit, kw in ((eng._prefill_jit, {}), (eng._prefill_nohead_jit, {}),
                    (eng._prefill_row_jit,
                     {"head_row": jnp.zeros((1,), jnp.int32)})):
        text = jit.lower(eng.params, tokens=toks, **kw).as_text()
        assert "module @jit_prefill_forward " in text[:200]


def test_batched_prefill_keeps_each_prompts_last_row():
    """Prompts of one length bucket share a padded forward whose head runs on
    ``len(p) - 1`` of each row: the rows a prompt alone gets."""
    t = toy("llama")
    eng = InferenceEngine(t.params, t.cfg, PagedCacheConfig.for_model(
        t.cfg, 64, T), kv_quant=None)
    prompts = [tokens(n, seed=n) for n in (19, 27, 30)]
    states = eng.prefill_batch(prompts)
    for p, st in zip(prompts, states):
        whole, _ = prefill_forward(t.params, t.cfg,
                                   jnp.asarray(p, jnp.int32)[None])
        np.testing.assert_allclose(np.asarray(st.last_logits),
                                   np.asarray(whole[0, -1]), rtol=2e-5,
                                   atol=2e-5)


def test_prompt_logprobs_still_scores_every_position():
    """``prompt_logprobs`` runs the default, the whole form: a distribution
    for every position but the first."""
    t = toy("llama")
    eng = paged_engine(t)
    prompt = tokens(21, seed=9)
    scored = eng.prompt_logprobs(prompt, k=3)
    assert len(scored) == len(prompt) - 1
    whole, _ = prefill_forward(t.params, t.cfg,
                               jnp.asarray(prompt, jnp.int32)[None])
    lp = np.asarray(jax.nn.log_softmax(whole[0].astype(jnp.float32)))
    for i, (chosen, top) in enumerate(scored):
        assert abs(chosen - lp[i, prompt[i + 1]]) < 1e-4
        assert len(top) == 3


@pytest.mark.parametrize("n_chunks", [1, 2, 5])
def test_state_engine_runs_the_head_on_the_last_chunks_row_alone(n_chunks):
    """The state family's engine chooses the same way: no logits until the
    last chunk, 1 head chunk of ``n``, and the row the whole form gives."""
    t = toy("brumby")
    pc = StateCacheConfig.for_model(t.cfg, 64, T, 4 * C, max_rows=2)
    prompt = tokens((n_chunks - 1) * C + T + 3, seed=10 + n_chunks)

    def whole(params, cfg, tokens, cache, slot, start, n_valid):
        return t.fns["prefill_fn"](params, cfg, tokens, cache, slot, start,
                                   n_valid)

    rows = {}
    for form, fn in (("kept", t.fns["prefill_fn"]), ("whole", whole)):
        fns = dict(t.fns, prefill_fn=fn)
        eng = StateEngine(t.params, t.cfg, pc, prefill_chunk=C,
                          decode_chunk=4, **fns)
        prof = _prof()
        with prof.step() as rec:
            pp = eng.prefill_start(prompt)
            for i in range(n_chunks):
                state = eng.prefill_step(pp)
                assert (pp.logits is None) == (i < n_chunks - 1)
        heads = 1 if form == "kept" else n_chunks
        assert (rec["prefill"]["chunks"], rec["prefill"]["head_chunks"]) == (
            n_chunks, heads)
        rows[form] = np.asarray(state.last_logits)
        rows[form + "_token"] = eng.decode(state, 1)
    assert rows["kept_token"] == rows["whole_token"]
    np.testing.assert_allclose(rows["kept"], rows["whole"], rtol=0,
                               atol=F32_SUMS)
