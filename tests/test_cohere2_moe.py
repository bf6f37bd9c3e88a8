"""The window/full parallel-block family with an expert share
(models/cohere2_moe.py) at a small size on the CPU: prefill and decode through
the paged cache against the plain reference's full forward pass by LOGITS,
over prompts shorter than, equal to and several times the window; the shares
of all chips adding up to the uncut layer; the window gather against masked
whole-table attention; a store load that skips the window layers' dead pages
with every slot it does not hold poisoned; the window pool (pages held for
the window only, returned pages never read again, the local hit that falls
back, release); the counters; what the loader refuses."""

import json
import os
import sys
import types
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.engine import InferenceEngine
from infinistore_tpu.kv import PagedCacheConfig, init_cache
from infinistore_tpu.kv.cache import write_token_kv
from infinistore_tpu.models import family_of, load_config_file
from infinistore_tpu.models.attention import (
    paged_decode_attention,
    paged_window_decode_attention,
    window_page_span,
)
from infinistore_tpu.models.cohere2_moe import (
    cohere2_moe_prefill_forward,
    expert_layer,
)
from infinistore_tpu.models.moe import all_experts_ffn, routed_experts

from test_latent_moe import connect, store  # noqa: F401 -- the store fixture

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "harness"))
import family  # noqa: E402

TOY = json.load(open(os.path.join(BENCH, "configs", "cohere2-moe-toy.json")))
SEED = 7
T = 16
# the toy's window is 256; the tests that walk the page rules use one of four
# pages, so that prompts of a few hundred tokens are several windows long
WINDOW = 64


def model_file(tmp_path, spec, seed=SEED):
    path = os.path.join(tmp_path, "model.json")
    with open(path, "w") as f:
        json.dump(family.model_file(spec, seed), f)
    return path


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The toy of the family as ``serve --model`` would load it, in float32
    and in the served bfloat16, with the reference's own draw; the window cut
    to four pages."""
    path = model_file(str(tmp_path_factory.mktemp("toy")), TOY)
    model_id, cfg, seed = load_config_file(path)
    cfg = replace(cfg, sliding_window=WINDOW)
    fam = family_of(cfg)
    ref = family.reference(TOY)
    sizes = family.counts(TOY).sizes(TOY) | {"W": WINDOW}
    params = fam["init"](cfg, jax.random.PRNGKey(seed))
    return types.SimpleNamespace(
        path=path, model_id=model_id, cfg=cfg, fns=fam["fns"], params=params,
        ref=ref, sizes=sizes, ref_params=ref.draw_weights(sizes, seed))


def engine(toy, n_blocks=128, window_blocks=None, **kw):
    pc = PagedCacheConfig.for_model(toy.cfg, n_blocks, T,
                                    window_blocks=window_blocks)
    kw.setdefault("kv_quant", None)
    return InferenceEngine(toy.params, toy.cfg, pc, prefill_chunk=64,
                           **toy.fns, **kw)


def test_reference_draws_what_the_program_draws(toy):
    assert (jax.tree.structure(toy.params)
            == jax.tree.structure(toy.ref_params))
    for a, b in zip(jax.tree.leaves(toy.params), jax.tree.leaves(toy.ref_params)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


def reference_logits(toy, tokens, n_last, precision="f32"):
    """The reference's log-probabilities are a log-softmax of its logits; the
    program's logits are held to them after the same normalisation."""
    return np.asarray(toy.ref.make_forward(toy.sizes, precision)(
        toy.ref_params, tokens, n_last))


def logprobs(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))


# Tolerance: the program computes in bfloat16 (8 bits of mantissa) through
# four parallel blocks; against the float32 reference it reads 0.010-0.016
# RMS over ALL 512 logits (after log-softmax) here, and the reference itself
# in W8A8 int8, one precision down, 0.05-0.07.  0.03 lies between with room
# on both sides; a program in float32 reads under 1e-4.
RMS_TOLERANCE = 0.03


@pytest.mark.parametrize("n_prompt", [40, WINDOW, 150, 5 * WINDOW + 9],
                         ids=["shorter", "equal", "twice", "five_windows"])
def test_prefill_then_decode_through_the_cache_against_the_reference(toy, n_prompt):
    """Chunked prefill over a bucketed prefix buffer (window layers slice
    their window out of it), then four decode steps (window layers gather
    their window's pages), against the reference's full forward, every logit
    of five positions compared."""
    eng = engine(toy)
    prompt = np.random.default_rng(n_prompt).integers(1, 512, size=n_prompt).tolist()
    st = eng.prefill(prompt)
    got, toks = [logprobs(st.last_logits)], []
    for _ in range(4):
        toks += eng.decode(st, 1)
        got.append(logprobs(st.last_logits))
    want = reference_logits(toy, prompt + toks, 5)
    d = np.stack(got) - want
    assert float(np.sqrt(np.mean(d * d))) <= RMS_TOLERANCE
    eng.release(st)


def test_the_int8_control_fails_the_tolerance(toy):
    """One precision below bfloat16 in the program's place has to come out
    as not correct by the same tolerance."""
    tokens = np.random.default_rng(3).integers(1, 512, size=154).tolist()
    d = reference_logits(toy, tokens, 5, "int8") - reference_logits(toy, tokens, 5)
    assert float(np.sqrt(np.mean(d * d))) > RMS_TOLERANCE


def test_batch_of_unequal_lengths_decodes_as_each_alone(toy):
    """Batch > 1 with lengths below, across and far above the window: each
    row's window starts at its own page."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 512, size=n).tolist() for n in (30, 100, 300)]
    eng = engine(toy)
    states = [eng.prefill(p) for p in prompts]
    outs = eng.decode_batch(states, 6)
    for p, st, out in zip(prompts, states, outs):
        want = reference_logits(toy, p + out, 1)
        d = logprobs(st.last_logits) - want[0]
        assert float(np.sqrt(np.mean(d * d))) <= RMS_TOLERANCE
        alone = engine(toy)
        st1 = alone.prefill(p)
        assert alone.decode(st1, 6) == out


def f32(toy):
    cfg = replace(toy.cfg, dtype=jnp.float32)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), toy.params)
    return cfg, params


def test_a_float32_program_is_the_reference(toy):
    """In float32 the program and the reference are one function: the
    equations (LayerNorm, the parallel block, no rotation in full layers,
    groups of query heads, the share's normalisation, the shared mean, the
    tied head) agree to rounding."""
    cfg, params = f32(toy)
    tokens = np.random.default_rng(5).integers(1, 512, size=3 * WINDOW + 5).tolist()
    logits, _ = cohere2_moe_prefill_forward(params, cfg, jnp.asarray([tokens]))
    got = logprobs(logits[0, -4:])
    np.testing.assert_allclose(got, reference_logits(toy, tokens, 4), atol=2e-4)


# -- the share ------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(toy):
    """The routed parts of all four shares of the toy's 32 experts, plus the
    shared part counted once, equal the uncut layer: what each chip leaves
    out is exactly what the others compute."""
    cfg, _ = f32(toy)
    E, Eh, d, f = cfg.n_experts, cfg.n_experts_held, cfg.dim, cfg.ffn_dim
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    whole = {"router": jax.random.normal(ks[0], (d, E)) / np.sqrt(d),
             "w_gate": jax.random.normal(ks[1], (E, d, f)) / 8,
             "w_up": jax.random.normal(ks[2], (E, d, f)) / 8,
             "w_down": jax.random.normal(ks[3], (E, f, d)) / 8,
             "ws_gate": jax.random.normal(ks[4], (d, 2 * f)) / 8,
             "ws_up": jax.random.normal(ks[5], (d, 2 * f)) / 8,
             "ws_down": jax.random.normal(ks[6], (2 * f, d)) / 8}
    h = jax.random.normal(ks[7], (2, 9, d))
    uncut, n_all = expert_layer(whole, replace(cfg, n_experts_held=E), h)
    assert int(n_all) == 2 * 9 * cfg.top_k
    zero_shared = {k: jnp.zeros_like(v) for k, v in whole.items() if k.startswith("ws_")}
    shared, _ = expert_layer(
        whole | {k: jnp.zeros_like(whole[k]) for k in ("w_gate", "w_up", "w_down")},
        replace(cfg, n_experts_held=E), h)
    total, n_local = shared, 0
    for j in range(E // Eh):
        part = {k: whole[k][j * Eh:(j + 1) * Eh] for k in ("w_gate", "w_up", "w_down")}
        y, n = expert_layer(whole | part | zero_shared,
                            replace(cfg, first_expert=j * Eh), h)
        total, n_local = total + y, n_local + int(n)
    assert n_local == int(n_all)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("held_from, held", [(None, 16), (0, 16), (4, 8), (12, 4)])
def test_routed_experts_told_its_share_matches_the_oracle(held_from, held):
    """``routed_experts`` with ``held_from``: pairs whose expert is absent
    add nothing; told "all" (None) it is the function it was."""
    n, E, k, d, f = 33, 16, 6, 32, 48
    ks = jax.random.split(jax.random.PRNGKey(held), 5)
    x = jax.random.normal(ks[0], (n, d))
    w_gate, w_up = (jax.random.normal(kk, (E, d, f)) / 6 for kk in ks[1:3])
    w_down = jax.random.normal(ks[3], (E, f, d)) / 7
    vals, idx = jax.lax.top_k(jax.random.normal(ks[4], (n, E)), k)
    w = jax.nn.softmax(vals, axis=-1)
    lo = held_from or 0
    dense = jnp.zeros((n, E)).at[jnp.arange(n)[:, None], idx].set(w)
    dense = dense * ((jnp.arange(E) >= lo) & (jnp.arange(E) < lo + held))
    got = routed_experts(x, idx.astype(jnp.int32), w, w_gate[lo:lo + held],
                         w_up[lo:lo + held], w_down[lo:lo + held],
                         held_from=held_from)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(all_experts_ffn(x, dense, w_gate, w_up, w_down)),
        rtol=2e-4, atol=2e-5)


def test_told_all_lowers_as_before():
    """A layer that holds every expert passes no ``held_from`` and lowers to
    the program it always has: the share's selects on the pairs are not in
    it (Kanana's and Mixtral's layers; their own tests hold the values)."""
    x, idx, w = jnp.zeros((8, 16)), jnp.zeros((8, 2), jnp.int32), jnp.zeros((8, 2))
    mats = (jnp.zeros((4, 16, 8)), jnp.zeros((4, 16, 8)), jnp.zeros((4, 8, 16)))
    whole = jax.jit(routed_experts).lower(x, idx, w, *mats).as_text()
    share = jax.jit(lambda *a: routed_experts(*a, held_from=0)).lower(
        x, idx, w, *mats).as_text()
    n = lambda text: text.count("stablehlo.select")
    assert n(share) > n(whole)


# -- (a) a window layer reads its window's pages and no others ---------------------

@pytest.mark.parametrize("lens", [[5], [64, 65, 200], [63, 129, 250, 16]])
def test_window_gather_equals_masked_whole_table_attention(lens):
    """On equal shapes (a table no wider than the window's span) the gather
    is the masked attention to the last bit; on a wider table it reads
    ``window_page_span`` pages where the mask reads all, and agrees to
    rounding.  Pages below the window are poisoned: the gather never reads
    them."""
    H, Hkv, D, W = 8, 2, 16, 64
    B, pages = len(lens), 16
    span = window_page_span(W, T)
    ks = jax.random.split(jax.random.PRNGKey(len(lens)), 3)
    pc = PagedCacheConfig(n_layers=2, n_kv_heads=Hkv, head_dim=D,
                          n_blocks=B * pages + 1, block_tokens=T, dtype=jnp.float32)
    cache = init_cache(pc)
    table = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages) + 1
    k = jax.random.normal(ks[0], (B, pages * T, Hkv, D))
    v = jax.random.normal(ks[1], (B, pages * T, Hkv, D))
    for t in range(max(lens)):
        cache = write_token_kv(cache, 1, table[:, t // T], jnp.full((B,), t % T),
                               k[:, t], v[:, t])
    q = jax.random.normal(ks[2], (B, H, D))
    lens_a = jnp.asarray(lens, jnp.int32)
    masked = paged_decode_attention(q, cache, 1, table, lens_a, window=W)
    # equal shapes: cut the table to the span, shifted so each row's window
    # is inside it (what the gather does by index)
    first = np.maximum(np.asarray(lens) - W, 0) // T
    cut = jnp.stack([jnp.take(table[b], jnp.minimum(first[b] + jnp.arange(span),
                                                    pages - 1)) for b in range(B)])
    shifted = paged_decode_attention(
        q, cache, 1, cut, lens_a - first * T, window=W)
    poisoned = cache
    for b, n in enumerate(lens):
        dead = table[b, :first[b]]
        if len(dead):
            poisoned = poisoned.at[:, :, :, dead].set(jnp.nan)
    got = paged_window_decode_attention(q, poisoned, 1, table, lens_a, W)
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(shifted))
    np.testing.assert_allclose(np.asarray(got), np.asarray(masked),
                               rtol=2e-5, atol=2e-6)


# -- (b) a re-ask loads from the store only pages a query can read --------------

def ask(eng, prompt, n=3):
    st = eng.prefill(prompt)
    out = [np.asarray(st.last_logits)]
    for _ in range(n):
        eng.decode(st, 1)
        out.append(np.asarray(st.last_logits))
    return st, out


def profiled(fn):
    """Run ``fn`` as one profiled step; its result and the summary."""
    from infinistore_tpu.engine.stepprof import StepProfiler
    from infinistore_tpu.utils.metrics import MetricsRegistry

    prof = StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    with prof.step():
        out = fn()
    return out, prof.summary()


def scraped():
    """What /metrics would say of pages by layer kind, under the names of
    the summary's counts."""
    from infinistore_tpu.utils.metrics import default_registry

    reg = default_registry()
    pages = lambda layers, outcome: reg.family_value(
        "istpu_engine_store_prefix_pages_total",
        {"layers": layers, "outcome": outcome}) or 0
    moved = lambda event: reg.family_value(
        "istpu_engine_window_pages_total", {"event": event}) or 0
    return {"store_pages_full": pages("full", "fetched"),
            "store_pages_window": pages("window", "fetched"),
            "store_pages_window_skipped": pages("window", "skipped"),
            "window_pages_acquired": moved("acquired"),
            "window_pages_returned": moved("returned")}


def kv_counts(fn):
    """``fn`` as one profiled step: its result and the summary's counts of
    pages by layer kind that the /metrics families gained too (what a push
    sent of the window layers and the pinned peak are the summary's alone:
    tests/test_mimo_v2.py)."""
    before = scraped()
    out, summary = profiled(fn)
    after = scraped()
    kv = {k: summary["kv"][k] for k in after}
    assert {k: after[k] - before[k] for k in after} == kv
    return out, kv


KV_ZERO = {"store_pages_full": 0, "store_pages_window": 0,
           "store_pages_window_skipped": 0, "window_pages_acquired": 0,
           "window_pages_returned": 0}


def poison(eng, keep=()):
    """NaN in every slot of both pools, or of the window pool only but for
    the window pages ``keep`` names."""
    full, window = eng.cache
    if keep == ():
        eng.cache = (jnp.full_like(full, jnp.nan), jnp.full_like(window, jnp.nan))
        return
    dead = np.setdiff1d(np.arange(window.shape[3]), np.asarray(keep, np.int32))
    eng.cache = (full, window.at[:, :, :, dead].set(jnp.nan))


def held_window(st):
    return st.window_ids[st.window_reclaimed:]


def test_store_load_skips_dead_window_pages_bit_for_bit(toy, store):
    """A re-ask of a prefix of five windows: the window layers' pages below
    the window are neither held, nor fetched, nor scattered (every slot of
    the window pool that the sequence does not hold, POISONED with NaN
    beforehand, stays poisoned), every other page comes back bit for bit,
    and the logits equal, exactly, those computed from HBM.  Everything was
    pushed: a shorter prefix needs the pages this one skips."""
    n_doc = 5 * WINDOW
    prompt = np.random.default_rng(2).integers(1, 512, size=n_doc + 7).tolist()
    n = len(prompt) // T
    conns = [connect(store) for _ in range(2)]
    a = engine(toy, conn=conns[0], model_id=toy.model_id)
    st_a, from_hbm = ask(a, prompt)
    b = engine(toy, conn=conns[1], model_id=toy.model_id)
    poison(b)
    (st_b, skipping), counts = kv_counts(lambda: ask(b, prompt))
    dead = (n * T - WINDOW) // T
    assert st_b.store_chunks == n and dead == 16
    assert st_b.window_reclaimed == dead == st_a.window_reclaimed
    assert counts == KV_ZERO | {
        "store_pages_full": n, "store_pages_window": 3 * (n - dead),
        "store_pages_window_skipped": 3 * dead,
        "window_pages_acquired": n + 1 - dead}
    page = lambda eng, pool, ids, i: np.asarray(eng.cache[pool][:, :, :, ids[i]])
    for i in range(n):
        assert np.array_equal(page(a, 0, st_a.block_ids, i),
                              page(b, 0, st_b.block_ids, i))    # the full layer
        if i >= dead:
            assert np.array_equal(page(a, 1, st_a.window_ids, i),
                                  page(b, 1, st_b.window_ids, i))
    others = np.setdiff1d(np.arange(b.pc.window_blocks), held_window(st_b))
    assert np.isnan(np.asarray(b.cache[1][:, :, :, others])).all()  # never written
    for x, y in zip(from_hbm, skipping):
        assert np.isfinite(y).all() and np.array_equal(x, y)
    for cn in conns:
        cn.close()


def test_int8_pages_go_through_the_same_plan(toy, store):
    """``--kv-quant int8`` stays available (the page is K and V by head in
    both pools): the same pages are skipped, the fetched ones are
    dequantized a layer group at a time, and the answer is the computed one
    to int8's rounding."""
    prompt = np.random.default_rng(8).integers(1, 512, size=5 * WINDOW + 3).tolist()
    conns = [connect(store), connect(store)]
    model_id = toy.model_id + "-q8"
    a = engine(toy, conn=conns[0], model_id=model_id, kv_quant="int8")
    _, computed = ask(a, prompt)
    b = engine(toy, conn=conns[1], model_id=model_id, kv_quant="int8")
    (st, got), counts = kv_counts(lambda: ask(b, prompt))
    n = len(prompt) // T
    assert st.store_chunks == n
    assert counts == KV_ZERO | {
        "store_pages_full": n, "store_pages_window": 3 * 4,
        "store_pages_window_skipped": 3 * (n - 4), "window_pages_acquired": 5}
    for x, y in zip(computed, got):
        d = logprobs(x) - logprobs(y)          # int8's rounding, and the
        assert np.isfinite(d).all()            # choices of experts it flips
        assert 0 < float(np.sqrt(np.mean(d * d))) < 0.4
    for cn in conns:
        cn.close()


def test_a_prompt_shorter_than_the_window_skips_and_returns_nothing(toy, store):
    prompt = np.random.default_rng(4).integers(1, 512, size=WINDOW - 9).tolist()
    conns = [connect(store), connect(store)]
    a = engine(toy, conn=conns[0], model_id=toy.model_id)
    a.release(a.prefill(prompt))
    b = engine(toy, conn=conns[1], model_id=toy.model_id)
    st, counts = kv_counts(lambda: b.prefill(prompt))
    assert st.store_chunks == len(prompt) // T
    assert counts == KV_ZERO | {
        "store_pages_full": st.store_chunks,
        "store_pages_window": 3 * st.store_chunks,
        "window_pages_acquired": len(st.window_ids)}
    assert st.window_reclaimed == 0
    for cn in conns:
        cn.close()


def test_a_local_hit_whose_window_pages_are_gone_is_filled_from_the_store_or_cut(
        toy, store):
    """The full layers' pool keeps a document's pages; the window pool only
    those of its last window.  A later, SHORTER prompt hits the document
    locally with its window reaching into chunks whose window pages were
    never held: they come from the store then, and the answer equals a fresh
    computation; asked again, the window pool holds them and nothing is
    fetched; with the store gone a hit is cut to the prefix whose window is
    held, and the answer is still that."""
    rng = np.random.default_rng(6)
    doc = rng.integers(1, 512, size=5 * WINDOW).tolist()
    long_q, short = doc + [3, 4, 5], doc[:3 * WINDOW] + [9, 9, 9]
    conns = [connect(store), connect(store)]
    a = engine(toy, conn=conns[0], model_id=toy.model_id)
    a.release(a.prefill(long_q))                        # pushed
    _, want = ask(engine(toy), short)                   # computed, no store
    b = engine(toy, conn=conns[1], model_id=toy.model_id)
    poison(b)
    st = b.prefill(long_q)
    assert st.window_reclaimed == 16 and len(held_window(st)) == 5
    b.release(st)
    # the short prompt's 12 chunks are held in the full layers' pool; its
    # window (chunks 8-11) is not in the window pool: filled from the store
    (st2, got), counts = kv_counts(lambda: ask(b, short))
    assert st2.local_chunks == 12 and st2.store_chunks == 0
    assert counts == KV_ZERO | {"store_pages_window": 3 * 4,
                                "window_pages_acquired": 13 - 8}
    for x, y in zip(want, got):
        assert np.array_equal(x, y)
    b.release(st2)
    # asked again: both pools hold what it reads
    (st2, got), counts = kv_counts(lambda: ask(b, short))
    assert st2.local_chunks == 12
    assert counts == KV_ZERO | {"window_pages_acquired": 13 - 8}
    for x, y in zip(want, got):
        assert np.array_equal(x, y)
    b.release(st2)
    # the store gone: a hit whose window is not held is cut to where it is
    short2 = doc[:2 * WINDOW] + [8, 8, 8]
    _, want2 = ask(engine(toy), short2)
    b.transfer.guarded_load = lambda cache, *a, **k: (cache, False)
    free = (b.pages.available, b.wpages.available)
    st3, got2 = ask(b, short2)
    assert st3.reused_chunks == 0 and st3.local_chunks == 0
    for x, y in zip(want2, got2):
        assert np.array_equal(x, y)
    b.release(st3)
    assert (b.pages.available, b.wpages.available) == free
    for cn in conns:
        cn.close()


# -- (c) a sequence holds window-layer pages for its window only -----------------

def test_a_sequence_holds_window_pages_for_its_window_only(toy):
    """Ten windows of prompt, then decode across page boundaries: the full
    layer's table holds every page, the window pool's at most the window's
    span and the page being written; what was returned is counted, and the
    answer is the reference's."""
    prompt = np.random.default_rng(21).integers(1, 512, size=10 * WINDOW + 5).tolist()
    eng = engine(toy)
    span = window_page_span(WINDOW, T)

    def run():
        st = eng.prefill(prompt)
        assert len(held_window(st)) <= span
        toks = []
        for _ in range(3):
            toks += eng.decode(st, 16)
            assert len(held_window(st)) <= span + 1
        return st, toks

    (st, toks), counts = kv_counts(run)
    n = len(st.block_ids)
    assert len(st.window_ids) == n == -(-(len(prompt) + 48) // T)
    assert counts["window_pages_acquired"] == n
    assert counts["window_pages_returned"] == st.window_reclaimed > 36
    want = reference_logits(toy, prompt + toks, 1)
    d = logprobs(st.last_logits) - want[0]
    assert float(np.sqrt(np.mean(d * d))) <= RMS_TOLERANCE
    eng.release(st)
    assert eng.wpages.available == eng.pc.window_blocks


def test_window_pages_returned_are_never_read_again(toy):
    """After every prefill chunk and before every decode dispatch, every slot
    of the window pool that the sequence does not hold is POISONED with NaN
    (what another sequence might write there): the logits are those of an
    engine left alone, to the last bit."""
    prompt = np.random.default_rng(22).integers(1, 512, size=6 * WINDOW + 11).tolist()

    def run(poisoning):
        eng = engine(toy, n_blocks=64)
        pp = eng.prefill_start(prompt)
        st = None
        while st is None:
            st = eng.prefill_step(pp)
            if poisoning:
                poison(eng, keep=pp.window_ids[pp.window_reclaimed:])
        out = [np.asarray(st.last_logits)]
        for _ in range(5):
            if poisoning:
                # what the dispatch does at its entry, done before it so that
                # the pages it takes are kept from the poison
                eng._reclaim_window_pages(st)
                grow = -(-(len(st.tokens) + 8) // T) - len(st.window_ids)
                poison(eng, keep=held_window(st))
                if grow > 0:
                    # a page taken anew holds what its last owner left there:
                    # numbers, beyond the row's length and masked
                    st.block_ids += eng.pages.acquire(grow)
                    new = eng.wpages.acquire(grow)
                    st.window_ids += new
                    eng.cache = (eng.cache[0], eng.cache[1].at[
                        :, :, :, np.asarray(new)].set(0))
            eng.decode(st, 8)
            out.append(np.asarray(st.last_logits))
        return out

    for x, y in zip(run(False), run(True)):
        assert np.isfinite(y).all() and np.array_equal(x, y)


def test_two_sequences_share_a_window_and_return_it_one_by_one(toy):
    """Two prompts with a common document pin the same window pages; the one
    that decodes on returns them while the other still reads them: each
    answers as it does alone, and at the end both pools are whole."""
    rng = np.random.default_rng(23)
    doc = rng.integers(1, 512, size=3 * WINDOW).tolist()
    p1, p2 = doc + [5, 6, 7], doc + [9]
    alone = []
    for p, ns in ((p1, (72, 8)), (p2, (4,))):
        e = engine(toy)
        st = e.prefill(p)
        alone.append((sum((e.decode(st, n) for n in ns), []),
                      np.asarray(st.last_logits)))
    eng = engine(toy)
    s1, s2 = eng.prefill(p1), eng.prefill(p2)
    assert s2.local_chunks == 12
    shared = set(held_window(s1)) & set(held_window(s2))
    assert len(shared) == 4                                 # chunks 8-11
    out1 = eng.decode(s1, 72) + eng.decode(s1, 8)           # passes them
    assert not shared & set(held_window(s1))
    assert shared <= set(held_window(s2))
    out2 = eng.decode(s2, 4)
    assert (out1, out2) == (alone[0][0], alone[1][0])
    assert np.array_equal(np.asarray(s1.last_logits), alone[0][1])
    assert np.array_equal(np.asarray(s2.last_logits), alone[1][1])
    eng.release(s1)
    eng.release(s2)
    assert eng.pages.available == eng.pc.n_blocks
    assert eng.wpages.available == eng.pc.window_blocks


def test_release_returns_each_page_once(toy):
    eng = engine(toy, n_blocks=64, window_blocks=48)
    states = [eng.prefill(list(range(1, 90 + 17 * i))) for i in range(3)]
    eng.decode_batch(states, 5)
    pp = eng.prefill_start(list(range(7, 300)))
    eng.prefill_step(pp)
    eng.abandon_prefill(pp)
    for st in states:
        eng.release(st)
    assert eng.free_pages == 64 and eng._window_reserved == 0
    for pages, n in ((eng.pages, 64), (eng.wpages, 48)):
        assert sorted(pages.alloc._free + list(pages._cached)) == list(range(n))
        assert not pages._refs


def test_a_window_pool_that_runs_out_leaves_both_pools_as_they_were(toy):
    """Admission is all or nothing over both pools: a prompt whose quota of
    window pages (its window's, a chunk's, one more: 4 + 4 + 1) the window
    pool has not unreserved raises MemoryError and nothing stays pinned; the
    scheduler's ``free_pages`` is the full layers' pool's while a quota is
    unreserved, else none."""
    eng = engine(toy, n_blocks=64, window_blocks=12)
    assert eng.free_pages == 64
    st = eng.prefill(list(range(1, 100)))                   # 7 pages of each
    assert eng._window_reserved == 9 and eng.free_pages == 0
    before = (eng.pages.available, eng.wpages.available)
    with pytest.raises(MemoryError):
        eng.prefill_start(list(range(3, 3 + 9 * T)))        # 9 > what is left
    assert (eng.pages.available, eng.wpages.available) == before
    assert not any(r > 1 for r in eng.pages._refs.values())
    eng.release(st)


def test_one_kind_of_layer_is_one_pool(toy):
    """The dense and latent families, and a stack whose every layer is
    windowed, keep ONE array and one table; ``window_blocks`` is refused for
    them."""
    from infinistore_tpu.models import TINY

    pc = PagedCacheConfig.for_model(TINY, 32, T)
    assert pc.window_layers == () and len(pc.pools) == 1
    assert not isinstance(jax.eval_shape(lambda: init_cache(pc)), tuple)
    with pytest.raises(ValueError, match="of one kind"):
        PagedCacheConfig.for_model(TINY, 32, T, window_blocks=16)
    two = PagedCacheConfig.for_model(toy.cfg, 32, T)
    assert two.window_layers == (0, 1, 2) and two.window_blocks == 32
    assert [ls for ls, _ in two.pools] == [(3,), (0, 1, 2)]


# -- counters ---------------------------------------------------------------------

def test_decode_counts_local_pairs_exactly(toy):
    """``decode.expert_pairs_local`` is summed on the device over the LIVE
    rows (a batch of 3 pads to 4: the pad row's pairs are not counted): the
    batch's count is the sum of its rows' counts decoded alone (float32, so
    that no choice rests on a rounding), and a share of all the pairs."""
    cfg, params = f32(toy)
    pc = PagedCacheConfig.for_model(cfg, 128, T)
    prompts = [list(range(1 + i, 40 + 9 * i)) for i in range(3)]

    def decode(ps):
        eng = InferenceEngine(params, cfg, pc, prefill_chunk=64, kv_quant=None,
                              **toy.fns)
        states = [eng.prefill(p) for p in ps]
        return profiled(lambda: eng.decode_batch(states, 3))

    outs, together = decode(prompts)
    d = together["decode"]
    layers, k, _ = cfg.expert_routing
    assert d["row_steps"] == 9 and d["expert_pairs"] == 9 * k * layers
    alone = [decode([p]) for p in prompts]
    assert [o[0] for o, _ in alone] == outs
    assert d["expert_pairs_local"] == sum(
        s["decode"]["expert_pairs_local"] for _, s in alone)
    assert 0 < d["expert_pairs_local"] < d["expert_pairs"]


# -- the model file ---------------------------------------------------------------

def _merged(key, **into):
    return lambda body: body[key].update(into)


@pytest.mark.parametrize("edit, says", [
    (_merged("published", hidden_size=256, extra_width=1), "does not read"),
    (_merged("reduced", hidden_size=64), "may name"),
    (_merged("reduced", num_experts_per_tok=2), "may name"),
    (lambda body: body.pop("stands_for"), "states its deployment"),
    (_merged("reduced", num_experts=4), r"num_experts=4 must be in \[8"),
    (_merged("reduced", vocab_size=128), "vocab_size=128 must be in"),
    (_merged("reduced", num_hidden_layers=6), "cuts a period"),
    (_merged("published", use_parallel_block=False), "use_parallel_block=True only"),
    (lambda body: body["published"].pop("sliding_window"), "published lacks"),
], ids=["unknown_width", "width_override", "experts_a_token", "share_without_deployment",
        "fewer_than_8_experts", "under_an_eighth_of_the_vocabulary", "half_a_period",
        "other_equations", "missing_size"])
def test_loader_refuses(tmp_path, edit, says):
    body = json.loads(json.dumps(family.model_file(TOY, SEED)))
    edit(body)
    path = os.path.join(tmp_path, "m.json")
    with open(path, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match=says):
        load_config_file(path)


@pytest.mark.parametrize("flags", [
    ["--kv-quant", "none", "--tp", "2"], ["--kv-quant", "none", "--ngram-spec"],
    ["--kv-quant", "none", "--draft-model", "tiny"]])
def test_serve_refuses_at_start_up(toy, flags):
    """A mesh and speculation are refused before a weight is drawn; int8
    pages are not: this family's page is K and V by head."""
    from infinistore_tpu import serve

    with pytest.raises(SystemExit, match="this model family is served without"):
        serve.main(["--model", toy.path, "--port", "0", "--n-blocks", "64", *flags])


def test_serve_refuses_a_window_pool_for_a_stack_of_one_kind():
    from infinistore_tpu import serve

    with pytest.raises(SystemExit, match="--window-blocks.*of one kind"):
        serve.main(["--model", "tiny", "--port", "0", "--n-blocks", "64",
                    "--window-blocks", "32"])


def test_program_shapes_equal_the_counts(toy):
    spec = json.load(open(os.path.join(BENCH, "configs", "command-a-plus-l4-e16.json")))
    counts = family.counts(spec)
    s = counts.sizes(spec)
    assert (s["L"], s["Eh"], s["E"], s["V"], s["W"]) == (4, 16, 128, 32768, 4096)
    assert s["windowed"] == [True, True, True, False]
    assert counts.weight_bytes(spec) == 9_470_779_392
    # the fill check's product is what the server allocates: BOTH pools,
    # the full layer's of --n-blocks and the window layers' of --window-blocks
    sv = spec["serve"]
    _, cfg, _ = load_config_file(model_file(os.path.dirname(toy.path), spec))
    window_blocks = int(sv["args"][sv["args"].index("--window-blocks") + 1])
    assert counts.pool_blocks(spec) == (sv["n_blocks"], window_blocks)
    pc = PagedCacheConfig.for_model(cfg, sv["n_blocks"], sv["block_tokens"],
                                    window_blocks=window_blocks)
    pools = jax.eval_shape(lambda: init_cache(pc))
    assert [p.shape[0] for p in pools] == [1, 3]
    assert [p.shape[3] for p in pools] == [sv["n_blocks"], window_blocks]
    assert (sv["n_blocks"] * sv["block_tokens"] * counts.cache_bytes_per_token(spec)
            == sum(p.size * p.dtype.itemsize for p in pools) == pc.cache_bytes)
    # a decode step's K and V: the window in three layers, the length in one
    one = counts.decode_step_bytes(spec, 1, 16384) - counts.decode_step_bytes(spec, 1, 0)
    assert one == (3 * 4096 + 16384) * 4096
