"""The window/full family whose layer kinds write pages of two shapes
(models/mimo_v2.py) at a small size on the CPU: prefill and decode through the
paged cache against the plain reference's full forward pass by LOGITS, at
prompt lengths under, at and over the window and across a chunk boundary; the
controls that have to fail (int8, the sink left out, the value scale left out,
a key rotated whole); the sixteen shares adding up to the uncut layer; a page
with the shape of its pool, held, pushed and fetched by kind; window pages
taken a chunk at a time within a quota, returned pages never read again (on
this toy AND on Command-A's); Command-A's pushes as they were; what ``serve``
refuses."""

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
from infinistore_tpu.kv.hashing import chunk_keys, layer_key
from infinistore_tpu.kv.transfer import KeysByPool, KVTransferEngine
from infinistore_tpu.models import family_of, load_config_file
from infinistore_tpu.models.mimo_v2 import (
    expert_layer,
    mimo_v2_prefill_forward,
)

from test_latent_moe import connect, store  # noqa: F401 -- the store fixture

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "harness"))
import family  # noqa: E402

load = lambda name: json.load(open(os.path.join(BENCH, "configs", name)))
TOY = load("mimo-v2-toy.json")
COHERE_TOY = load("cohere2-moe-toy.json")
REAL = load("mimo-v2-flash-l7-e16.json")
SEED = 7
T = 16
WINDOW = 32         # the toy's: two pages
CHUNK = 64          # --prefill-chunk of the toy: four pages


def model_file(tmp_path, spec, seed=SEED):
    path = os.path.join(tmp_path, "model.json")
    with open(path, "w") as f:
        json.dump(family.model_file(spec, seed), f)
    return path


def loaded(tmp, spec, **cfg_edit):
    path = model_file(str(tmp), spec)
    model_id, cfg, seed = load_config_file(path)
    cfg = replace(cfg, **cfg_edit)
    fam = family_of(cfg)
    ref = family.reference(spec)
    sizes = family.counts(spec).sizes(spec)
    if "sliding_window" in cfg_edit:
        sizes["W"] = cfg_edit["sliding_window"]
    return types.SimpleNamespace(
        path=path, model_id=model_id, cfg=cfg, fns=fam["fns"],
        params=fam["init"](cfg, jax.random.PRNGKey(seed)),
        ref=ref, sizes=sizes, seed=seed)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The toy as ``serve --model`` would load it, in the served bfloat16,
    with the reference's own draw."""
    t = loaded(tmp_path_factory.mktemp("toy"), TOY)
    t.ref_params = t.ref.draw_weights(t.sizes, t.seed)
    return t


def pinned(params):
    """The choice of experts PINNED in every expert layer (a selection bias
    of 10 on two experts this share holds and two it does not; the weights
    stay the chosen scores over their sum)."""
    bias = jnp.zeros((32,), jnp.float32).at[jnp.asarray([0, 1, 9, 17])].set(10.0)
    return params | {"layers": tuple(
        {k: (bias if k == "router_bias" else v) for k, v in layer.items()}
        for layer in params["layers"])}


@pytest.fixture(scope="module")
def pinned_toy(toy):
    """The toy with the choice pinned, in the program and in the reference:
    what a bfloat16 program is held to a tolerance on (below)."""
    return types.SimpleNamespace(**(vars(toy) | {
        "params": pinned(toy.params), "ref_params": pinned(toy.ref_params)}))


@pytest.fixture(scope="module")
def cohere_toy(tmp_path_factory):
    """Command-A's toy, its window cut to four pages (tests/test_cohere2_moe.py)."""
    return loaded(tmp_path_factory.mktemp("cohere"), COHERE_TOY, sliding_window=64)


def engine(toy, n_blocks=128, window_blocks=48, **kw):
    pc = PagedCacheConfig.for_model(toy.cfg, n_blocks, T,
                                    window_blocks=window_blocks)
    kw.setdefault("kv_quant", None)
    kw.setdefault("prefill_chunk", CHUNK)
    return InferenceEngine(toy.params, toy.cfg, pc, **toy.fns, **kw)


def test_reference_draws_what_the_program_draws(toy):
    assert (jax.tree.structure(toy.params)
            == jax.tree.structure(toy.ref_params))
    for a, b in zip(jax.tree.leaves(toy.params), jax.tree.leaves(toy.ref_params)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    sinks = [l["sink"] for l in toy.params["layers"] if "sink" in l]
    assert len(sinks) == 5 and all(float(jnp.min(s)) > 0 for s in sinks)


def reference_logits(toy, tokens, n_last, precision="f32"):
    return np.asarray(toy.ref.make_forward(toy.sizes, precision)(
        toy.ref_params, tokens, n_last))


def logprobs(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))


def rms(d):
    return float(np.sqrt(np.mean(np.square(d))))


def from_the_nearest_answer(toy, tokens, got):
    """RMS, over ALL 512 logits (after log-softmax) of the last ``len(got)``
    positions, of ``got`` minus the NEAREST of the reference's answers at
    each position (``forward.answers``: every choice of experts within a
    near-tie of the reference's own; the rule of reference/cohere2_moe.py,
    which the benchmark's check holds the chip to)."""
    answers = toy.ref.make_forward(toy.sizes, "f32").answers(
        toy.ref_params, tokens, len(got))
    d = []
    for row, (lps, _) in zip(got, answers):
        dd = np.asarray(row)[None] - lps
        d.append(dd[int(np.argmin(np.sum(dd * dd, axis=1)))])
    return rms(np.stack(d))


# Tolerance.  The choice of 4 of 32 experts is discrete: a bfloat16 program
# flips it where two selection values lie within rounding, and at this toy's
# width (128) and with no shared expert beside the routed ones a flip at ANY
# earlier position moves what the compared position reads: free, a sound
# bfloat16 program reads 0.011-0.097 from the nearest answer and the int8
# control 0.041-0.053, so no limit parts them (at the published width the
# scores move a tenth as much, and the chip's check parts them: PERF.md
# section 2).  So the bfloat16 program is held to the reference WITH THE
# CHOICE PINNED in both, where the tolerance measures arithmetic: it reads
# 0.013-0.016 RMS over ALL 512 logits (after log-softmax) over eight prompts,
# the reference itself in W8A8 int8, one precision down, 0.037-0.050; 0.024 is
# the geometric middle.  The FREE choice is held through the same path in
# float32, where program and reference are one function (under 1e-5), and
# each equation left out reads 0.39 and more.
RMS_TOLERANCE = 0.024
LENGTHS = pytest.mark.parametrize(
    "n_prompt", [20, WINDOW, 50, CHUNK + 5, 3 * CHUNK + 9],
    ids=["under_the_window", "at_the_window", "over_it", "across_a_chunk",
         "ten_windows"])


def served(eng, n_prompt):
    """A prompt prefilled in chunks and four decode steps: the tokens and the
    log-probabilities of five positions."""
    prompt = np.random.default_rng(n_prompt).integers(1, 512, size=n_prompt).tolist()
    st = eng.prefill(prompt)
    got, toks = [logprobs(st.last_logits)], []
    for _ in range(4):
        toks += eng.decode(st, 1)
        got.append(logprobs(st.last_logits))
    eng.release(st)
    return prompt + toks, np.stack(got)


@LENGTHS
def test_prefill_then_decode_through_the_cache_against_the_reference(
        pinned_toy, n_prompt):
    """Chunked prefill (the full layers over a bucketed prefix buffer, the
    window layers over their own buffer of the window's rows), then four
    decode steps (window layers gather their window's pages, the sink in
    their softmax), in the served bfloat16 against the reference's full
    forward, every logit of five positions compared."""
    tokens, got = served(engine(pinned_toy), n_prompt)
    assert rms(got - reference_logits(pinned_toy, tokens, 5)) <= RMS_TOLERANCE


@LENGTHS
def test_the_free_choice_through_the_cache_in_float32(toy, n_prompt):
    """The same path with the choice of experts free, in float32: the paged
    cache, the chunk boundary, the window's buffer and gather, the sink and
    the routing are the reference's function to rounding."""
    cfg, params = f32(toy)
    pc = PagedCacheConfig.for_model(cfg, 128, T, window_blocks=48)
    tokens, got = served(InferenceEngine(
        params, cfg, pc, prefill_chunk=CHUNK, kv_quant=None, **toy.fns), n_prompt)
    np.testing.assert_allclose(got, reference_logits(toy, tokens, 5), atol=2e-4)


@pytest.mark.parametrize("seed", [3, 4])
def test_the_int8_control_fails_the_tolerance(pinned_toy, seed):
    """One precision below bfloat16 in the program's place has to come out
    as not correct by the same tolerance."""
    tokens = np.random.default_rng(seed).integers(1, 512, size=154).tolist()
    d = (reference_logits(pinned_toy, tokens, 5, "int8")
         - reference_logits(pinned_toy, tokens, 5))
    assert rms(d) > RMS_TOLERANCE


def f32(toy, **cfg_edit):
    cfg = replace(toy.cfg, dtype=jnp.float32, **cfg_edit)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), toy.params)
    return cfg, params


def test_a_float32_program_is_the_reference(toy):
    """In float32 the program and the reference are one function: the
    equations (RMSNorm, the sequential block, 8 against 4 key/value heads,
    the partial rotation by kind, the sink, the scaled values, the dense
    first layer, the share's normalisation, the untied head) agree to
    rounding."""
    cfg, params = f32(toy)
    tokens = np.random.default_rng(5).integers(1, 512, size=3 * WINDOW + 5).tolist()
    logits, _ = mimo_v2_prefill_forward(params, cfg, jnp.asarray([tokens]))
    got = logprobs(logits[0, -4:])
    np.testing.assert_allclose(got, reference_logits(toy, tokens, 4), atol=2e-4)


def _no_sink(cfg, params):
    layers = tuple({k: (jnp.full_like(v, -jnp.inf) if k == "sink" else v)
                    for k, v in l.items()} for l in params["layers"])
    return cfg, params | {"layers": layers}


@pytest.mark.parametrize("broken", [
    _no_sink,
    lambda cfg, params: (replace(cfg, value_scale=1.0), params),
    lambda cfg, params: (replace(cfg, partial_rotary_factor=1.0), params),
], ids=["the_sink_left_out", "the_value_scale_left_out", "a_key_rotated_whole"])
def test_an_equation_left_out_fails(toy, broken):
    """Each of the family's own equations is seen by the check: a float32
    program without it differs from the reference by more than a sound
    bfloat16 program may."""
    cfg, params = broken(*f32(toy))
    tokens = np.random.default_rng(5).integers(1, 512, size=3 * WINDOW + 5).tolist()
    logits, _ = mimo_v2_prefill_forward(params, cfg, jnp.asarray([tokens]))
    assert from_the_nearest_answer(
        toy, tokens, list(logprobs(logits[0, -4:]))) > 10 * RMS_TOLERANCE


def test_batch_of_unequal_lengths_decodes_as_each_alone(pinned_toy):
    """Batch > 1 with lengths below, across and far above the window: each
    row's window starts at its own page."""
    toy = pinned_toy
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 512, size=n).tolist() for n in (20, 70, 200)]
    eng = engine(toy)
    states = [eng.prefill(p) for p in prompts]
    outs = eng.decode_batch(states, 6)
    for p, st, out in zip(prompts, states, outs):
        want = reference_logits(toy, p + out, 1)
        assert rms(logprobs(st.last_logits) - want[0]) <= RMS_TOLERANCE
        alone = engine(toy)
        st1 = alone.prefill(p)
        assert alone.decode(st1, 6) == out


def test_the_sixteen_shares_add_up_to_the_uncut_layer(toy):
    """The routed parts of all sixteen shares of 32 experts (2 a chip) equal
    the uncut layer, and their local pairs add up to all pairs: what each
    chip leaves out is exactly what the others compute."""
    cfg, _ = f32(toy)
    E, d, f = cfg.n_experts, cfg.dim, cfg.moe_ffn_dim
    Eh = E // 16
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    whole = {"router": jax.random.normal(ks[0], (d, E)) / np.sqrt(d),
             "router_bias": 0.1 * jax.random.normal(ks[1], (E,)),
             "w_gate": jax.random.normal(ks[2], (E, d, f)) / 8,
             "w_up": jax.random.normal(ks[3], (E, d, f)) / 8,
             "w_down": jax.random.normal(ks[4], (E, f, d)) / 8}
    h = jax.random.normal(ks[5], (2, 9, d))
    uncut, n_all = expert_layer(whole, replace(cfg, n_experts_held=E), h)
    assert int(n_all) == 2 * 9 * cfg.top_k
    total, n_local = 0, 0
    for j in range(16):
        part = {k: whole[k][j * Eh:(j + 1) * Eh] for k in ("w_gate", "w_up", "w_down")}
        y, n = expert_layer(whole | part, replace(cfg, n_experts_held=Eh,
                                                  first_expert=j * Eh), h)
        total, n_local = total + y, n_local + int(n)
    assert n_local == int(n_all)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=2e-4, atol=2e-5)


# -- a page has the shape of its pool -------------------------------------------

def real_cache():
    sv = REAL["serve"]
    _, cfg, _ = load_config_file(model_file(os.environ.get("TMPDIR", "/tmp"), REAL))
    window_blocks = int(sv["args"][sv["args"].index("--window-blocks") + 1])
    return cfg, PagedCacheConfig.for_model(
        cfg, sv["n_blocks"], sv["block_tokens"], window_blocks=window_blocks)


def test_program_shapes_equal_the_counts(tmp_path):
    """At the published widths: the weights, each pool's bytes a token
    (5,120 B in the two full layers' pool, 25,600 B in the five window
    layers'), what the server allocates, and nothing padded."""
    counts = family.counts(REAL)
    s = counts.sizes(REAL)
    assert (s["L"], s["Eh"], s["E"], s["V"], s["W"]) == (7, 16, 256, 19072, 128)
    assert s["windowed"] == [False, True, True, True, True, False, True]
    assert s["moe"] == [False] + [True] * 6
    cfg, pc = real_cache()
    abstract = jax.eval_shape(
        lambda: family_of(cfg)["init"](cfg, jax.random.PRNGKey(0)))
    assert counts.weight_bytes(REAL) == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(abstract))
    assert pc.pool_kv == ((4, 192, 128), (8, 192, 128))
    assert [pc.page_shape_of(p) for p in (0, 1)] == [(1, 1, 16, 1280), (1, 1, 16, 2560)]
    assert [pc.page_bytes_of(p) for p in (0, 1)] == [40960, 81920]
    assert counts.pool_token_bytes(REAL) == (5120, 25600)
    pools = jax.eval_shape(lambda: init_cache(pc))
    assert [p.shape for p in pools] == [(2, 1, 1, 10240, 16, 1280),
                                        (5, 1, 1, 512, 16, 2560)]
    sizes = [p.size * p.dtype.itemsize for p in pools]
    assert tuple(sizes) == counts.pool_bytes(REAL)
    sv = REAL["serve"]
    assert (sv["n_blocks"] * sv["block_tokens"] * counts.cache_bytes_per_token(REAL)
            == sum(sizes) == pc.cache_bytes)
    # the store's granule and the pool's bound: no less than a token pushes
    assert counts.store_page_bytes(REAL, 16) == pc.page_bytes_of(0)
    assert (counts.store_page_bytes(REAL, 16) * s["L"] / 16
            >= counts.pushed_bytes_per_token(REAL, 16, 512) == 11520)
    # a decode step's keys and values: the window in five layers at 5,120 B,
    # the length in two at 2,560 B
    one = counts.decode_step_bytes(REAL, 1, 16384) - counts.decode_step_bytes(REAL, 1, 0)
    assert one == 5 * 128 * 5120 + 2 * 16384 * 2560


def test_a_whole_chunk_pushes_11520_bytes_a_token():
    """By the keys a push names, at the published widths: of a whole chunk of
    32 pages the two full layers send every page (40,960 B each), the five
    window layers the last 8 before the boundary (81,920 B each), under
    their own layers' keys, in stack order."""
    _, pc = real_cache()
    eng = types.SimpleNamespace(pc=pc, prefill_chunk=512, _window=128)
    eng._dead_chunks = lambda n: InferenceEngine._dead_chunks(eng, n)
    keys = [f"k{i}" for i in range(64)]
    tr = KVTransferEngine(None, pc)
    total = 0
    for lo in (0, 32):
        sent = [i for i in range(lo, lo + 32)
                if InferenceEngine._window_sent(eng, i, 64 + 9)]
        assert sent == list(range(lo + 24, lo + 32))
        by_pool = KeysByPool((keys[lo:lo + 32], [keys[i] for i in sent]))
        plan = tr._parts_blocks(None, by_pool)
        named = [k for blocks, _ in plan for k, _ in blocks]
        assert len(named) == len(set(named)) == 2 * 32 + 5 * 8
        layers = [int(k.rsplit("#L", 1)[1]) for k in named]
        assert layers == sorted(layers)                     # stack order
        for li in range(7):
            want = keys[lo:lo + 32] if li in (0, 5) else [keys[i] for i in sent]
            assert [k for k in named if k.endswith(f"#L{li}")] == [
                layer_key(k, li) for k in want]
        total += sum(len(blocks) * pb for blocks, pb in plan)
    assert total == 64 * 16 * 11520


def held_window(st):
    return st.window_ids[st.window_reclaimed:]


def ask(eng, prompt, n=3):
    st = eng.prefill(prompt)
    out = [np.asarray(st.last_logits)]
    for _ in range(n):
        eng.decode(st, 1)
        out.append(np.asarray(st.last_logits))
    return st, out


def profiled(fn):
    from infinistore_tpu.engine.stepprof import StepProfiler
    from infinistore_tpu.utils.metrics import MetricsRegistry

    prof = StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    with prof.step():
        out = fn()
    return out, prof.summary()


def poison(eng, keep=()):
    """NaN in every slot of both pools, or of the window pool only but for
    the window pages ``keep`` names."""
    full, window = eng.cache
    if keep == ():
        eng.cache = (jnp.full_like(full, jnp.nan), jnp.full_like(window, jnp.nan))
        return
    dead = np.setdiff1d(np.arange(window.shape[3]), np.asarray(keep, np.int32))
    eng.cache = (full, window.at[:, :, :, dead].set(jnp.nan))


def test_pushed_and_fetched_by_kind_bit_for_bit(toy, store):
    """A document of three chunks and a tail: a push sends the full layers'
    page of every chunk and the window layers' pages of the last window
    before each chunk boundary and before the prompt's end, no others (the
    store does not hold them).  A re-ask with ANOTHER tail, in an engine
    whose every slot is poisoned, adopts the document at its boundary from
    the store: two layers' pages whole, five layers' last two, the dead
    window pages neither sent nor fetched; its logits equal, exactly, those
    computed from scratch."""
    rng = np.random.default_rng(2)
    doc = rng.integers(1, 512, size=3 * CHUNK).tolist()
    first, again = doc + [3, 4, 5, 6, 7], doc + [9, 8, 7]
    conns = [connect(store) for _ in range(3)]
    a = engine(toy, conn=conns[0], model_id=toy.model_id)
    (st_a, _), summary = profiled(lambda: ask(a, first))
    n = len(first) // T                                     # 12 whole blocks
    sent = [i for i in range(n) if a._window_sent(i, n)]
    assert sent == [2, 3, 6, 7, 9, 10, 11]                  # 2 a boundary; the end
    assert summary["kv"]["window_pages_pushed"] == 5 * len(sent)
    assert summary["kv"]["window_pages_push_skipped"] == 5 * (n - len(sent))
    assert summary["kv"]["window_pinned_peak"] == a.window_pinned_peak == 2 + 4
    keys = chunk_keys(first, toy.model_id, chunk_tokens=T)
    exists = lambda i, li: conns[2].check_exist(layer_key(keys[i], li))
    for i in range(n):
        assert all(exists(i, li) for li in (0, 5))
        assert all(exists(i, li) == (i in sent) for li in (1, 2, 3, 4, 6))
    _, want = ask(engine(toy), again)                       # computed, no store
    b = engine(toy, conn=conns[1], model_id=toy.model_id)
    poison(b)
    (st_b, got), summary = profiled(lambda: ask(b, again))
    assert st_b.store_chunks == 12 == len(doc) // T
    assert summary["kv"]["store_pages_full"] == 2 * 12
    assert summary["kv"]["store_pages_window"] == 5 * 2
    assert summary["kv"]["store_pages_window_skipped"] == 5 * 10
    page = lambda eng, pool, ids, i: np.asarray(eng.cache[pool][:, :, :, ids[i]])
    for i in range(12):
        assert np.array_equal(page(a, 0, st_a.block_ids, i),
                              page(b, 0, st_b.block_ids, i))
    others = np.setdiff1d(np.arange(b.pc.window_blocks), held_window(st_b))
    assert np.isnan(np.asarray(b.cache[1][:, :, :, others])).all()  # never written
    for x, y in zip(want, got):
        assert np.isfinite(y).all() and np.array_equal(x, y)
    for cn in conns:
        cn.close()


def test_a_hit_off_a_chunk_boundary_is_cut_to_the_boundary(toy, store):
    """The store holds a window layer's pages before chunk boundaries and
    before the pushing prompt's end only: a prompt that shares the document
    up to the middle of a chunk adopts it at the boundary below (the full
    layers' pages reach deeper; the window's do not exist there), and answers
    as computed."""
    rng = np.random.default_rng(4)
    doc = rng.integers(1, 512, size=3 * CHUNK).tolist()
    conns = [connect(store), connect(store)]
    a = engine(toy, conn=conns[0], model_id=toy.model_id)
    a.release(a.prefill(doc + [1, 2, 3]))
    short = doc[:CHUNK + 2 * T + 3] + [7, 7]                # shares 6 blocks
    _, want = ask(engine(toy), short)
    b = engine(toy, conn=conns[1], model_id=toy.model_id)
    st, got = ask(b, short)
    assert st.store_chunks == CHUNK // T == 4               # not 6
    for x, y in zip(want, got):
        assert np.array_equal(x, y)
    for cn in conns:
        cn.close()


# -- window pages a chunk at a time ---------------------------------------------

def test_window_pages_are_taken_a_chunk_at_a_time_within_the_quota(toy):
    """A prompt of thirteen chunks never pins more than its window's pages
    and one chunk's; the engine reserves that quota at admission, refuses the
    sequence that would pass the pool before it pins anything, and release
    returns quota and pages."""
    quota = WINDOW // T + CHUNK // T + 1
    eng = engine(toy, n_blocks=256, window_blocks=2 * quota)
    prompt = np.random.default_rng(9).integers(1, 512, size=13 * CHUNK + 5).tolist()
    pp = eng.prefill_start(prompt)
    assert pp.window_quota == eng._window_reserved == quota and pp.window_ids == []
    st = None
    while st is None:
        st = eng.prefill_step(pp)
        assert len(pp.window_ids) - pp.window_reclaimed <= quota - 1
    assert eng.window_pinned_peak == WINDOW // T + CHUNK // T
    other = eng.prefill(prompt[:100])
    assert eng._window_reserved == 2 * quota and eng.free_pages == 0
    before = (eng.pages.available, eng.wpages.available)
    with pytest.raises(MemoryError, match="window layers' pool"):
        eng.prefill_start(prompt[5:200])
    assert (eng.pages.available, eng.wpages.available) == before
    eng.decode_batch([st, other], 40)
    assert eng.window_pinned_peak <= quota
    eng.release(st)
    eng.release(other)
    assert eng._window_reserved == 0 and eng.free_pages == 256
    assert eng.wpages.available == 2 * quota and not eng.wpages._refs


@pytest.mark.parametrize("which", ["mimo", "command_a"])
def test_returned_window_pages_are_never_read_again(which, toy, cohere_toy):
    """Two sequences in flight, their chunks interleaved, and a long one
    behind them: after every prefill chunk and before every decode dispatch
    every slot of the window pool that no sequence holds is POISONED with
    NaN (what another sequence might write there); each sequence's logits
    are those of an engine left alone, to the last bit."""
    t = toy if which == "mimo" else cohere_toy
    rng = np.random.default_rng(22)
    prompts = [rng.integers(1, 512, size=n).tolist()
               for n in (3 * CHUNK + 11, 2 * CHUNK + 40, 9 * CHUNK + 7)]

    def run(poisoning):
        eng = engine(t, n_blocks=128, window_blocks=64)
        held = lambda live: sum((x.window_ids[x.window_reclaimed:] for x in live), [])
        pps = [eng.prefill_start(p) for p in prompts[:2]]
        done = {}
        while len(done) < 2:                                # chunk by chunk, by turns
            for i, pp in enumerate(pps):
                if i in done:
                    continue
                st = eng.prefill_step(pp)
                if st is not None:
                    done[i] = st
                if poisoning:
                    poison(eng, keep=held(
                        [p for j, p in enumerate(pps) if j not in done]
                        + list(done.values())))
        states = [done[0], done[1], eng.prefill(prompts[2])]
        out = [[np.asarray(s.last_logits)] for s in states]
        for _ in range(3):
            if poisoning:
                # what the dispatch does at its entry, done before it so that
                # the pages it takes are kept from the poison; a page taken
                # anew holds what its last owner left there: numbers, beyond
                # the row's length and masked
                had = [len(s.window_ids) for s in states]
                eng._grow_tables(states, 8)
                poison(eng, keep=held(states))
                new = sum((s.window_ids[n:] for s, n in zip(states, had)), [])
                if new:
                    eng.cache = (eng.cache[0], eng.cache[1].at[
                        :, :, :, np.asarray(new)].set(0))
            eng.decode_batch(states, 8)
            for o, s in zip(out, states):
                o.append(np.asarray(s.last_logits))
        return out

    for xs, ys in zip(run(False), run(True)):
        for x, y in zip(xs, ys):
            assert np.isfinite(y).all() and np.array_equal(x, y)


def test_command_a_pushes_the_keys_it_pushed(cohere_toy, store):
    """Where the window is at least a chunk a push sends every page of every
    layer, as before this family came: the same keys in the same order (layer
    by layer in stack order within a push, chunk by chunk within a layer)."""
    prompt = np.random.default_rng(8).integers(1, 512, size=3 * CHUNK + 21).tolist()
    conn = connect(store)
    eng = engine(cohere_toy, conn=conn, model_id=cohere_toy.model_id)
    pushed, orig = [], eng.transfer._push_banded

    def recording(parts, plan, stages):
        pushed.append([k for blocks, _ in plan for k, _ in blocks])
        return orig(parts, plan, stages)

    eng.transfer._push_banded = recording
    st = eng.prefill(prompt)
    eng.store_flush()
    keys = chunk_keys(prompt, cohere_toy.model_id, chunk_tokens=T)
    n = len(prompt) // T
    bounds = [(lo, min(lo + CHUNK // T, n)) for lo in range(0, n, CHUNK // T)]
    assert pushed == [[layer_key(keys[i], li) for li in range(4)
                       for i in range(lo, hi)] for lo, hi in bounds]
    assert all(eng._window_sent(i, n) for i in range(n))
    eng.release(st)
    conn.close()


# -- the model file and what ``serve`` refuses -----------------------------------

def _merged(key, **into):
    return lambda body: body[key].update(into)


@pytest.mark.parametrize("edit, says", [
    (_merged("published", hidden_size=256, extra_width=1), "does not read"),
    (_merged("reduced", head_dim=64), "may name"),
    (_merged("reduced", num_experts_per_tok=2), "may name"),
    (lambda body: body.pop("stands_for"), "states its deployment"),
    (_merged("reduced", n_routed_experts=4), r"n_routed_experts=4 must be in \[8"),
    (_merged("reduced", vocab_size=128), "vocab_size=128 must be in"),
    (_merged("reduced", num_hidden_layers=5), "a whole period"),
    (_merged("published", add_swa_attention_sink_bias=False),
     "add_swa_attention_sink_bias=True only"),
    (_merged("published", swa_head_dim=32), "computes them equal"),
    (lambda body: body["published"].pop("swa_num_key_value_heads"), "published lacks"),
], ids=["unknown_width", "width_override", "experts_a_token", "share_without_deployment",
        "fewer_than_8_experts", "under_an_eighth_of_the_vocabulary", "before_a_period",
        "other_equations", "another_width_in_the_window_layers", "missing_size"])
def test_loader_refuses(tmp_path, edit, says):
    body = json.loads(json.dumps(family.model_file(TOY, SEED)))
    edit(body)
    path = os.path.join(tmp_path, "m.json")
    with open(path, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match=says):
        load_config_file(path)


@pytest.mark.parametrize("flags", [
    ["--kv-quant", "none", "--tp", "2"], ["--kv-quant", "none", "--pp", "2"],
    ["--kv-quant", "none", "--ngram-spec"],
    ["--kv-quant", "none", "--draft-model", "tiny"], ["--kv-quant", "int8"], "lora"],
    ids=["tp", "pp", "ngram_speculation", "draft_speculation", "int8_pages", "lora"])
def test_serve_refuses_in_words(toy, flags):
    """A mesh, speculation and int8 pages (a page of keys and values of
    unequal widths side by side has no per-plane scale) are refused before a
    weight is drawn; a LoRA bank where the engine is built."""
    if flags == "lora":
        pc = PagedCacheConfig.for_model(toy.cfg, 32, T)
        bank = types.SimpleNamespace(tree=None, scale=1.0, n_adapters=2)
        with pytest.raises(ValueError, match="LoRA composes the built-in Llama"):
            InferenceEngine(toy.params, toy.cfg, pc, lora=bank, **toy.fns)
        return
    from infinistore_tpu import serve

    with pytest.raises(SystemExit, match="this model family is served without"):
        serve.main(["--model", toy.path, "--port", "0", "--n-blocks", "64", *flags])


def test_the_cut_keeps_to_the_rule():
    """``family.cut_problems``: every width the source's, ``reduced`` depth,
    experts held and vocabulary only, ``stands_for`` 16 chips a layer."""
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"] if c["name"] == "mimo-v2-flash-l7-e16")
    assert family.cut_problems(entry, REAL) == []
    assert sorted(entry["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                        "vocab_size"]
    assert REAL["stands_for"]["chips_per_layer"] == 16
    for k, v in REAL["model"]["published"].items():
        if k not in entry["reduced"] and not isinstance(v, list):
            assert REAL[k] == v, k
