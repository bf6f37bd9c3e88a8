"""The latent-attention, routed-expert family (models/mla_moe.py) at a small
size on the CPU: prefill and decode through the latent paged cache against
the plain reference's full forward pass, the two attention paths against each
other, the routed expert layer against the all-experts oracle, a latent page
through the store and back bit for bit, the reference's draws against the
program's, what the loader and ``serve`` refuse; and, for every
configuration of BENCHMARK.json, that its family's files resolve
(benchmarks/harness/family.py) and its counts equal the program's sizes."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as ist
from infinistore_tpu.engine import InferenceEngine
from infinistore_tpu.kv import PagedCacheConfig, init_cache, read_pages
from infinistore_tpu.kv.cache import cache_kind, write_token_rows
from infinistore_tpu.kv.transfer import KVTransferEngine
from infinistore_tpu.models import family_of, load_config_file
from infinistore_tpu.models.attention import (
    latent_absorbed_decode_attention,
    latent_expanded_attention,
)
from infinistore_tpu.models.moe import (
    all_experts_ffn,
    routed_experts,
    sigmoid_top_k,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "harness"))
import family  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TOY = json.load(open(os.path.join(BENCH, "configs", "latent-moe-toy.json")))
SEED = 7


def model_file(tmp_path, spec, seed=SEED):
    path = os.path.join(tmp_path, "model.json")
    with open(path, "w") as f:
        json.dump(family.model_file(spec, seed), f)
    return path


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The toy of the family as ``serve --model`` would load it: config,
    weights, the engine's hooks, and the reference's own draw."""
    path = model_file(str(tmp_path_factory.mktemp("toy")), TOY)
    model_id, cfg, seed = load_config_file(path)
    fam = family_of(cfg)
    ref = family.reference(TOY)
    sizes = family.counts(TOY).sizes(TOY)
    return types.SimpleNamespace(
        path=path, model_id=model_id, cfg=cfg, fns=fam["fns"],
        params=fam["init"](cfg, jax.random.PRNGKey(seed)), ref=ref,
        sizes=sizes, ref_params=ref.draw_weights(sizes, seed))


def engine(toy, n_blocks=64, **kw):
    pc = PagedCacheConfig.for_model(toy.cfg, n_blocks, 16)
    return InferenceEngine(toy.params, toy.cfg, pc, prefill_chunk=64,
                           kv_quant=None, **toy.fns, **kw)


def logprobs(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))


def test_reference_draws_what_the_program_draws(toy):
    """Holds ``reference/latent_moe.py:draw_weights`` and
    ``init_mla_moe_params`` together: same tree, same bits."""
    assert (jax.tree.structure(toy.params)
            == jax.tree.structure(toy.ref_params))
    for a, b in zip(jax.tree.leaves(toy.params), jax.tree.leaves(toy.ref_params)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


# Tolerance: the program computes in bfloat16 (8 bits of mantissa) through
# three layers and reads 0.019 RMS against the float32 reference here; the
# reference itself in W8A8 int8, one precision down, reads 0.059.  0.035 is
# between the two with room on both sides, as the chip's limit is set.
RMS_TOLERANCE = 0.035


@pytest.mark.parametrize("against, passes", [("f32", True), ("int8", False)])
def test_prefill_then_decode_through_the_cache_against_the_reference(
        toy, against, passes):
    """Chunked prefill (three chunks over a bucketed prefix buffer, expanded
    attention), then four decode steps (absorbed attention over the paged
    latent cache), against the reference's full forward pass, on the
    reference's five likeliest tokens at five positions.  The int8 control
    has to fail the same tolerance."""
    eng = engine(toy)
    prompt = np.random.default_rng(0).integers(1, 512, size=150).tolist()
    st = eng.prefill(prompt)
    got, toks = [logprobs(st.last_logits)], []
    for _ in range(4):
        toks += eng.decode(st, 1)
        got.append(logprobs(st.last_logits))
    want = np.asarray(toy.ref.make_forward(toy.sizes, against)(
        toy.ref_params, prompt + toks, 5))
    f32 = want if against == "f32" else np.asarray(
        toy.ref.make_forward(toy.sizes, "f32")(toy.ref_params, prompt + toks, 5))
    top = np.argsort(f32, -1)[:, -5:]
    # the control stands in the program's place: it is held to float32
    lhs = np.stack(got) if against == "f32" else want
    d = np.take_along_axis(lhs - f32, top, -1)
    rms = float(np.sqrt(np.mean(d * d)))
    assert (rms <= RMS_TOLERANCE) == passes, rms


def test_reference_answers_every_choice_within_a_near_tie(toy, monkeypatch):
    """The choice of experts is discrete: where the reference's own choice
    rests on a margin under NEAR_TIE, the other choice is a correct answer
    too, and the comparison holds the program to the nearest.  A choice
    further off than NEAR_TIE is not forgiven."""
    ref = toy.ref
    select = np.array([0.9, 0.8, 0.7, 0.6994, 0.3, 0.2, 0.1, 0.05])
    sets = ref.near_tie_sets(select, 3)
    assert sets[0] == ([0, 1, 2], 0.0)
    assert [sorted(c) for c, _ in sets[1:]] == [[0, 1, 3]]
    assert sets[1][1] == pytest.approx(0.0006)
    select[3] = 0.68                              # clear of the boundary
    assert ref.near_tie_sets(select, 3) == [([0, 1, 2], 0.0)]
    # a whole pass: with a wide NEAR_TIE the toy's positions get several
    # answers; leaf 0 is the full pass's own; an answer made of another
    # leaf is resolved to it exactly, an answer far from all is not
    monkeypatch.setattr(ref, "NEAR_TIE", 0.05)
    tokens = np.random.default_rng(0).integers(1, 512, size=154).tolist()
    forward = ref.make_forward(toy.sizes, "f32")
    own = np.asarray(forward(toy.ref_params, tokens, 4))
    answers = forward.answers(toy.ref_params, tokens, 4)
    assert max(len(crossed) for _, crossed in answers) > 1
    for (lps, crossed), lp in zip(answers, own):
        assert crossed[0] == 0.0 and np.abs(lps[0] - lp).max() < 1e-4

    def as_answer(rows):
        top = [np.argsort(r)[-5:] for r in rows]
        return {"ids": [int(t[-1]) for t in top],
                "top": [{int(i): float(r[i]) for i in t} for r, t in zip(rows, top)]}

    other = [lps[-1] for lps, _ in answers]       # the furthest leaf of each
    got = ref.compare([as_answer(other)], [answers])
    assert got["rms"] < 1e-6 and got["n_values"] == 20
    assert got["resolved"] == sum(len(c) > 1 for _, c in answers)
    far = ref.compare([as_answer([r - 1.0 for r in other])], [answers])
    assert far["rms"] > 0.5


@pytest.mark.parametrize("batch, pages, lens", [(1, 4, [50]), (3, 8, [128, 17, 77])])
def test_absorbed_attention_equals_expanded_on_the_same_page(batch, pages, lens):
    """The decode path (queries carried into the latent space over one row
    a token) and the prefill path (rows up-projected to keys and values by
    head) are one function of the page: float32, same rows, same answer."""
    H, R, nope, rope, v, T = 4, 64, 32, 16, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    S = pages * T
    rows = jax.random.normal(ks[0], (batch, S, R + rope), jnp.float32)
    q = jax.random.normal(ks[1], (batch, H, nope + rope), jnp.float32)
    w_kvb = jax.random.normal(ks[2], (R, H * (nope + v)), jnp.float32) / 8
    pc = PagedCacheConfig(n_layers=2, n_kv_heads=1, head_dim=R + rope,
                          n_blocks=batch * pages + 3, dtype=jnp.float32, planes=1)
    cache = init_cache(pc)
    table = (jnp.arange(batch * pages, dtype=jnp.int32).reshape(batch, pages) + 3)
    for t in range(S):      # one token at a time, as the decode step writes
        cache = write_token_rows(cache, 1, table[:, t // T],
                                 jnp.full((batch,), t % T), rows[:, t, None, None, :])
    lens = jnp.asarray(lens, jnp.int32)
    got = latent_absorbed_decode_attention(q, cache, 1, table, lens, w_kvb, R, nope)
    for b in range(batch):
        n = int(lens[b])
        want = latent_expanded_attention(
            q[b:b + 1, None], rows[b:b + 1, :n], w_kvb, R, nope, q_offset=n - 1)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want[0, 0]),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("gates", ["sigmoid", "softmax"])
@pytest.mark.parametrize("n_tokens, n_experts, k", [(1, 8, 2), (40, 8, 2), (33, 16, 6)])
def test_routed_experts_match_the_all_experts_oracle(gates, n_tokens, n_experts, k):
    """A skewed router: one expert gets most tokens, the last three get
    none; every token still gets its k experts (no drop, no capacity)."""
    d, f = 32, 48
    ks = jax.random.split(jax.random.PRNGKey(n_tokens), 5)
    x = jax.random.normal(ks[0], (n_tokens, d), jnp.float32)
    w_gate, w_up = (jax.random.normal(kk, (n_experts, d, f), jnp.float32) / 6
                    for kk in ks[1:3])
    w_down = jax.random.normal(ks[3], (n_experts, f, d), jnp.float32) / 7
    logits = jax.random.normal(ks[4], (n_tokens, n_experts), jnp.float32)
    logits = logits.at[:, 0].add(4.0).at[:, -3:].add(-50.0)
    if gates == "sigmoid":
        idx, w = sigmoid_top_k(jax.nn.sigmoid(logits), jnp.zeros(n_experts), k, 2.448)
    else:
        vals, idx = jax.lax.top_k(logits, k)
        w = jax.nn.softmax(vals, axis=-1)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=n_experts)
    assert counts[0] == n_tokens and not counts[-3:].any()
    dense = jnp.zeros((n_tokens, n_experts)).at[
        jnp.arange(n_tokens)[:, None], idx].set(w)
    np.testing.assert_allclose(
        np.asarray(routed_experts(x, idx, w, w_gate, w_up, w_down)),
        np.asarray(all_experts_ffn(x, dense, w_gate, w_up, w_down)),
        rtol=2e-4, atol=2e-5)


@pytest.fixture(scope="module")
def store():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    mport = s.getsockname()[1]
    s.close()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server", "--service-port", str(port),
         "--manage-port", str(mport), "--prealloc-size", "1",
         "--minimal-allocate-size", "16", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    deadline = time.time() + 30
    while time.time() < deadline:
        if proc.poll() is not None:
            pytest.fail("store server failed to start")
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            break
        except OSError:
            time.sleep(0.1)
    yield port
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def connect(port):
    c = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=port, connection_type=ist.TYPE_SHM))
    c.connect()
    return c


def test_latent_page_to_the_store_and_back_bit_for_bit(toy, store):
    """A document's latent pages are pushed as the cache config states them
    (one plane, [T, rank + rope]), come back bit for bit into another
    engine's cache, and decode through the same program to the same logits
    exactly: what the benchmark's paired probes hold the chip to."""
    prompt = np.random.default_rng(1).integers(1, 512, size=16 * 9 + 5).tolist()
    n = len(prompt) // 16

    def ask(eng):
        st = eng.prefill(prompt)
        out = [np.asarray(st.last_logits)]
        for _ in range(3):
            eng.decode(st, 1)
            out.append(np.asarray(st.last_logits))
        return st, out

    conn_a, conn_b = connect(store), connect(store)
    a = engine(toy, conn=conn_a, model_id=toy.model_id)
    assert a.transfer.wire_page_bytes == a.pc.page_bytes == 16 * 80 * 2
    a.release(a.prefill(prompt))             # computed, pushed (strict)
    st_a, from_hbm = ask(a)                  # the same pages, still in HBM
    assert st_a.local_chunks == n and st_a.store_chunks == 0
    b = engine(toy, conn=conn_b, model_id=toy.model_id)
    st_b, from_store = ask(b)
    assert st_b.store_chunks == n and st_b.local_chunks == 0
    pages_a = read_pages(a.cache, jnp.asarray(st_a.block_ids[:n]))
    pages_b = read_pages(b.cache, jnp.asarray(st_b.block_ids[:n]))
    assert pages_a.shape == (3, 1, 1, n, 16, 80)
    assert np.array_equal(np.asarray(pages_a), np.asarray(pages_b))
    for x, y in zip(from_hbm, from_store):
        assert np.array_equal(x, y)
    conn_a.close()
    conn_b.close()


def _merged(key, **into):
    return lambda body: body[key].update(into)


def _without(key, gone):
    return lambda body: body[key].pop(gone)


@pytest.mark.parametrize("edit, says", [
    (_merged("published", extra_width=1), "does not read"),
    (_without("published", "kv_lora_rank"), "published lacks"),
    (_merged("reduced", num_hidden_layers=2, n_routed_experts=4),
     "num_hidden_layers only"),
    (_merged("reduced", num_hidden_layers=1), "num_hidden_layers must be in"),
    (_merged("published", q_lora_rank=1536), "computes q_lora_rank=None only"),
    (_merged("published", qk_head_dim=64), "does not follow"),
    (lambda body: body.update(family="made_up"), "is not one"),
], ids=["unknown_width", "missing_size", "reduced_experts", "too_shallow",
        "other_equations", "inconsistent", "unknown_family"])
def test_loader_refuses(tmp_path, edit, says):
    """Every size is stated and none overridden; depth is the only cut; a
    file of another make of model is not computed under this one's name."""
    body = json.loads(json.dumps(family.model_file(TOY, SEED)))
    edit(body)
    path = os.path.join(tmp_path, "m.json")
    with open(path, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match=says):
        load_config_file(path)


@pytest.mark.parametrize("flags", [
    ["--kv-quant", "int8"], ["--kv-quant", "none", "--tp", "2"],
    ["--kv-quant", "none", "--ngram-spec"], ["--kv-quant", "none", "--draft-model", "tiny"],
])
def test_serve_refuses_at_start_up(toy, flags):
    """int8 pages, a mesh and speculation are refused before a weight is
    drawn: never a wrong scale, never llama's verify step on these weights."""
    from infinistore_tpu import serve

    with pytest.raises(SystemExit, match="this model family is served without"):
        serve.main(["--model", toy.path, "--port", "0", "--n-blocks", "64", *flags])


@pytest.mark.parametrize("what", ["lora", "mesh", "int8", "verify"])
def test_engine_refuses(toy, what):
    """The same walls one level down, for callers that build the engine."""
    pc = PagedCacheConfig.for_model(toy.cfg, 64, 16)
    if what == "lora":
        with pytest.raises(ValueError, match="LoRA composes"):
            InferenceEngine(toy.params, toy.cfg, pc, kv_quant=None, **toy.fns,
                            lora=types.SimpleNamespace(tree=None, scale=1.0))
    elif what == "mesh":
        with pytest.raises(ValueError, match="served on one device"):
            InferenceEngine(toy.params, toy.cfg, pc, kv_quant=None, **toy.fns,
                            mesh=object())
    elif what == "int8":
        with pytest.raises(ValueError, match="scales pages per"):
            KVTransferEngine(object(), pc, quant="int8")
    else:
        eng = InferenceEngine(toy.params, toy.cfg, pc, kv_quant=None, **toy.fns)
        st = eng.prefill([1, 2, 3, 4, 5])
        with pytest.raises(Exception, match="verify_fn"):
            eng.verify(st, [7, 8], 5)


def test_decode_counts_routed_pairs(toy):
    """``decode.expert_pairs`` is exact (k a row a layer a step);
    ``decode.experts_expected`` is the expectation from the counted rows."""
    from infinistore_tpu.engine.scheduler import Scheduler
    from infinistore_tpu.engine.stepprof import StepProfiler

    from infinistore_tpu.utils.metrics import MetricsRegistry

    prof = StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    sched = Scheduler(engine(toy), max_batch=2, stepprof=prof)
    sched.submit(list(range(1, 40)), max_new_tokens=4)
    sched.run()
    d = prof.summary()["decode"]
    layers, k, n_experts = toy.cfg.expert_routing
    assert d["row_steps"] == 4 and d["steps"] == 4
    assert d["expert_pairs"] == d["row_steps"] * k * layers == 4 * 2 * 2
    assert d["experts_expected"] == pytest.approx(
        4 * layers * n_experts * (1 - (1 - k / n_experts) ** 1))


# -- every configuration of the benchmark resolves through family.py ----------

@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda e: e["name"])
def test_benchmark_configuration_resolves(entry, tmp_path):
    """Its reference, counts, model file and rehearsal exist; the cut keeps
    to the guide's section 4; the model file loads through the program's own
    loader; and the count module's weights and cache bytes a token equal the
    program's sizes (from shapes: nothing is allocated)."""
    spec = json.load(open(os.path.join(ROOT, entry["file"])))
    assert family.cut_problems(entry, spec) == []
    assert os.path.exists(os.path.join(
        BENCH, "reference", f"{family.reference_name(spec)}.py"))
    ref = family.reference(spec)
    for fn in ("draw_weights", "make_forward", "reference_logprobs", "compare",
               "control_answers"):
        assert callable(getattr(ref, fn))
    counts = family.counts(spec)
    toy_file = family.rehearsal_file(spec)
    assert os.path.exists(toy_file)
    toy_spec = json.load(open(toy_file))
    assert family.reference_name(toy_spec) == family.reference_name(spec)
    assert toy_spec.get("costs") == spec.get("costs")
    _, cfg, seed = load_config_file(model_file(str(tmp_path), spec, seed=11))
    assert seed == 11 and cfg.n_layers == spec["num_hidden_layers"]
    shapes = jax.eval_shape(
        lambda: family_of(cfg)["init"](cfg, jax.random.PRNGKey(0)))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert counts.weight_bytes(spec) == held
    # the fill check's product is the cache as the server allocates it: one
    # array, or one pool a layer kind (--window-blocks in serve.args); a
    # family whose cache is state slots is held to its own shapes in
    # tests/test_retention.py::test_allocated_bytes_equal_the_counts, one
    # whose sequence keeps pages AND a state in tests/test_lfm2_moe.py's and
    # tests/test_jamba.py's
    sv = spec["serve"]
    if cache_kind(cfg) != "pages":
        assert "--state-stride" in sv["args"]
        return
    window_blocks = (int(sv["args"][sv["args"].index("--window-blocks") + 1])
                     if "--window-blocks" in sv["args"] else None)
    pc = PagedCacheConfig.for_model(cfg, sv["n_blocks"], sv["block_tokens"],
                                    window_blocks=window_blocks)
    assert (counts.cache_bytes_per_token(spec) * pc.block_tokens * pc.n_blocks
            == pc.cache_bytes)
    assert counts.store_page_bytes(spec, pc.block_tokens) == pc.page_bytes
    s = counts.sizes(spec)
    assert {"L", "d", "V"} <= set(s) and s["L"] == cfg.n_layers
