"""The power-retention family (models/retention.py) and the cache of state
slots it forces (kv/cache.py ``StateCacheConfig``, engine/state_engine.py,
kv/transfer.py ``StateTransferEngine``) at a small size on the CPU: the
recurrent form the program runs against the plain reference's attention
form, by logits; a prompt that starts from an adopted checkpoint (from HBM,
from the store) bit for bit the prompt computed whole; that adoption copies;
which checkpoint is kept, pushed and evicted; what a failing store and a
poisoned slot cost; what the loader, ``serve`` and the engine refuse; the
counts against what is allocated; and the controls the benchmark's check has
to refuse."""

import dataclasses
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
from infinistore_tpu.engine.state_engine import StateEngine
from infinistore_tpu.kv.cache import StateCacheConfig, StateSlots, init_cache
from infinistore_tpu.models import family_of, load_config_file
from infinistore_tpu.models import retention as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "harness"))
import family  # noqa: E402

TOY = json.load(open(os.path.join(BENCH, "configs", "retention-toy.json")))
REAL = json.load(open(os.path.join(BENCH, "configs", "brumby-14b-l8.json")))
SEED = 7
T, STRIDE, CHUNK = 16, 128, 32
# Log-probabilities of the program in float32 against the float32 reference:
# the two run different algorithms for one function (the recurrence over the
# symmetric square in chunks, the attention form over all keys), so what is
# left is the order of float32 sums: 4e-6 at most over 677 tokens here.  The
# same reference with its state accumulated in bfloat16 reads 0.12.
F32_TOL = 1e-4
# The served type (bfloat16 weights and activations) against the float32
# reference, RMS over the top-5 log-probabilities as run.py takes it: 0.015
# sound; W8A8 int8 0.031, the bfloat16 state 0.13, a zeroed checkpoint 2.1.
RMS_LIMIT = 0.021


def model_file(tmp_path, spec, seed=SEED):
    path = os.path.join(tmp_path, "model.json")
    with open(path, "w") as f:
        json.dump(family.model_file(spec, seed), f)
    return path


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = model_file(str(tmp_path_factory.mktemp("toy")), TOY)
    model_id, cfg, seed = load_config_file(path)
    fam = family_of(cfg)
    ref = family.reference(TOY)
    sizes = family.counts(TOY).sizes(TOY)
    params = fam["init"](cfg, jax.random.PRNGKey(seed))
    return types.SimpleNamespace(
        path=path, model_id=model_id, cfg=cfg, fns=fam["fns"], params=params,
        # the same weights in float32: the program's arithmetic alone
        cfg32=dataclasses.replace(cfg, dtype=jnp.float32),
        params32=jax.tree.map(lambda x: x.astype(jnp.float32), params),
        ref=ref, sizes=sizes, ref_params=ref.draw_weights(sizes, seed),
        f32=ref.make_forward(sizes, "f32"))


def engine(toy, f32=False, n_blocks=64, max_rows=4, stride=STRIDE, chunk=CHUNK,
           **kw):
    cfg = toy.cfg32 if f32 else toy.cfg
    pc = StateCacheConfig.for_model(cfg, n_blocks, T, stride, max_rows=max_rows)
    return StateEngine(toy.params32 if f32 else toy.params, cfg, pc,
                       prefill_chunk=chunk, decode_chunk=4, **toy.fns, **kw)


def logprobs(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, size=n).tolist()


def ask(eng, prompt, n=3):
    """Prefill then ``n`` single decode steps: the state, the logits after the
    prompt and after each generated token, and the tokens."""
    st = eng.prefill(prompt)
    rows, out = [np.asarray(st.last_logits)], []
    for _ in range(n):
        out += eng.decode(st, 1)
        rows.append(np.asarray(st.last_logits))
    return st, rows, out


def top5_rms(lps, ref_lps):
    idx = np.argsort(lps, -1)[:, -5:]
    d = np.take_along_axis(lps, idx, -1) - np.take_along_axis(ref_lps, idx, -1)
    return float(np.sqrt(np.mean(d * d)))


# -- the model against its plain reference --------------------------------------

def test_reference_draws_what_the_program_draws(toy):
    for k, v in toy.params["layers"].items():
        assert np.array_equal(np.asarray(v, np.float32),
                              np.asarray(toy.ref_params["layers"][k], np.float32)), k
    for k in ("embed", "lm_head", "ln_out"):
        assert np.array_equal(np.asarray(toy.params[k], np.float32),
                              np.asarray(toy.ref_params[k], np.float32)), k
    # the seeded gate: every head forgets over 64 to 16,384 tokens
    tau = -1.0 / np.asarray(jax.nn.log_sigmoid(toy.params["layers"]["bg"]))
    assert tau.min() >= 64 * 0.999 and tau.max() <= 16384 * 1.001
    assert toy.params["layers"]["wg"].dtype == jnp.float32


@pytest.mark.parametrize("D", [8, 16, 128])
def test_symmetric_square_is_the_square_of_the_dot_product(D):
    q, k = jax.random.normal(jax.random.PRNGKey(D), (2, 6, D), jnp.float32)
    pq, pk = R.symmetric_square(q), R.symmetric_square(k)
    assert pq.shape == (6, D * (D + 1) // 2)
    np.testing.assert_allclose(np.sum(pq * pk, -1), np.sum(q * k, -1) ** 2,
                               rtol=2e-5)


@pytest.mark.parametrize("chunk", [16, 48])
def test_recurrent_form_equals_attention_form_by_logits(toy, chunk):
    """The model's own forwards over bare slots, in float32: chunked prefill
    at two chunk sizes (a padded last chunk among them) and decode step by
    step, every position's logits against the reference's full forward."""
    cfg, params = toy.cfg32, toy.params32
    pc = StateCacheConfig.for_model(cfg, 16, T, 64, max_rows=2)
    cache = init_cache(pc)
    prefill = jax.jit(lambda *a: R.retention_prefill_forward(params, cfg, *a))
    decode = jax.jit(lambda *a: R.retention_decode_forward(
        params, cfg, *a, None, None, None))
    prompt, n = tokens(150, 1), 131
    got = []
    for off in range(0, n, chunk):
        piece = prompt[off:min(off + chunk, n)]
        nv = len(piece)
        logits, cache = prefill(
            jnp.asarray([piece + [0] * (chunk - nv)]), cache,
            jnp.int32(1), jnp.int32(off), jnp.int32(nv))
        got.append(np.asarray(logits[0, :nv]))
    for i in range(n, len(prompt)):
        logits, cache = decode(
            jnp.asarray([prompt[i], 0]), jnp.asarray([i, 0]), cache,
            jnp.asarray([[1], [pc.n_slots]]))
        got.append(np.asarray(logits[:1]))
    want = np.asarray(toy.f32(toy.ref_params, prompt, len(prompt)))
    assert np.abs(logprobs(np.concatenate(got)) - want).max() < F32_TOL
    low = np.asarray(toy.ref.make_forward(toy.sizes, "statebf16")(
        toy.ref_params, prompt, len(prompt)))
    assert np.abs(low - want).max() > 10 * F32_TOL


@pytest.mark.parametrize("lengths", [
    (STRIDE - 9,), (STRIDE,), (5 * STRIDE + 37,),
    (3 * STRIDE + 5, 41, 2 * STRIDE)],
    ids=["shorter", "equal", "several_strides", "batch_of_unequal_lengths"])
def test_prefill_then_decode_through_the_slots_against_the_reference(toy, lengths):
    """Through the engine's slots (a row's slot, chunked prefill, the
    checkpoint on the way, the decode scan over a padded batch), in float32:
    prompts shorter than, equal to and several times the stride, alone and
    three rows of unequal lengths together."""
    eng = engine(toy, f32=True)
    prompts = [tokens(n, 10 + i) for i, n in enumerate(lengths)]
    states = [eng.prefill(p) for p in prompts]
    rows = [[np.asarray(st.last_logits)] for st in states]
    for _ in range(3):
        eng.decode_batch(states, 1)
        for r, st in zip(rows, states):
            r.append(np.asarray(st.last_logits))
    for p, st, r in zip(prompts, states, rows):
        want = np.asarray(toy.f32(toy.ref_params, st.tokens[:-1], 3))
        assert np.abs(logprobs(np.stack(r[:3])) - want).max() < F32_TOL
        assert st.tokens[:len(p)] == p and len(st.tokens) == len(p) + 3
        eng.release(st)
    assert eng.slots.rows_free == eng.pc.max_rows


# -- a store on this machine ---------------------------------------------------------

@pytest.fixture(scope="module")
def store():
    ports = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server", "--service-port",
         str(ports[0]), "--manage-port", str(ports[1]), "--prealloc-size", "1",
         "--minimal-allocate-size", "16", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    deadline = time.time() + 30
    while time.time() < deadline:
        if proc.poll() is not None:
            pytest.fail("store server failed to start")
        try:
            socket.create_connection(("127.0.0.1", ports[0]), timeout=0.5).close()
            break
        except OSError:
            time.sleep(0.1)
    yield ports[0]
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def connect(port):
    c = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=port, connection_type=ist.TYPE_SHM))
    c.connect()
    return c


def profiled(fn):
    from infinistore_tpu.engine.stepprof import StepProfiler
    from infinistore_tpu.utils.metrics import MetricsRegistry

    prof = StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    with prof.step():
        out = fn()
    return out, prof.summary()["state"]


def scraped():
    """What /metrics says of checkpoints, under the summary's names."""
    from infinistore_tpu.utils.metrics import default_registry

    reg = default_registry()
    val = lambda name, **labels: reg.family_value(name, labels) or 0
    out = {f"checkpoints_{e}": val("istpu_engine_state_checkpoints_total", event=e)
           for e in ("taken", "pushed", "skipped_stored")}
    out.update({f"adopted_{s}": val("istpu_engine_state_adoptions_total", source=s)
                for s in ("local", "store")})
    out["bytes_pushed"] = val("istpu_engine_state_bytes_pushed_total")
    out["shared_tokens_recomputed"] = val(
        "istpu_engine_state_shared_tokens_recomputed_total")
    out["resident_evicted"] = val("istpu_engine_state_resident_evicted_total")
    # what an engine of pages AND slots counts besides (engine/hybrid_engine.py)
    out["store_hits"] = val("istpu_engine_state_store_hits_total", depth="all")
    out["store_hits_full"] = val("istpu_engine_state_store_hits_total",
                                 depth="full")
    out["bytes_loaded"] = val("istpu_engine_state_bytes_loaded_total")
    # and one whose state's update is a scan over the chunk (models/jamba.py)
    out.update({f"scan_{w}": val("istpu_engine_state_scan_total", what=w)
                for w in ("chunks", "full_chunks", "tokens")})
    return out


def state_counts(fn):
    """``fn`` as one profiled step: its result and the summary's ``state``
    block, which the /metrics families gained too."""
    before = scraped()
    out, summary = profiled(fn)
    after = scraped()
    assert {k: after[k] - before[k] for k in after} == summary
    return out, summary


# -- adoption: bit for bit, and a copy -----------------------------------------------

@pytest.mark.parametrize("source", ["hbm", "store"])
def test_a_prompt_from_an_adopted_checkpoint_is_bit_equal_to_it_computed(
        toy, store, source):
    """What the benchmark's paired probes hold the chip to: the prompt
    computed whole, then started from its checkpoint resident in HBM, then (on
    another engine) from the same checkpoint come back from the store: the
    same chunks through the same program, so every logit is equal."""
    prompt = tokens(3 * STRIDE + 21, 20 + (source == "store"))
    conn = connect(store)
    a = engine(toy, conn=conn, model_id=toy.model_id)
    (st, whole, out), counts = state_counts(lambda: ask(a, prompt))
    assert st.reused_chunks == 0
    assert counts["checkpoints_taken"] == counts["checkpoints_pushed"] == 1
    assert counts["bytes_pushed"] == (
        a.pc.slot_bytes) == a.transfer.wire_page_bytes * a.pc.n_layers
    a.release(st)
    b = a if source == "hbm" else engine(toy, conn=connect(store),
                                         model_id=toy.model_id)
    (st2, again, out2), counts = state_counts(lambda: ask(b, prompt))
    n = 3 * STRIDE // T
    assert st2.reused_chunks == n
    assert (st2.local_chunks, st2.store_chunks) == ((n, 0) if source == "hbm"
                                                    else (0, n))
    assert counts[f"adopted_{'local' if source == 'hbm' else 'store'}"] == 1
    assert counts["checkpoints_taken"] == counts["checkpoints_pushed"] == 0
    assert out2 == out
    for x, y in zip(whole, again):
        assert np.array_equal(x, y)
    if source == "store":
        # a store hit became resident as a computed checkpoint does
        b.release(st2)
        st3 = b.prefill(prompt)
        assert (st3.local_chunks, st3.store_chunks) == (n, 0)
        assert np.array_equal(np.asarray(st3.last_logits), whole[0])
        # and the slot itself is what was written
        src = a.slots.match(st2.chunk_keys[n - 1])
        b.cache, ok = b.transfer.guarded_load(b.cache, [3], [st2.chunk_keys[n - 1]])
        assert ok
        for x, y in zip(a.cache, b.cache):
            assert np.array_equal(np.asarray(x[src]), np.asarray(y[3]))
        a.slots.unpin(src)
    conn.close()


def test_adoption_copies(toy):
    """Decoding a row never changes the resident checkpoint it started from,
    and two rows adopted from one checkpoint go their own ways."""
    eng = engine(toy)
    doc = tokens(2 * STRIDE, 30)
    eng.release(eng.prefill(doc + tokens(7, 31)))
    (slot,) = eng.slots._by_key.values()
    kept = [np.asarray(a[slot]) for a in eng.cache]
    tails = [tokens(9, 32), tokens(30, 33)]
    alone = []
    for tail in tails:       # each tail computed whole on an engine of its own
        st, rows, _ = ask(engine(toy), doc + tail)
        alone.append(rows)
    states = [eng.prefill(doc + tail) for tail in tails]
    assert [st.local_chunks for st in states] == [2 * STRIDE // T] * 2
    assert len({st.slot for st in states} | {slot}) == 3
    rows = [[np.asarray(st.last_logits)] for st in states]
    for _ in range(3):
        eng.decode_batch(states, 1)
        for r, st in zip(rows, states):
            r.append(np.asarray(st.last_logits))
    for a, b in zip(alone, rows):
        assert np.array_equal(a[0], b[0])       # the prefill: batch one, equal
        np.testing.assert_allclose(logprobs(np.stack(a)), logprobs(np.stack(b)),
                                   atol=0.05)   # the scan at batch 2: close
    assert not np.array_equal(rows[0][1], rows[1][1])
    for before, a in zip(kept, eng.cache):
        assert np.array_equal(before, np.asarray(a[slot]))


def test_only_the_deepest_aligned_position_is_kept(toy):
    eng = engine(toy)
    short = eng.prefill(tokens(STRIDE - 1, 40))       # shorter than the stride
    exact = eng.prefill(tokens(STRIDE, 41))           # nothing follows it
    assert not eng.slots._by_key
    prompt = tokens(3 * STRIDE + 40, 42)
    (st, counts) = state_counts(lambda: eng.prefill(prompt))
    assert counts["checkpoints_taken"] == 1
    assert list(eng.slots._by_key) == [st.chunk_keys[3 * STRIDE // T - 1]]
    for s in (short, exact, st):
        eng.release(s)
    # the document alone: its checkpoint lies AT its end, deeper than
    # len - 1, so it is not adopted; no shallower one was kept: recomputed
    (doc, counts) = state_counts(lambda: eng.prefill(prompt[:3 * STRIDE]))
    assert doc.reused_chunks == 0 and counts["adopted_local"] == 0
    assert counts["shared_tokens_recomputed"] == 3 * STRIDE - 1
    assert len(eng.slots._by_key) == 2          # and keeps one at 2 x stride
    # a longer prompt over the same document starts from the deepest
    (longer, counts) = state_counts(
        lambda: eng.prefill(prompt[:3 * STRIDE] + tokens(STRIDE + 3, 43)))
    assert longer.reused_chunks == 3 * STRIDE // T
    assert counts["adopted_local"] == 1 and counts["checkpoints_taken"] == 1
    assert counts["shared_tokens_recomputed"] == 0


def test_a_stored_key_is_not_pushed_twice_and_a_failed_load_recomputes(
        toy, store, monkeypatch):
    """A store that fails in the middle of a load costs a miss and a
    recompute with equal logits, never the request; and the checkpoint it
    then reaches again is not pushed a second time."""
    conn = connect(store)
    eng = engine(toy, conn=conn, model_id=toy.model_id, max_rows=2, n_blocks=24)
    assert (eng.pc.n_slots, eng.pc.max_rows) == (3, 2)    # one resident slot
    prompt = tokens(2 * STRIDE + 11, 50)
    st, whole, _ = ask(eng, prompt)
    eng.release(st)
    eng.release(eng.prefill(tokens(STRIDE + 5, 51)))      # evicts the first
    assert st.chunk_keys[2 * STRIDE // T - 1] not in eng.slots

    def dies(*a, **kw):
        raise ConnectionError("the store went away mid-load")

    monkeypatch.setattr(eng.transfer, "fetch_pages", dies)
    (st2, again, _), counts = state_counts(lambda: ask(eng, prompt))
    assert st2.reused_chunks == 0 and st2.store_chunks == 0
    for x, y in zip(whole, again):
        assert np.array_equal(x, y)
    assert counts["checkpoints_skipped_stored"] == 1
    assert counts["checkpoints_pushed"] == 0 and counts["bytes_pushed"] == 0
    assert counts["resident_evicted"] == 1
    monkeypatch.undo()
    eng.release(st2)
    eng.release(eng.prefill(tokens(STRIDE + 6, 52)))
    eng.transfer.breaker.record_success()
    st3 = eng.prefill(prompt)
    assert st3.store_chunks == 2 * STRIDE // T
    assert np.array_equal(np.asarray(st3.last_logits), whole[0])
    conn.close()


def test_a_poisoned_slot_changes_nothing(toy):
    """What a slot held before never reaches the arithmetic: every slot NaN
    before the rows arrive, a pad row in the decode batch (3 rows in a bucket
    of 4, its slot id past the slots)."""
    prompts = [tokens(STRIDE + 9, 60), tokens(33, 61), tokens(2 * STRIDE + 1, 62)]

    def run(poison):
        eng = engine(toy)
        if poison:
            eng.cache = tuple(jnp.full_like(a, jnp.nan) for a in eng.cache)
        states = [eng.prefill(p) for p in prompts]
        outs = eng.decode_batch(states, 5)
        return outs, [np.asarray(st.last_logits) for st in states]

    clean, poisoned = run(False), run(True)
    assert clean[0] == poisoned[0]
    for x, y in zip(clean[1], poisoned[1]):
        assert np.array_equal(x, y)


# -- the slots' bookkeeping ----------------------------------------------------------

def test_resident_slots_are_lru_pinned_while_held_and_rows_come_back_once():
    slots = StateSlots(n_slots=5, max_rows=2)
    rows = [slots.take_row(), slots.take_row()]
    assert sorted(rows) == [0, 1] and slots.rows_free == 0
    with pytest.raises(MemoryError):
        slots.take_row()
    slots.free_row(rows[0])
    with pytest.raises(AssertionError):
        slots.free_row(rows[0])                 # each slot out once
    for key in "abc":
        slot = slots.keep()
        slots.register(key, slot)
        slots.unpin(slot)
    assert sorted(slots._by_key.values()) == [2, 3, 4]
    held = slots.match("a")                     # used, and pinned
    slot = slots.keep()                         # evicts b, the oldest unpinned
    slots.register("d", slot)
    assert "b" not in slots and "a" in slots and slots.evicted == 1
    pinned = [slots.match("c"), slot]           # a, c and d all held now
    assert slots.keep() is None                 # nothing to evict
    slots.unpin(held)
    assert slots.keep() == held and "a" not in slots
    assert slots.match("nope") is None and pinned[0] is not None


def test_release_and_abandon_return_the_rows_slot_once(toy):
    eng = engine(toy, max_rows=2, n_blocks=32)
    a = eng.prefill(tokens(20, 70))
    pp = eng.prefill_start(tokens(STRIDE + 40, 71))
    with pytest.raises(MemoryError):
        eng.prefill_start(tokens(5, 72))
    assert eng.free_pages == 0
    eng.prefill_step(pp)
    eng.abandon_prefill(pp)
    eng.abandon_prefill(pp)
    eng.release(a)
    eng.release(a)
    assert eng.slots.rows_free == 2 and eng.free_pages == 2 * eng.pc.n_blocks


# -- what is refused -----------------------------------------------------------------

def _merged(key, **into):
    return lambda body: body[key].update(into)


@pytest.mark.parametrize("edit, says", [
    (_merged("published", state_dim=64), "does not read"),
    (lambda body: body["published"].pop("head_dim"), "published lacks"),
    (_merged("reduced", hidden_size=64), "num_hidden_layers only"),
    (_merged("reduced", num_attention_heads=2), "num_hidden_layers only"),
    (_merged("reduced", num_hidden_layers=0), "num_hidden_layers must be in"),
    (_merged("published", tie_word_embeddings=True), "computes tie_word_embeddings=False"),
    (_merged("published", num_key_value_heads=3), "do not group"),
], ids=["unknown_width", "missing_size", "width_override", "head_override",
        "no_layers", "other_equations", "ungrouped_heads"])
def test_loader_refuses(tmp_path, edit, says):
    body = json.loads(json.dumps(family.model_file(TOY, SEED)))
    edit(body)
    path = os.path.join(tmp_path, "m.json")
    with open(path, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match=says):
        load_config_file(path)


OK_FLAGS = ["--kv-quant", "none", "--prefill-chunk", "64", "--state-stride", "128"]


@pytest.mark.parametrize("flags, says", [
    (["--kv-quant", "int8", "--prefill-chunk", "64", "--state-stride", "128"],
     "served without --kv-quant int8"),
    (OK_FLAGS + ["--tp", "2"], "served without --tp/--pp"),
    (OK_FLAGS + ["--ngram-spec"], "served without --ngram-spec"),
    (OK_FLAGS + ["--draft-model", "tiny"], "served without --draft-model"),
    (["--kv-quant", "none", "--prefill-chunk", "64"], "pass --state-stride"),
    (["--kv-quant", "none", "--prefill-chunk", "48", "--state-stride", "128"],
     "multiple of --prefill-chunk"),
    (["--kv-quant", "none", "--state-stride", "128"], "multiple of --prefill-chunk"),
    (OK_FLAGS + ["--window-blocks", "8"], "no --window-blocks"),
    (OK_FLAGS + ["--max-batch", "64"], "fewer than the 64 rows"),
], ids=["int8", "tp", "ngram", "draft", "no_stride", "stride_not_chunks",
        "no_chunk", "window_blocks", "more_rows_than_slots"])
def test_serve_refuses_at_start_up(toy, flags, says):
    from infinistore_tpu import serve

    with pytest.raises(SystemExit, match=says):
        serve.main(["--model", toy.path, "--port", "0", "--n-blocks", "64", *flags])


def test_serve_refuses_a_stride_for_a_paged_model():
    from infinistore_tpu import serve

    with pytest.raises(SystemExit, match="this model keeps pages"):
        serve.main(["--model", "tiny", "--port", "0", "--state-stride", "128"])


@pytest.mark.parametrize("what", ["int8", "mesh", "lora", "chunk", "scoring",
                                  "verify", "stride"])
def test_engine_refuses(toy, what):
    pc = StateCacheConfig.for_model(toy.cfg, 64, T, STRIDE, max_rows=4)
    make = lambda **kw: StateEngine(toy.params, toy.cfg, pc, **toy.fns,
                                    **{"prefill_chunk": CHUNK, **kw})
    if what == "int8":
        with pytest.raises(ValueError, match="a state has no such scale"):
            make(kv_quant="int8")
    elif what in ("mesh", "lora"):
        with pytest.raises(ValueError, match=f"served without {what}"):
            make(**{what: object()})
    elif what == "chunk":
        with pytest.raises(ValueError, match="multiple of prefill_chunk"):
            make(prefill_chunk=48)
    elif what == "stride":
        with pytest.raises(ValueError, match="is no multiple of it"):
            StateCacheConfig.for_model(toy.cfg, 64, T, 100, max_rows=4)
    else:
        eng = make()
        st = eng.prefill([1, 2, 3, 4, 5])
        if what == "scoring":
            with pytest.raises(ValueError, match="prompt scoring"):
                eng.prompt_logprobs([1, 2, 3])
        else:
            with pytest.raises(Exception, match="verify_fn"):
                eng.verify(st, [7, 8], 5)


# -- the counts, and the harness's reckoning ------------------------------------------

@pytest.mark.parametrize("spec", [TOY, REAL], ids=["toy", "brumby-14b-l8"])
def test_allocated_bytes_equal_the_counts(spec, tmp_path):
    """``n_blocks x block_tokens x cache_bytes_per_token`` (serve_proc.py's
    fill check) is the bytes of the slots as ``init_cache`` shapes them; a
    slot is a layer's state times the layers; the store's granule divides a
    layer's state; ``max_batch`` of the slots are rows', the rest resident."""
    counts = family.counts(spec)
    _, cfg, _ = load_config_file(model_file(str(tmp_path), spec))
    sv = spec["serve"]
    arg = lambda name: int(sv["args"][sv["args"].index(name) + 1])
    pc = StateCacheConfig.for_model(cfg, sv["n_blocks"], sv["block_tokens"],
                                    arg("--state-stride"), max_rows=8)
    shapes = jax.eval_shape(lambda: init_cache(pc))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert held == pc.cache_bytes == (sv["n_blocks"] * sv["block_tokens"]
                                      * counts.cache_bytes_per_token(spec))
    assert pc.slot_bytes == counts.slot_bytes(spec)
    assert pc.page_bytes == counts.layer_state_bytes(spec)
    blocks = spec["state"]["min_checkpoint_tokens"] // sv["block_tokens"]
    assert counts.store_page_bytes(spec, sv["block_tokens"]) * blocks == pc.page_bytes
    assert pc.n_slots - pc.max_rows == pc.n_slots - 8 > 0
    assert pc.stride % arg("--prefill-chunk") == 0
    if spec is REAL:
        assert (pc.n_slots, pc.page_bytes, pc.slot_bytes) == (16, 34080768, 272646144)
        assert counts.cache_bytes_per_token(spec) == 66564
        assert round(counts.weight_bytes(spec) / 1e9, 2) == 8.40


def test_the_reckoned_store_pool_covers_what_the_mix_pushes():
    """run.py sizes the store's pool from the tokens a run pushes; here every
    prompt of the cell's plan is walked by the engine's own rule (one
    checkpoint at the deepest multiple of the stride, a key once) and the
    bytes that pushes stay under the pool."""
    sys.path.insert(0, BENCH)
    import run

    c = run.load_cell("brumby-14b-l8.doc-reask-long", 0)
    plan = c["generate"](3700000123, 50.0)
    pool = run.pool_gib(c, [plan]) << 30
    counts, spec = c["counts"], c["config"]
    stride, slot = counts.stride(spec), counts.slot_bytes(spec)
    prompts = ([ph["requests"][0]["prompt"] for ph in plan["warm_decode"]]
               + [p["body"]["prompt"] for p in plan["probes"]]
               + [b["prompt"] for b in plan["fill"]]
               + [b["prompt"] for b in plan["warm_reask"]]
               + [s["body"]["prompt"] for s in plan["schedule"]])
    stored = {tuple(p[:(len(p) - 1) // stride * stride]) for p in prompts
              if len(p) > stride}
    assert len(stored) > 14                     # the population and the new ones
    assert len(stored) * slot < pool
    granule = max(16, counts.store_page_bytes(spec, 16) // 1024) * 1024
    assert -(-counts.layer_state_bytes(spec) // granule) * granule * 8 * len(stored) < pool


# -- what the benchmark's check has to refuse -----------------------------------------

def test_the_controls_and_a_zeroed_checkpoint_fail_the_limit(toy):
    """At tiny widths, as PERF.md section 2 sets the limit on the chip: the
    sound program under it; the reference in W8A8 int8, the reference with
    its state accumulated in bfloat16, and the program started from a
    checkpoint that was zeroed, each over it."""
    prompt = tokens(5 * STRIDE + 37, 80)
    eng = engine(toy)
    st, rows, out = ask(eng, prompt, 4)
    eng.release(st)
    given = prompt + out[:-1]
    want = np.asarray(toy.f32(toy.ref_params, given, 4))
    sound = top5_rms(logprobs(np.stack(rows[:4])), want)
    assert sound < RMS_LIMIT
    for precision in ("int8", "statebf16"):
        low = np.asarray(toy.ref.make_forward(toy.sizes, precision)(
            toy.ref_params, given, 4))
        assert top5_rms(low, want) > RMS_LIMIT, precision
    (slot,) = eng.slots._by_key.values()
    eng.cache = tuple(a.at[slot].set(0.0) for a in eng.cache)
    st, rows, out = ask(eng, prompt, 4)
    assert st.local_chunks == 5 * STRIDE // T
    want = np.asarray(toy.f32(toy.ref_params, prompt + out[:-1], 4))
    assert top5_rms(logprobs(np.stack(rows[:4])), want) > 10 * RMS_LIMIT


# -- strict durability: the acknowledgement is awaited once a step, per request ------

import strict_settle  # noqa: E402


@pytest.fixture
def settle_kit(toy, store):
    """``strict_settle``'s kit over state slots: a prompt of 150 tokens at a
    stride of 128 pushes ONE checkpoint (every layer, one push) at the end of
    its second chunk of 64, and runs a third."""
    import itertools

    conns, ids, solo = [], itertools.count(), {}

    def build(durability="strict", store_=True):
        if store_:
            conns.append(connect(store))
        return engine(
            toy, f32=True, chunk=64, max_rows=12, n_blocks=192,
            conn=conns[-1] if store_ else None, store_durability=durability,
            model_id=f"settle-{os.getpid()}-{time.time_ns()}-{next(ids)}")

    def alone(prompt, n):
        if tuple(prompt) not in solo:
            eng = build(store_=False)
            solo[tuple(prompt)] = eng.decode(eng.prefill(prompt), n)
        return solo[tuple(prompt)]

    yield types.SimpleNamespace(
        engine=lambda durability="strict", store=True: build(durability, store),
        max_batch=12, first=tokens(20, 380),
        prompts=lambda n: [tokens(150, 381 + next(ids)) for _ in range(n)],
        solo=alone, unnamed=lambda eng, prompt: True, names_pages=False)
    for c in conns:
        c.close()


@pytest.mark.parametrize("case", strict_settle.CASES,
                         ids=lambda c: c.__name__[5:])
def test_strict_settle_over_state_slots(settle_kit, case):
    case(settle_kit)


@pytest.mark.parametrize("form", strict_settle.FORMS)
def test_strict_blocking_prefill_returns_after_the_acknowledgement(
        settle_kit, form):
    strict_settle.case_blocking_forms_return_after_the_acknowledgement(
        settle_kit, form)


@pytest.mark.parametrize("mode", strict_settle.MODES)
def test_strict_burst_outputs_equal_solo_runs_and_only_strict_parks(
        settle_kit, mode):
    strict_settle.case_burst_outputs_equal_solo_runs(settle_kit, mode)
