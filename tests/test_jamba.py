"""The Mamba-1 / attention family (models/jamba.py, models/ssm_scan.py) and the
float32 state it puts into the cache of TWO KINDS (kv/cache.py
``HybridCacheConfig``, engine/hybrid_engine.py, kv/transfer.py
``HybridTransferEngine``) at a small size on the CPU: the program against the
plain reference's whole-sequence forward, by logits, and that a bfloat16 state
fails the same tolerance; chunked prefill at every boundary the conv's three
kept rows and the recurrence can meet; the kernel in interpret mode against
the plain scan; a prompt that starts from pages AND a float32 checkpoint (from
HBM, from the store) bit for bit the prompt computed whole, and what a hit
becomes when one of the two kinds is gone; that pages and slots come back;
what the loader, ``serve`` and the engine refuse; the counts against what is
allocated; and the controls the benchmark's check has to refuse."""

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
from infinistore_tpu.engine.hybrid_engine import HybridEngine
from infinistore_tpu.kv.cache import HybridCacheConfig, cache_kind, init_cache
from infinistore_tpu.models import family_of, load_config_file
from infinistore_tpu.models import jamba as M
from infinistore_tpu.models import ssm_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "harness"))
import family  # noqa: E402

TOY = json.load(open(os.path.join(BENCH, "configs", "jamba-toy.json")))
REAL = json.load(open(os.path.join(BENCH, "configs", "jamba2-3b.json")))
LFM2_TOY = json.load(open(os.path.join(BENCH, "configs", "lfm2-moe-toy.json")))
LFM2_REAL = json.load(open(os.path.join(BENCH, "configs", "lfm2-24b-a2b-l10.json")))
SEED = 11
T, STRIDE, CHUNK = 16, 32, 32
# Log-probabilities of the program in float32 against the float32 reference:
# one function computed twice (chunks and a carried state against the whole
# sequence token by token, states on the sublanes against the published
# [channels, states]), so what is left is the order of float32 sums.  Read
# 1.3e-5 to 4e-5; the same program with its state rounded to bfloat16 between
# tokens reads 3e-3 and more, and fails it
F32_TOL = 2e-4
# The served type (bfloat16 weights and activations, float32 state) against
# the float32 reference, RMS over the top-5 log-probabilities as run.py takes
# it, on the probe below: see the test for the readings
RMS_LIMIT = 0.06


def model_file(tmp_path, spec, seed=SEED):
    path = os.path.join(tmp_path, "model.json")
    with open(path, "w") as f:
        json.dump(family.model_file(spec, seed), f)
    return path


def layers_of(cfg, params):
    """The program's stacked Mamba layers and its attention layers as one dict
    a layer, in the stack's order, as the reference holds them."""
    mamba = iter(range(len(cfg.state_layers)))
    attn = iter(params["attn"])
    return [next(attn) if t == "attention" else
            jax.tree.map(lambda x, i=next(mamba): x[i], params["mamba"])
            for t in cfg.layer_types]


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
    pc = HybridCacheConfig.for_model(cfg, n_blocks, T, stride, max_rows=max_rows)
    return HybridEngine(toy.params32 if f32 else toy.params, cfg, pc,
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


def probe(prompt, rows, out):
    """What run.py hands the reference of one probe."""
    top = []
    for row in rows[:len(out)]:
        lp = logprobs(row)
        top.append({int(t): float(lp[t]) for t in np.argsort(lp)[-5:]})
    return {"prompt": list(prompt), "ids": [int(t) for t in out], "top": top}


def checked(toy, probes):
    """The benchmark's comparison (serve_proc.py): the RMS."""
    ref = toy.ref.reference_logprobs(toy.f32, toy.ref_params, probes)
    return toy.ref.compare(probes, ref), ref


def at_start(eng):
    """Every page and every row's slot is back."""
    return (eng.pages.available == eng.pc.n_blocks
            and eng.slots.rows_free == eng.pc.max_rows)


def slot_state(eng, slot, ci):
    """``(s [d_state, d_inner], conv rows [d_conv - 1, d_inner])`` of one Mamba
    layer in one slot."""
    return M._split_state(eng.cfg, jnp.asarray(eng.cache[1][slot, ci]).reshape(-1))


# -- the model against its plain reference --------------------------------------

def test_reference_draws_what_the_program_draws(toy):
    mine_all = layers_of(toy.cfg, toy.params)
    assert len(mine_all) == len(toy.ref_params["layers"]) == 16
    assert toy.cfg.page_layers == (3, 11) and len(toy.cfg.state_layers) == 14
    for mine, theirs in zip(mine_all, toy.ref_params["layers"]):
        assert set(mine) == set(theirs)
        for k in mine:
            assert mine[k].dtype == theirs[k].dtype, k
            # the program holds A_log with the states on the sublanes
            got = np.asarray(mine[k], np.float32)
            assert np.array_equal(got.T if k == "a_log" else got,
                                  np.asarray(theirs[k], np.float32)), k
    for k in ("embed", "ln_out"):
        assert np.array_equal(np.asarray(toy.params[k], np.float32),
                              np.asarray(toy.ref_params[k], np.float32)), k
    assert "lm_head" not in toy.params          # the head is the embedding
    # seeded as the family initialises them: A = -(n + 1), a step in
    # [0.001, 0.1] whose inverse softplus is b_dt, float32
    lw = mine_all[0]
    assert lw["a_log"].dtype == lw["b_dt"].dtype == lw["d_skip"].dtype == jnp.float32
    np.testing.assert_allclose(-np.exp(np.asarray(lw["a_log"]))[:, 0],
                               -np.arange(1, 17), rtol=1e-6)
    step = np.asarray(jax.nn.softplus(lw["b_dt"]))
    assert 0.001 <= step.min() and step.max() <= 0.1 * (1 + 1e-5)
    assert step.max() / step.min() > 20             # log-uniform over the range


@pytest.mark.parametrize("lengths", [
    (1,), (2,), (STRIDE - 9,), (STRIDE,), (5 * STRIDE + 7,),
    (3 * STRIDE + 5, 41, 2 * STRIDE)],
    ids=["one_token", "two_tokens", "shorter", "equal", "several_strides",
         "batch_of_unequal_lengths"])
def test_prefill_then_decode_through_the_cache_against_the_reference(toy, lengths):
    """Through the engine's pages and slots (chunked prefill with the state
    carried across chunks, a checkpoint every stride, the decode scan over a
    padded batch that moves each row's state), in float32, against the
    reference's forward over the whole sequence token by token: prompts
    shorter than the conv's reach of three, shorter than, equal to and several
    times the stride, alone and three rows of unequal lengths."""
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
    assert at_start(eng)


def test_a_state_held_in_bfloat16_fails_the_tolerance(toy, monkeypatch):
    """The tolerance tells a float32 state from one a precision below: the
    same program, float32 weights and activations, with the recurrence's state
    rounded to bfloat16 after every token (in the chunk's scan and in the
    decode step) is out of it by an order of magnitude; so is the reference's
    own ``statebf16`` control against its float32 self."""
    prompt = tokens(3 * STRIDE + 5, 15)

    def rounded_step(s, x, dt, B, C, At):
        y, s = ssm_scan.selective_step(s, x, dt, B, C, At)
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.sum(s * C[..., :, None], axis=-2), s

    def rounded_scan(x, dt, B, C, At, s0):
        def one(s, xs):
            y, s = rounded_step(s, *xs, At)
            return s, y
        s, y = jax.lax.scan(one, s0, (x, dt, B, C))
        return y, s

    monkeypatch.setattr(M, "selective_scan", rounded_scan)
    monkeypatch.setattr(M, "selective_step", rounded_step)
    # the engine's jitted forwards are shared by (function, config): another
    # eps makes these programs this test's own
    cfg = dataclasses.replace(toy.cfg32, norm_eps=1.0000001e-6)
    pc = HybridCacheConfig.for_model(cfg, 64, T, STRIDE, max_rows=4)
    eng = HybridEngine(toy.params32, cfg, pc, prefill_chunk=CHUNK,
                       decode_chunk=4, **toy.fns)
    st, rows, _ = ask(eng, prompt)
    want = np.asarray(toy.f32(toy.ref_params, st.tokens[:-1], 3))
    assert np.abs(logprobs(np.stack(rows[:3])) - want).max() > 10 * F32_TOL
    low = np.asarray(toy.ref.make_forward(toy.sizes, "statebf16")(
        toy.ref_params, st.tokens[:-1], 3))
    assert np.abs(low - want).max() > 10 * F32_TOL


@pytest.mark.parametrize("n, chunk", [
    (2 * CHUNK + 1, CHUNK), (2 * CHUNK + 2, CHUNK), (2 * CHUNK + 3, CHUNK),
    (3 * CHUNK, CHUNK), (CHUNK + 5, CHUNK), (1, CHUNK), (2, CHUNK),
    (4 * CHUNK + 19, 2 * CHUNK)],
    ids=["one_past_a_boundary", "two_past_a_boundary", "three_past_a_boundary",
         "whole_chunks", "padded_last_chunk", "one_token", "two_tokens",
         "chunks_of_two_strides"])
def test_chunked_prefill_equals_unchunked(toy, n, chunk):
    """A chunk boundary inside the conv's reach (the first one to three tokens
    of a chunk read rows the chunk before left), a padded last chunk, prompts
    shorter than the three rows kept: the logits and the next three steps
    against the same prompt in ONE chunk, and the row's state in every Mamba
    layer against the other's: a padded position that entered the recurrence
    or the kept rows would show there."""
    prompt = tokens(n, 40 + n)
    whole = engine(toy, f32=True, chunk=8 * CHUNK, stride=8 * CHUNK, n_blocks=128)
    parts = engine(toy, f32=True, chunk=chunk, stride=2 * CHUNK)
    (sw, a, _), (sp, b, _) = ask(whole, prompt), ask(parts, prompt)
    assert sp.chunks == -(-(-(-n // T) * T) // chunk) and sw.chunks == 1
    np.testing.assert_allclose(logprobs(np.stack(a)), logprobs(np.stack(b)),
                               atol=F32_TOL)
    for ci in range(len(toy.cfg.state_layers)):
        (s1, r1), (s2, r2) = slot_state(whole, sw.slot, ci), slot_state(parts, sp.slot, ci)
        np.testing.assert_allclose(s1, s2, atol=1e-4)
        np.testing.assert_allclose(r1, r2, atol=1e-4)
    # the first Mamba layer's kept rows are the conv's inputs at the last
    # three positions (zeros before the sequence's start), by the reference's
    # own first projection
    lw = toy.ref_params["layers"][0]
    x = toy.ref_params["embed"][jnp.asarray(sp.tokens)].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        a0 = toy.ref.rmsnorm(x, lw["ln_attn"].astype(jnp.float32), toy.sizes["eps"])
        xin = np.asarray(a0 @ lw["w_in"].astype(jnp.float32))[:, :toy.sizes["di"]]
    want = np.zeros((3, toy.sizes["di"]), np.float32)
    want[max(0, 3 - len(xin)):] = xin[-3:]
    np.testing.assert_allclose(slot_state(parts, sp.slot, 0)[1], want, atol=1e-4)


def test_a_rows_output_is_its_own_whatever_it_is_batched_with(toy):
    """A row decoded alone, and beside two others and a pad row (a batch of 3
    is padded to 4: the pad row's slot lies past the slots): the same tokens,
    the same state to float32 rounding; and no resident checkpoint or other
    row's slot is touched by the pad row's write."""
    eng = engine(toy, f32=True)
    prompts = [tokens(n, 60 + i) for i, n in enumerate((70, 33, 50))]
    alone = engine(toy, f32=True)
    st = alone.prefill(prompts[0])
    out = alone.decode(st, 4)
    states = [eng.prefill(p) for p in prompts]
    before = np.asarray(eng.cache[1])
    outs = eng.decode_batch(states, 4)
    assert outs[0] == out
    np.testing.assert_allclose(np.asarray(states[0].last_logits),
                               np.asarray(st.last_logits), atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(eng.cache[1][states[0].slot]),
        np.asarray(alone.cache[1][st.slot]), atol=1e-5)
    after = np.asarray(eng.cache[1])
    rows = [s.slot for s in states]
    others = [i for i in range(eng.pc.n_slots) if i not in rows]
    assert np.array_equal(before[others], after[others])
    assert not np.array_equal(before[rows], after[rows])


# -- the selective scan ------------------------------------------------------------

def scan_inputs(T_, ch, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (T_, ch)),
            jax.nn.softplus(jax.random.normal(k[1], (T_, ch)) - 3.0),
            jax.random.normal(k[2], (T_, N)), jax.random.normal(k[3], (T_, N)),
            -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                              (N, ch)),
            jax.random.normal(k[4], (N, ch)))


@pytest.mark.parametrize("T_, ch", [(256, 1024), (64, 128), (16, 256), (384, 1536)],
                         ids=["two_token_blocks", "one_small_block",
                              "a_padded_tail", "three_blocks_each_way"])
def test_the_scan_kernel_in_interpret_mode_equals_the_plain_scan(T_, ch):
    """The TPU's kernel run by Pallas' interpreter on the CPU against the
    ``lax.scan`` over tokens, ``y`` and the state, to float32 rounding (the
    same expression in the same order: read 0.0 here and on the chip); twice
    the same bits; and the state carried across two calls equals one call."""
    a = scan_inputs(T_, ch, 16, seed=T_)
    assert ssm_scan.kernel_engages(T_, ch, 16)
    y0, s0 = jax.jit(ssm_scan.selective_scan_plain)(*a)
    y1, s1 = ssm_scan.selective_scan_kernel(*a, interpret=True)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-5)
    y2, s2 = ssm_scan.selective_scan_kernel(*a, interpret=True)
    assert np.array_equal(y1, y2) and np.array_equal(s1, s2)
    h = T_ // 2
    if ssm_scan.kernel_engages(h, ch, 16):
        ya, sa = ssm_scan.selective_scan_kernel(
            *(x[:h] for x in a[:4]), a[4], a[5], interpret=True)
        yb, sb = ssm_scan.selective_scan_kernel(
            *(x[h:] for x in a[:4]), a[4], sa, interpret=True)
        np.testing.assert_allclose(np.concatenate([ya, yb]), y1, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(sb, s1, rtol=1e-6, atol=1e-6)


def test_a_token_with_a_zero_step_is_the_identity_of_the_recurrence():
    """``dt = 0`` at a position leaves the state as it was whatever ``x``, ``B``
    and ``C`` hold there: what keeps a padded tail out of a state."""
    x, dt, B, C, At, s0 = scan_inputs(32, 128, 16, seed=5)
    dt = dt.at[20:].set(0.0)
    _, s_cut = ssm_scan.selective_scan_plain(x[:20], dt[:20], B[:20], C[:20],
                                             At, s0)
    padded = (x.at[20:].set(1e3), dt, B.at[20:].set(-7.0), C, At, s0)
    assert np.array_equal(ssm_scan.selective_scan_plain(*padded)[1], s_cut)
    np.testing.assert_allclose(
        ssm_scan.selective_scan_kernel(*padded, interpret=True)[1], s_cut,
        rtol=1e-6)
    # sizes that are no whole tiles keep the plain form
    assert not ssm_scan.kernel_engages(20, 128, 16)
    assert not ssm_scan.kernel_engages(32, 96, 16)


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


def state_counts(fn):
    """``fn`` as one profiled step: its result and the summary's ``state``."""
    from infinistore_tpu.engine.stepprof import StepProfiler
    from infinistore_tpu.utils.metrics import MetricsRegistry

    prof = StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    with prof.step():
        out = fn()
    return out, prof.summary()["state"]


_IDS = iter(range(10**6))


def fresh_id(toy):
    return f"{toy.model_id}-{os.getpid()}-{time.time_ns()}-{next(_IDS)}"


# -- the hybrid hit -------------------------------------------------------------------

@pytest.mark.parametrize("source", ["hbm", "store"])
def test_a_prompt_from_pages_and_a_checkpoint_is_bit_equal_to_it_computed(
        toy, store, source):
    """What the benchmark's paired probes hold the chip to: the prompt
    computed whole, then started from its pages and its float32 checkpoint
    resident in HBM, then (on another engine) from the same come back from the
    store: the same chunks through the same program, so every logit is equal;
    and the checkpoint went to the store and came back WITHOUT A CAST: float32
    there, the same bits here."""
    prompt = tokens(3 * STRIDE + 21, 20 + (source == "store"))
    conn, mid = connect(store), fresh_id(toy)
    a = engine(toy, conn=conn, model_id=mid)
    (st, whole, out), counts = state_counts(lambda: ask(a, prompt))
    assert st.reused_chunks == 0 and st.slot >= 0 and len(st.block_ids) == 8
    # a checkpoint at every stride the prefill passed, each pushed once
    assert counts["checkpoints_taken"] == counts["checkpoints_pushed"] == 3
    assert counts["bytes_pushed"] == 3 * a.pc.slot_bytes
    # one float32 width a layer: s [16, 128] and the conv's 3 rows of 128
    assert a.pc.slot_dtype == jnp.float32 and a.cache[1].dtype == jnp.float32
    assert a.pc.slot_bytes == 14 * a.pc.state_bytes == 14 * (16 + 3) * 128 * 4
    # the chunks' scans were counted: 7 chunks of 32 (the last one padded)
    assert (counts["scan_chunks"], counts["scan_full_chunks"],
            counts["scan_tokens"]) == (4, 4, 4 * CHUNK)
    push = a.transfer.push_totals
    n_complete = len(prompt) // T
    assert push["bytes"] == (n_complete * 2 * a.pc.page_bytes
                             + 3 * a.pc.slot_bytes)
    a.release(st)
    b = a if source == "hbm" else engine(toy, conn=connect(store), model_id=mid)
    (st2, again, out2), counts = state_counts(lambda: ask(b, prompt))
    n = 3 * STRIDE // T
    assert st2.reused_chunks == n
    assert (st2.local_chunks, st2.store_chunks) == ((n, 0) if source == "hbm"
                                                    else (0, n))
    assert counts[f"adopted_{'local' if source == 'hbm' else 'store'}"] == 1
    assert counts["bytes_loaded"] == (0 if source == "hbm" else b.pc.slot_bytes)
    # the pages matched one chunk past the third stride: recomputed, counted
    assert counts["shared_tokens_recomputed"] == T
    assert (counts["store_hits"], counts["store_hits_full"]) == (
        (0, 0) if source == "hbm" else (1, 1))
    assert out2 == out
    for x, y in zip(whole, again):
        assert np.array_equal(x, y)
    if source == "store":
        # the checkpoint that came back is what was kept, bit for bit, and is
        # resident now as a computed one is
        key = st2.chunk_keys[n - 1]
        src, dst = a.slots.match(key), b.slots.match(key)
        kept, back = np.asarray(a.cache[1][src]), np.asarray(b.cache[1][dst])
        assert kept.dtype == back.dtype == np.float32
        assert np.array_equal(kept.view(np.uint32), back.view(np.uint32))
        assert np.abs(kept).max() > 0
        a.slots.unpin(src), b.slots.unpin(dst)
        # on the wire as in HBM: the store's bytes of one layer's state are
        # the slot's float32 bytes
        from infinistore_tpu.kv.hashing import layer_key

        li = a.pc.state_layers[5]
        wire = np.zeros(a.pc.state_bytes, np.uint8)
        conn.register_mr(wire.ctypes.data, wire.nbytes)
        conn.read_cache([(layer_key(key, li), 0)], a.pc.state_bytes,
                        wire.ctypes.data)
        assert np.array_equal(wire.view(np.float32), kept[5].reshape(-1))
        # and so are the pages
        for x in st.chunk_keys[:n]:
            pa = a.pages._key_to_block[x]
            pb = b.pages._key_to_block[x]
            assert np.array_equal(np.asarray(a.cache[0][:, :, :, pa]),
                                  np.asarray(b.cache[0][:, :, :, pb]))
    b.release(st2)
    assert at_start(a) and at_start(b)
    conn.close()


def test_the_scan_and_the_load_are_counted_in_metrics_as_in_the_summary(toy, store):
    """What the benchmark's new readers read, in both sinks: the chunks and
    tokens through the scan, and the bytes of a checkpoint that came back."""
    from infinistore_tpu.utils.metrics import default_registry

    reg = default_registry()
    read = lambda: {
        "scan_chunks": reg.family_value("istpu_engine_state_scan_total",
                                        {"what": "chunks"}) or 0,
        "scan_full_chunks": reg.family_value("istpu_engine_state_scan_total",
                                             {"what": "full_chunks"}) or 0,
        "scan_tokens": reg.family_value("istpu_engine_state_scan_total",
                                        {"what": "tokens"}) or 0,
        "bytes_loaded": reg.family_value(
            "istpu_engine_state_bytes_loaded_total", {}) or 0}
    conn, mid = connect(store), fresh_id(toy)
    prompt = tokens(2 * STRIDE + 5, 97)
    a = engine(toy, conn=conn, model_id=mid)
    a.release(a.prefill(prompt))
    b = engine(toy, conn=connect(store), model_id=mid)
    before = read()
    (st, _, _), counts = state_counts(lambda: ask(b, prompt))
    gained = {k: v - before[k] for k, v in read().items()}
    assert gained == {k: counts[k] for k in gained}
    assert gained == {"scan_chunks": 1, "scan_full_chunks": 0, "scan_tokens": T,
                      "bytes_loaded": b.pc.slot_bytes}
    b.release(st)
    conn.close()


def drop_checkpoints(eng, keys):
    """Forget the resident checkpoints under ``keys`` (as an eviction does)."""
    for k in keys:
        slot = eng.slots._by_key.pop(k)
        eng.slots._free.append(slot)


def drop_pages(eng, keys):
    """Forget the resident pages under ``keys`` (as a reclaim does)."""
    for k in keys:
        bid = eng.pages._key_to_block.pop(k)
        del eng.pages._block_key[bid]
        eng.pages._cached.pop(bid)
        eng.pages.alloc.free([bid])


def delete_from_store(conn, eng, keys, layers):
    from infinistore_tpu.kv.hashing import layer_key

    conn.delete_keys([layer_key(k, li) for k in keys for li in layers])


@pytest.mark.parametrize("gone", [
    "resident_checkpoint", "stored_checkpoint_too", "resident_page",
    "both_everywhere", "stored_pages_of_the_last_stride"])
def test_a_hit_is_the_deepest_position_at_which_both_exist(toy, store, gone):
    """3 strides and a tail computed and pushed, then asked again with
    something missing.  The deepest checkpoint gone from HBM: it comes from
    the store and the hit is whole.  Gone from the store too: the hit is the
    next shallower stride.  A page gone from HBM: the pages from there on come
    from the store.  Pages and checkpoints gone everywhere: a miss that
    recomputes.  The last stride's pages gone from the store and from HBM: the
    hit ends where the pages end.  The logits are the computed prompt's every
    time."""
    prompt = tokens(3 * STRIDE + 21, 90)
    conn, mid = connect(store), fresh_id(toy)
    eng = engine(toy, conn=conn, model_id=mid)
    st, whole, out = ask(eng, prompt)
    keys, per = list(st.chunk_keys), STRIDE // T
    eng.release(st)
    n = 3 * per
    if gone == "resident_checkpoint":
        drop_checkpoints(eng, [keys[n - 1]])
        want = dict(reused=n, local=n, adopted="store", again=T)
    elif gone == "stored_checkpoint_too":
        drop_checkpoints(eng, [keys[n - 1]])
        delete_from_store(conn, eng, [keys[n - 1]], eng.pc.state_layers)
        want = dict(reused=2 * per, local=2 * per, adopted="local",
                    again=STRIDE + T)
    elif gone == "resident_page":
        drop_pages(eng, keys[per + 1:n])
        want = dict(reused=n, local=per + 1, adopted="local", again=T)
    elif gone == "both_everywhere":
        drop_pages(eng, keys[:n])
        drop_checkpoints(eng, [keys[c * per - 1] for c in (1, 2, 3)])
        delete_from_store(conn, eng, keys[:n], range(eng.pc.n_layers))
        want = dict(reused=0, local=0, adopted=None, again=0)
    else:
        drop_pages(eng, keys[2 * per:n])
        delete_from_store(conn, eng, keys[2 * per + 1:n], eng.pc.page_layers)
        want = dict(reused=2 * per, local=2 * per, adopted="local", again=T)
    (st2, again, out2), counts = state_counts(lambda: ask(eng, prompt))
    assert st2.reused_chunks == want["reused"]
    assert st2.local_chunks == want["local"]
    assert st2.store_chunks == want["reused"] - want["local"]
    for src in ("local", "store"):
        assert counts[f"adopted_{src}"] == int(want["adopted"] == src)
    assert counts["shared_tokens_recomputed"] == want["again"]
    assert out2 == out
    for x, y in zip(whole, again):
        assert np.array_equal(x, y)
    eng.release(st2)
    assert at_start(eng)
    conn.close()


def test_a_load_that_fails_costs_a_shallower_hit_and_never_a_request(toy, store):
    """The store holds pages and checkpoints the engine no longer does, and the
    load fails under it (a state layer's key vanishes between lookup and
    load): the hit falls back to what HBM holds of both, every page taken for
    the failed load goes back, the slots are untouched, and the answer is the
    computed prompt's; a store that is down altogether is a miss."""
    prompt = tokens(3 * STRIDE + 21, 91)
    conn, mid = connect(store), fresh_id(toy)
    eng = engine(toy, conn=conn, model_id=mid)
    st, whole, out = ask(eng, prompt)
    keys, per = list(st.chunk_keys), STRIDE // T
    eng.release(st)
    drop_pages(eng, keys[2 * per:3 * per])
    drop_checkpoints(eng, [keys[3 * per - 1]])
    lookup = eng.transfer.lookup_prefix

    def lookup_then_lose(chunk_keys_, states=False):
        n = lookup(chunk_keys_, states=states)
        if states:      # the checkpoint is there when asked for, gone when read
            delete_from_store(conn, eng, [keys[3 * per - 1]],
                              eng.pc.state_layers[3:4])
        return n

    eng.transfer.lookup_prefix = lookup_then_lose
    (st2, again, out2), counts = state_counts(lambda: ask(eng, prompt))
    assert (st2.reused_chunks, st2.local_chunks) == (2 * per, 2 * per)
    assert counts["adopted_local"] == 1 and counts["adopted_store"] == 0
    assert counts["bytes_loaded"] == 0
    assert (counts["store_hits"], counts["store_hits_full"]) == (1, 0)
    assert out2 == out
    for x, y in zip(whole, again):
        assert np.array_equal(x, y)
    eng.release(st2)
    assert at_start(eng)
    eng2 = engine(toy, conn=connect(store), model_id=mid)
    eng2.transfer.breaker.record_failure = lambda: None
    eng2.transfer._call = lambda *a, **k: (_ for _ in ()).throw(OSError("down"))
    st3, rows3, out3 = ask(eng2, prompt)
    assert st3.reused_chunks == 0 and out3 == out
    eng2.release(st3)
    assert at_start(eng2)
    conn.close()


def test_adoption_copies_and_pages_beyond_the_hit_are_not_written(toy):
    """Decoding a row never changes the resident checkpoint it started from
    nor the shared pages it adopted, and two rows adopted from one document go
    their own ways."""
    eng = engine(toy)
    doc = tokens(2 * STRIDE, 30)
    first = eng.prefill(doc + tokens(7, 31))
    keys = first.chunk_keys[:4]
    eng.release(first)
    slot = eng.slots._by_key[keys[3]]
    kept = np.asarray(eng.cache[1][slot])
    shared = [eng.pages._key_to_block[k] for k in keys]
    pages = np.asarray(eng.cache[0][:, :, :, shared])
    tails = [tokens(9, 32), tokens(30, 33)]
    alone = [ask(engine(toy), doc + tail)[1] for tail in tails]
    states = [eng.prefill(doc + tail) for tail in tails]
    assert [st.local_chunks for st in states] == [4, 4]
    assert [st.block_ids[:4] for st in states] == [shared, shared]
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
    assert np.array_equal(kept, np.asarray(eng.cache[1][slot]))
    assert np.array_equal(pages, np.asarray(eng.cache[0][:, :, :, shared]))
    for st in states:
        eng.release(st)
    assert at_start(eng)


def test_pages_and_slots_come_back_after_release_abandon_and_exhaustion(toy):
    eng = engine(toy, max_rows=2, n_blocks=16)
    a = eng.prefill(tokens(20, 70))
    pp = eng.prefill_start(tokens(STRIDE + 40, 71))
    assert eng.free_pages == 0                     # both rows' slots are out
    with pytest.raises(MemoryError, match="state slots"):
        eng.prefill_start(tokens(5, 72))
    eng.prefill_step(pp)
    eng.abandon_prefill(pp)                        # a preempted or shed row
    eng.abandon_prefill(pp)
    assert eng.slots.rows_free == 1
    with pytest.raises(MemoryError, match="KV pages"):
        eng.prefill_start(tokens(16 * T, 73))      # more pages than are left
    assert eng.slots.rows_free == 1                # its row went back
    eng.release(a)
    eng.release(a)
    assert at_start(eng) and eng.free_pages == eng.pc.n_blocks


# -- what is refused -----------------------------------------------------------------

def _merged(key, **into):
    return lambda body: body[key].update(into)


@pytest.mark.parametrize("edit, says", [
    (lambda b: b["published"].pop("mamba_d_state"), "published lacks"),
    (_merged("published", head_dim=64), "does not read"),
    (_merged("published", num_experts=16), "expert layers of the larger models"),
    (_merged("published", mamba_conv_bias=False), "mamba_conv_bias=True only"),
    (_merged("published", tie_word_embeddings=False),
     "tie_word_embeddings=True only"),
    (_merged("published", attn_layer_offset=9), "leave no attention layer"),
    (_merged("published", attn_layer_offset=0), "follows a Mamba layer"),
    (_merged("reduced", num_hidden_layers=8), "published depth, uncut"),
    (_merged("published", num_attention_heads=3), "3 query heads over"),
], ids=["missing_size", "unknown_key", "experts", "conv_bias", "untied_head",
        "no_attention_layer", "attention_first", "reduced_depth", "heads"])
def test_loader_refuses(tmp_path, edit, says):
    body = json.loads(json.dumps(family.model_file(TOY, SEED)))
    edit(body)
    path = os.path.join(str(tmp_path), "m.json")
    with open(path, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match=says):
        load_config_file(path)


def test_loader_reads_the_published_file_uncut(tmp_path):
    mid, cfg, _ = load_config_file(model_file(str(tmp_path), REAL))
    assert cfg.n_layers == 28 and "-l28-" in mid and mid.startswith("jamba2-3b-")
    assert cfg.page_layers == (7, 21) and len(cfg.state_layers) == 26
    assert cfg.attn_follows == (0,) * 6 + (1,) + (0,) * 12 + (2,) + (0,) * 6
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2560, 20, 1, 128)
    assert (cfg.ffn_dim, cfg.vocab_size, cfg.norm_eps) == (8192, 65536, 1e-6)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) == (5120, 16, 4, 160)
    assert cfg.kv_page == (2, 1, 128) and cfg.state_width == 19 * 5120
    assert cache_kind(cfg) == "hybrid"
    # every published key of the catalog's row is the file's, unchanged, and
    # nothing is cut
    assert REAL["model"]["published"] == {
        k: REAL[k] for k in REAL["model"]["published"]}
    assert REAL["model"]["reduced"] == {} and REAL["reduced_keys"] == []
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == [] and family.cut_problems(entry, REAL) == []


def test_the_kind_of_cache_is_asked_of_the_config(tmp_path):
    """``serve`` picks the engine by ``cache_kind``: the dense preset keeps
    pages, the retention family a state, and both hybrid families give the
    kind's names."""
    from infinistore_tpu.models import TINY

    assert cache_kind(TINY) == "pages"
    ret = json.load(open(os.path.join(BENCH, "configs", "retention-toy.json")))
    for spec, kind in ((ret, "state"), (LFM2_TOY, "hybrid"), (TOY, "hybrid")):
        _, cfg, _ = load_config_file(model_file(str(tmp_path), spec))
        assert cache_kind(cfg) == kind
        if kind == "hybrid":
            assert set(cfg.page_layers) | set(cfg.state_layers) == set(
                range(cfg.n_layers))
            assert cfg.state_width > 0 and cfg.kv_page[0] == 2


OK_FLAGS = ["--kv-quant", "none", "--prefill-chunk", "64", "--state-stride", "128"]


@pytest.mark.parametrize("flags, says", [
    (["--kv-quant", "int8", "--prefill-chunk", "64", "--state-stride", "128"],
     "served without --kv-quant int8"),
    (OK_FLAGS + ["--tp", "2"], "served without --tp/--pp"),
    (OK_FLAGS + ["--ngram-spec"], "served without --ngram-spec"),
    (OK_FLAGS + ["--draft-model", "tiny"], "served without --draft-model"),
    (["--kv-quant", "none", "--prefill-chunk", "64"],
     "keeps pages for its attention layers and a state for the others: "
     "pass --state-stride"),
    (["--kv-quant", "none", "--prefill-chunk", "48", "--state-stride", "128"],
     "multiple of --prefill-chunk"),
    (OK_FLAGS + ["--window-blocks", "8"], "no --window-blocks"),
    (OK_FLAGS + ["--max-batch", "64"], "fewer than the 64 rows"),
], ids=["int8", "tp", "ngram", "draft", "no_stride", "stride_not_chunks",
        "window_blocks", "more_rows_than_slots"])
def test_serve_refuses_at_start_up(toy, flags, says):
    from infinistore_tpu import serve

    with pytest.raises(SystemExit, match=says):
        serve.main(["--model", toy.path, "--port", "0", "--n-blocks", "64", *flags])


def test_serve_refuses_expert_layers_in_words(tmp_path):
    from infinistore_tpu import serve

    body = json.loads(json.dumps(family.model_file(TOY, SEED)))
    body["published"].update(num_experts=16, num_experts_per_tok=2)
    path = os.path.join(str(tmp_path), "moe.json")
    with open(path, "w") as f:
        json.dump(body, f)
    with pytest.raises((SystemExit, ValueError), match="num_experts=16"):
        serve.main(["--model", path, "--port", "0", "--n-blocks", "64", *OK_FLAGS])


@pytest.mark.parametrize("what", ["int8", "mesh", "lora", "chunk", "scoring",
                                  "adopt", "draft", "stride", "cluster"])
def test_engine_refuses(toy, what):
    pc = HybridCacheConfig.for_model(toy.cfg, 64, T, STRIDE, max_rows=4)
    make = lambda **kw: HybridEngine(toy.params, toy.cfg, pc, **toy.fns,
                                     **{"prefill_chunk": CHUNK, **kw})
    if what == "int8":
        with pytest.raises(ValueError, match="a state has no such scale"):
            make(kv_quant="int8")
    elif what in ("mesh", "lora"):
        with pytest.raises(ValueError, match=f"served without {what}"):
            make(**{what: object()})
    elif what == "cluster":
        from infinistore_tpu.cluster import RoutedStorePool

        with pytest.raises(ValueError, match="ONE store connection"):
            make(conn=object.__new__(RoutedStorePool))
    elif what == "chunk":
        with pytest.raises(ValueError, match="multiple of prefill_chunk"):
            make(prefill_chunk=48)
    elif what == "stride":
        with pytest.raises(ValueError, match="is no multiple of it"):
            HybridCacheConfig.for_model(toy.cfg, 64, T, 100, max_rows=4)
    else:
        eng = make()
        st = eng.prefill([1, 2, 3, 4, 5])
        if what == "scoring":
            with pytest.raises(ValueError, match="prompt scoring"):
                eng.prompt_logprobs([1, 2, 3])
        elif what == "adopt":
            with pytest.raises(ValueError, match="keep a state too"):
                eng.adopt_prefill([1, 2], None, None)
        else:
            with pytest.raises(ValueError, match="drafts nothing"):
                eng.propose(st, 2)


# -- the counts, and the harness's reckoning ------------------------------------------

@pytest.mark.parametrize("spec", [TOY, REAL], ids=["toy", "jamba2-3b"])
def test_allocated_bytes_equal_the_counts(spec, tmp_path):
    """``n_blocks x block_tokens x cache_bytes_per_token`` (serve_proc.py's
    fill check) is the bytes of the pages (the ATTENTION layers alone) and of
    the float32 slots as ``init_cache`` shapes them; the weights as ``init``
    shapes them; what a block sends to the store, both kinds."""
    counts = family.counts(spec)
    _, cfg, _ = load_config_file(model_file(str(tmp_path), spec))
    sv = spec["serve"]
    stride = counts.stride(spec)
    pc = HybridCacheConfig.for_model(cfg, sv["n_blocks"], sv["block_tokens"],
                                     stride, max_rows=8)
    shapes = jax.eval_shape(lambda: init_cache(pc))
    nbytes = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    n_attn, n_state = len(cfg.page_layers), len(cfg.state_layers)
    assert shapes[0].shape[0] == n_attn == len(pc.page_layers)
    # a layer's width as rows of one lane tile: the layer axis is no tiled axis
    assert shapes[1].shape == (pc.n_slots, n_state, cfg.state_width // 128, 128)
    assert shapes[1].dtype == jnp.float32 and shapes[0].dtype == jnp.bfloat16
    assert pc.pools == ((cfg.page_layers, sv["n_blocks"]),)
    assert pc.cache_bytes == nbytes(shapes) == (
        sv["n_blocks"] * sv["block_tokens"] * counts.cache_bytes_per_token(spec))
    assert counts.layer_state_bytes(spec) == pc.state_bytes
    assert counts.slot_bytes(spec) == pc.slot_bytes
    weights = jax.eval_shape(
        lambda: family_of(cfg)["init"](cfg, jax.random.PRNGKey(0)))
    assert nbytes(weights) == counts.weight_bytes(spec)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(weights)
               ) == counts.n_params(spec)
    per_block = n_attn * pc.page_bytes + pc.slot_bytes * pc.block_tokens / stride
    got = counts.store_page_bytes(spec, pc.block_tokens) * cfg.n_layers
    assert per_block <= got < per_block + cfg.n_layers
    if spec is REAL:
        assert (pc.page_bytes, pc.state_bytes, pc.slot_bytes) == (
            8192, 389120, 10117120)
        assert (pc.n_slots, counts.cache_bytes_per_token(spec)) == (320, 20784)
        # the issue's count (its total holds the final norm's 2,560)
        assert counts.n_params(spec) == 3_029_337_472
        s = counts.sizes(spec)
        assert counts.mamba_params(s) == 41_241_792
        assert counts.attn_params(s) == 13_762_560
        fill = (nbytes(weights) + pc.cache_bytes) / 16.91e9
        assert sv["min_fill"] <= fill < 0.60
        assert counts.scan_flops_per_token(spec) == 6 * 5120 * 16 + 5120
        assert 61_000 < counts.scan_bytes_per_token(spec, 512) < 64_000


def test_lfm2s_cache_and_counts_are_what_they_were(tmp_path):
    """The other family of the cache of two kinds: its slots keep the model's
    type and one row a layer, its bytes are PR 43's, and its chunk's program
    names the same arrays."""
    counts = family.counts(LFM2_REAL)
    _, cfg, _ = load_config_file(model_file(str(tmp_path), LFM2_REAL))
    sv = LFM2_REAL["serve"]
    pc = HybridCacheConfig.for_model(cfg, sv["n_blocks"], sv["block_tokens"],
                                     counts.stride(LFM2_REAL), max_rows=8)
    shapes = jax.eval_shape(lambda: init_cache(pc))
    assert shapes[1].shape == (320, 8, 4096) and shapes[1].dtype == jnp.bfloat16
    assert pc.state_dtype is None and pc.state_lanes == 0
    assert (pc.page_bytes, pc.state_bytes, pc.slot_bytes) == (32768, 8192, 65536)
    assert pc.cache_bytes == 10240 * 16 * 4224 == 692_060_160
    assert counts.weight_bytes(LFM2_REAL) == 10_536_278_528
    assert (cfg.page_layers, cfg.state_layers) == (cfg.attn_layers, cfg.conv_layers)


# -- what the benchmark's check has to refuse -----------------------------------------

def test_the_controls_a_zeroed_checkpoint_and_zeroed_pages_fail_the_limit(toy):
    """At tiny widths, as PERF.md section 2 sets the limit on the chip: the
    sound program under it; the reference in W8A8 int8, the reference with its
    state held in bfloat16, the program started from a checkpoint that was
    zeroed and the program started from pages that were zeroed, each over it
    (with the seeded ``A_log`` a state remembers hundreds of tokens: zeros in
    its place move the tail's logits)."""
    prompt = tokens(5 * STRIDE + 3, 83)
    eng = engine(toy)
    st, rows, out = ask(eng, prompt, 4)
    keys = list(st.chunk_keys)
    eng.release(st)
    sound, ref = checked(toy, [probe(prompt, rows, out)])
    assert sound["rms"] < RMS_LIMIT and sound["chosen_not_in_ref_top5"] == 0
    for low_p in ("int8", "statebf16"):
        low = toy.ref.reference_logprobs(
            toy.ref.make_forward(toy.sizes, low_p), toy.ref_params,
            [probe(prompt, rows, out)])
        control = toy.ref.compare(
            toy.ref.control_answers(low, [probe(prompt, rows, out)]), ref)
        assert control["rms"] > (RMS_LIMIT if low_p == "int8" else 10 * F32_TOL)
    n = 5 * STRIDE // T
    slot = eng.slots._by_key[keys[n - 1]]
    saved = [np.array(a) for a in eng.cache]       # the engine donates its own
    zeroed = saved[1].copy()
    zeroed[slot] = 0
    eng.cache = (jnp.asarray(saved[0]), jnp.asarray(zeroed))
    st, rows, out2 = ask(eng, prompt, 4)
    assert st.local_chunks == n
    assert checked(toy, [probe(prompt, rows, out2)])[0]["rms"] > RMS_LIMIT
    eng.release(st)
    held = [eng.pages._key_to_block[k] for k in keys[:n]]
    zeroed = saved[0].copy()
    zeroed[:, :, :, held] = 0
    eng.cache = (jnp.asarray(zeroed), jnp.asarray(saved[1]))
    st, rows, out3 = ask(eng, prompt, 4)
    assert st.local_chunks == n
    assert checked(toy, [probe(prompt, rows, out3)])[0]["rms"] > RMS_LIMIT


# -- strict durability: the acknowledgement is awaited once a step, per request ------

import strict_settle  # noqa: E402


@pytest.fixture
def settle_kit(toy, store):
    """``strict_settle``'s kit over pages and float32 slots: a prompt of 70
    tokens at chunks and a stride of 64 pushes ONCE, four pages of each
    attention layer and the Mamba layers' states at 64 in one commit, and runs
    a second chunk that completes no page."""
    import itertools

    from infinistore_tpu.kv.hashing import chunk_keys

    conns, ids, solo = [], itertools.count(), {}

    def build(durability="strict", store_=True):
        if store_:
            conns.append(connect(store))
        return engine(
            toy, f32=True, chunk=64, stride=64, max_rows=12, n_blocks=192,
            conn=conns[-1] if store_ else None, store_durability=durability,
            model_id=f"settle-{os.getpid()}-{time.time_ns()}-{next(ids)}")

    def alone(prompt, n):
        if tuple(prompt) not in solo:
            eng = build(store_=False)
            solo[tuple(prompt)] = eng.decode(eng.prefill(prompt), n)
        return solo[tuple(prompt)]

    def unnamed(eng, prompt):
        keys = chunk_keys(prompt, eng.model_id, chunk_tokens=T)
        return eng.pages.peek_prefix(keys[:1]) == 0

    yield types.SimpleNamespace(
        engine=lambda durability="strict", store=True: build(durability, store),
        max_batch=12, first=tokens(20, 380),
        prompts=lambda n: [tokens(70, 381 + next(ids)) for _ in range(n)],
        solo=alone, unnamed=unnamed, names_pages=True)
    for c in conns:
        c.close()


@pytest.mark.parametrize("case", strict_settle.CASES,
                         ids=lambda c: c.__name__[5:])
def test_strict_settle_over_pages_and_float32_slots(settle_kit, case):
    case(settle_kit)


def test_strict_holds_the_prefix_back_until_pages_and_checkpoint_are_acknowledged(
        toy, store):
    """The push that carries a prompt's pages AND its checkpoint is held: the
    prefill finishes unsettled, names no page, and the store has neither kind;
    released, ``prefill_settle`` returns and a second engine adopts both."""
    conn, mid = connect(store), fresh_id(toy)
    eng = engine(toy, chunk=64, stride=64, conn=conn, model_id=mid)
    prompt = tokens(70, 95)
    held = strict_settle.HeldCommits(eng)
    held.hold(prompt)
    pp = eng.prefill_start(prompt)
    while not pp.finished:
        assert eng.prefill_step(pp) is None
    keys = held.keys_of(prompt)
    assert eng.pages.peek_prefix(keys[:1]) == 0 and not eng.seqs
    other = engine(toy, chunk=64, stride=64, conn=connect(store), model_id=mid)
    assert other.transfer.lookup_prefix(keys) == 0
    assert other.transfer.lookup_prefix([keys[3]], states=True) == 0
    held.release(prompt)
    st = eng.prefill_settle(pp)
    assert eng.pages.peek_prefix(keys[:4]) == 4
    assert other.transfer.lookup_prefix(keys) == 4
    assert other.transfer.lookup_prefix([keys[3]], states=True) == 1
    st2 = other.prefill(prompt)
    assert (st2.store_chunks, st2.local_chunks) == (4, 0)
    assert np.array_equal(np.asarray(st.last_logits), np.asarray(st2.last_logits))
    conn.close()
