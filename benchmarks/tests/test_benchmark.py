"""Tests of the benchmark's own yardstick.  CPU, seconds each; run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repository's tier-1 suite (that runs ``tests/``).
"""

from __future__ import annotations

import collections
import glob
import importlib.util
import itertools
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for sub in ("harness", "generators", "reference", "trace"):
    sys.path.insert(0, os.path.join(BENCH, sub))

import costs  # noqa: E402
import family  # noqa: E402
import mix  # noqa: E402
import reduce as trace_reduce  # noqa: E402
import stats  # noqa: E402


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]


# -- the reference against the program, both families' knobs -----------------------

@pytest.mark.parametrize("knobs", [{"qk_norm": True, "qkv_bias": False},
                                   {"qk_norm": False, "qkv_bias": True}],
                         ids=["qwen3-qk-norm", "qwen2.5-qkv-bias"])
def test_reference_agrees_with_the_program_and_the_control_does_not(knobs):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import dense
    from infinistore_tpu.models import TINY, init_params, prefill_forward, scaled

    spec = load("configs", "tiny.json")
    spec["knobs"] = knobs
    s = costs.sizes(spec)
    cfg = scaled(TINY, qk_norm=knobs["qk_norm"], attn_bias=knobs["qkv_bias"])
    theirs = init_params(cfg, jax.random.PRNGKey(11))
    ours = dense.draw_weights(s, 11)
    assert jax.tree.structure(theirs) == jax.tree.structure(ours)
    assert all(bool((a == b).all()) for a, b in
               zip(jax.tree.leaves(theirs), jax.tree.leaves(ours))), \
        "draw_weights no longer draws what init_params draws"

    rng = np.random.RandomState(3)
    answers = []
    for n in (40, 72, 100, 130):
        # the program scores toks[:n + 3]; its last four rows predict the
        # tokens after positions n-1 .. n+2 (teacher-forced on toks[n:n+3])
        toks = [int(t) for t in rng.randint(1, 512, size=n + 3)]
        logits, _ = prefill_forward(theirs, cfg, jnp.asarray(toks)[None],
                                    use_pallas=False)
        lp = np.asarray(jax.nn.log_softmax(logits[0, -4:].astype(jnp.float32)))
        top = np.argsort(lp, -1)[:, -5:]
        answers.append({"prompt": toks[:n], "ids": toks[n:] + [int(top[3, -1])],
                        "top": [{str(t): float(lp[i, t]) for t in top[i]}
                                for i in range(4)]})
    ref = dense.reference_logprobs(dense.make_forward(s, "f32"), ours, answers)
    # the chosen-token rule is about argmax tokens; here ids 0..2 are forced
    # random tokens, so only the statistic is read
    sound = dense.compare(answers, ref)
    low = dense.reference_logprobs(dense.make_forward(s, "int8"), ours, answers)
    control = dense.compare(dense.control_answers(low, answers), ref)
    assert sound["n_values"] == 4 * 4 * 5
    assert sound["rms"] < 0.03, sound
    assert control["rms"] > 1.3 * sound["rms"], (sound["rms"], control["rms"])


def test_compare_counts_a_chosen_token_outside_the_top5():
    import numpy as np

    import dense

    lp = np.log(np.full((1, 8), 1 / 8.0))
    lp[0, :5] += 0.5
    ans = [{"ids": [7], "top": [{"0": float(lp[0, 0])}]}]
    assert dense.compare(ans, [lp])["chosen_not_in_ref_top5"] == 1
    ans[0]["ids"] = [1]
    assert dense.compare(ans, [lp])["chosen_not_in_ref_top5"] == 0


# -- traffic: a pure function of the seed ----------------------------------------------

def plan_for(cell_name, seed, seconds=40.0):
    wl = next(w for w in MANIFEST["workloads"] if w["name"] == cell_name)
    cell = load("workloads", f"{cell_name}.json")
    traffic = load("traffic", f"{wl['traffic']}.json")
    config = load("configs", f"{wl['config']}.json")
    plan = mix.generate(traffic, cell, config, seed, seconds)
    # a closed loop's schedule has no end: look at its first requests
    plan["schedule"] = list(itertools.islice(plan["schedule"], 120))
    return plan, traffic, cell, config


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_requests_and_two_seeds_differ_in_content_only(cell):
    a, *_ = plan_for(cell, 2**31 + 17)
    b, *_ = plan_for(cell, 2**31 + 17)
    c, *_ = plan_for(cell, 99)
    assert json.dumps(a["schedule"]) == json.dumps(b["schedule"])
    assert json.dumps(a["probes"]) == json.dumps(b["probes"])
    shape = lambda p: [(s["kind"], s["due"], len(s["body"]["prompt"]),
                        s["body"]["max_tokens"]) for s in p["schedule"]]
    assert shape(a) == shape(c)          # same sizes at the same times ...
    assert [s["body"]["prompt"] for s in a["schedule"]] != \
           [s["body"]["prompt"] for s in c["schedule"]]      # ... other tokens
    for key in ("fill", "warm_reask"):
        assert [len(x["prompt"]) for x in a[key]] == [len(x["prompt"]) for x in c[key]]
    assert [len(p["body"]["prompt"]) for p in a["probes"]] == \
           [len(p["body"]["prompt"]) for p in c["probes"]]
    assert a["probes"][0]["body"]["prompt"] != c["probes"][0]["body"]["prompt"]


@pytest.mark.parametrize("cell", CELLS)
def test_grids_and_weights_are_honoured(cell):
    plan, traffic, _, _ = plan_for(cell, 5, seconds=300.0)
    outs = collections.Counter(s["body"]["max_tokens"] for s in plan["schedule"][:40])
    want = collections.Counter(mix.apportion(traffic["outputs"], 40))
    assert outs == want
    tails = {int(t) for t in traffic["tails"]}
    docs = {int(d) for d in traffic.get("documents", {}).get("lengths", {})} or {0}
    for s in plan["schedule"]:
        assert len(s["body"]["prompt"]) in {d + t for d in docs for t in tails}
        assert s["body"]["max_tokens"] % traffic["decode_chunk"] == 0


def test_apportion_is_exact():
    assert mix.apportion({"2048": 45, "3072": 30, "4096": 25}, 20) == \
        [2048] * 9 + [3072] * 6 + [4096] * 5
    assert len(mix.apportion({"1": 1, "2": 1, "3": 1}, 40)) == 40
    g = mix.gap_quantiles(40, 2.0)
    assert sum(g) == pytest.approx(20.0) and min(g) > 0
    assert sorted(g)[20] < 0.5 < max(g)          # exponential: median under the mean


def test_reask_reuse_distance_is_the_population():
    cell = next(c for c in CELLS if c.endswith("doc-reask"))
    plan, traffic, _, config = plan_for(cell, 7, seconds=300.0)
    n_docs = plan["meta"]["population_docs"]
    cache = config["serve"]["n_blocks"] * config["serve"]["block_tokens"]
    assert plan["meta"]["population_tokens"] >= \
        traffic["documents"]["population_cache_multiple"] * cache
    tails = {int(t) for t in traffic["tails"]}
    seen = []
    for s in plan["schedule"]:
        if s["kind"] == "reask":
            p = s["body"]["prompt"]
            key = next(tuple(p[:len(p) - t][:64]) for t in tails
                       if len(p) - t in {int(d) for d in traffic["documents"]["lengths"]})
            seen.append(key)
    assert len(seen) > n_docs
    for i, k in enumerate(seen[n_docs:], start=n_docs):
        assert seen[i - n_docs] == k            # the walk is cyclic ...
        assert k not in seen[i - n_docs + 1:i]  # ... and nothing comes back sooner
    share = sum(s["kind"] == "reask" for s in plan["schedule"][:40]) / 40
    assert share == traffic["documents"]["reask_share"]
    # the paired probes own their documents: the fill asks every other one,
    # enough of them to push the probes' pages out of HBM, and covers every
    # (document, tail) length a new document of the window can have
    fill_docs = {tuple(b["prompt"][:64]) for b in plan["fill"]}
    paired = [p for p in plan["probes"] if p["reask"]]
    assert len(paired) >= traffic["min_store_probes"]
    for p in paired:
        assert tuple(p["body"]["prompt"][:64]) not in fill_docs
    assert len(plan["fill"]) == n_docs - len(paired)
    assert sum(len(b["prompt"]) for b in plan["fill"]) >= cache
    assert {len(b["prompt"]) for b in plan["fill"]} >= \
        {len(s["body"]["prompt"]) for s in plan["schedule"] if s["kind"] == "new"}


def test_pair_diff_is_zero_for_equal_answers_and_sees_a_moved_logprob():
    a = {"ids": [5, 6], "top": [{"5": -0.5, "9": -1.5}, {"6": -0.25, "7": -2.0}]}
    same = stats.pair_diff(a, json.loads(json.dumps(a)))
    assert same == {"n_values": 4, "max_abs": 0.0, "unmatched": 0}
    b = {"ids": [5, 6], "top": [{"5": -0.5, "9": -1.53}, {"6": -0.25, "8": -2.0}]}
    moved = stats.pair_diff(a, b)
    assert moved["max_abs"] == pytest.approx(0.03) and moved["unmatched"] == 2
    c = {"ids": [9, 6], "top": [{"5": -0.6, "9": -0.4}, {"6": -0.25, "7": -2.0}]}
    forked = stats.pair_diff(a, c)         # other token chosen: later contexts differ
    assert forked["n_values"] == 2 and forked["unmatched"] == 2


def test_the_program_serving_int8_store_pages_is_refused_and_sound_pages_read_zero():
    """The check's control at a size a test can hold: control.py on the CPU
    with the tiny preset.  Sound pages come back bit for bit; int8 pages do
    not (on the chip, at the cell's size: PERF.md section 2)."""
    def read(kind):
        out = subprocess.run(
            [sys.executable, os.path.join(BENCH, "control.py"), "--workload",
             next(c for c in CELLS if c.endswith("doc-reask")), "--kind", kind,
             "--seeds", "21,22,23", "--rehearse", "1"],
            capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
        got = [json.loads(ln.split(": ", 1)[1]) for ln in out.stdout.splitlines()
               if "kv-quant" in ln]
        assert len(got) == 3 and all(g["formed"] == g["pairs"] == 4 for g in got)
        return got
    limit = load("configs", "tiny.json")["check"]["pair_logprob_max_abs_limit"]
    for g in read("none"):
        assert g["max_abs"] <= limit and g["unmatched"] == 0
    for g in read("kv-int8"):
        assert g["max_abs"] > max(limit, 0.005)


def test_a_run_whose_store_reads_are_broken_underneath_is_not_correct():
    """The rest of a run without the look for a chip (``--rehearse 1``), with
    the timed path broken underneath: the store flips a byte of every page it
    is asked to read back (its own fault injector, through the environment),
    so the client's checksum refuses the pages and the server recomputes.
    Every request still succeeds with sound tokens; `correct` must be false
    all the same.  (test_family.py sees it true on a sound rehearsal.)"""
    cell, seed = next(c for c in CELLS if c.endswith("doc-reask")), 2**31 + 5
    env = dict(os.environ, JAX_PLATFORMS="cpu", ISTPU_FAULTS=json.dumps(
        [{"op": "GET_DESC", "action": "corrupt", "times": -1}]))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed",
         str(seed), "--seconds", "6", "--trace", "0", "--rehearse", "1"],
        capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 3, out.stdout[-3000:] + out.stderr[-2000:]
    with open(os.path.join(ROOT, "chiprun_out", "bench", f"{cell}.s{seed}.t0",
                           "rows.json")) as f:
        rows = json.load(f)
    assert rows["correct"] is False
    failed = {name for name, *_, ok in rows["checks"] if not ok}
    assert failed == {"store_degraded", "pairs_formed"}, failed
    assert "correct: False" in out.stdout


# -- percentile and TPOT arithmetic on hand-made rows ----------------------------------

def row(due, first, last, tokens, events=None, ok=True):
    return {"t_due": due, "t_first": first, "t_last": last, "tokens": tokens,
            "ok": ok, "events": events or [(first, tokens)], "t_done": last}


def test_percentiles_ttft_tpot_and_rate():
    assert stats.nearest_rank([3, 1, 2, 4], 0.5) == 2
    assert stats.nearest_rank(range(1, 11), 0.9) == 9
    assert stats.nearest_rank([5], 0.9) == 5
    with pytest.raises(ValueError):
        stats.nearest_rank([], 0.5)
    r = row(10.0, 10.5, 12.5, 65, events=[(10.5, 32), (11.5, 32), (12.5, 1)])
    assert stats.ttft_s(r) == 0.5                  # from the due time
    assert stats.tpot_s(r) == pytest.approx(2.0 / 64)
    assert stats.tpot_s(row(0, 1, 1, 1)) is None
    rows = [row(0, 0.1 * i, 0.1 * i + 1, 33) for i in range(1, 11)]
    e = stats.end_to_end(rows, 0.0, 10.0)
    assert e["ttft_p50_ms"] == pytest.approx(500) and e["ttft_p90_ms"] == pytest.approx(900)
    assert e["tpot_p90_ms"] == pytest.approx(1000 / 32)
    assert e["out_tok_per_s"] == pytest.approx(33.0)
    # tokens count where they arrive, not where their request ends
    assert stats.tokens_in_window([r], 11.0, 13.0) == 33
    # a failed request gives no latency sample
    assert "ttft_p50_ms" not in stats.end_to_end([row(0, 1, 2, 5, ok=False)], 0, 0)


# -- the trace reduction on the committed small trace ---------------------------------------

def test_trace_reduction_on_the_recorded_sample():
    tr = load("trace", "sample_trace.json")
    out = trace_reduce.reduce(tr)
    expect = load("trace", "sample_expect.json")
    assert out["n_devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    # both decode executions of the sample touch the trace's edges: cut, not counted
    assert "decode" not in out["classes"] and out["cut_by_the_edges"]["count"] == 2
    for cls, want in list(expect["classes"].items()) + [
            ("cut", expect["cut_by_the_edges"])]:
        got = out["cut_by_the_edges"] if cls == "cut" else out["classes"][cls]
        assert got["count"] == want["count"]
        assert got["dur_s"] == pytest.approx(want["dur_s"], rel=1e-9)
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    assert out["breakdown"]["device_ops"][0][1] >= out["breakdown"]["device_ops"][-1][1]


def test_trace_reduction_by_hand():
    ms = 1_000_000
    tr = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_many(1)", 0, 3 * ms],               # cut by the trace's start
                ["jit__unknown(7)", 4 * ms, 2 * ms],      # a prefill chunk ...
                ["jit_convert_element_type(3)", 6 * ms, 0],
                ["jit__write_prefill_pages(4)", 6 * ms, ms // 10],   # ... by what follows
                ["jit__unknown(8)", 7 * ms, ms],          # some other jitted partial
                ["jit_many(1)", 10 * ms, 4 * ms],
                ["jit_many(1)", 16 * ms, 4 * ms]]},       # cut by the trace's end
            {"name": "XLA Ops", "events": [["fusion.1", 0, 3 * ms], ["fusion.1", 2 * ms, 3 * ms],
                                           ["copy.2", 10 * ms, 4 * ms],
                                           ["fusion.1", 16 * ms, 4 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["np.asarray(jax.Array)", 0, 20 * ms],
                                           ["PjitFunction(prefill_forward)", 5 * ms, ms]]}]}]}
    out = trace_reduce.reduce(tr)
    assert out["window_s"] == pytest.approx(20e-3)
    assert out["busy_s"] == pytest.approx((5 + 4 + 4) * 1e-3)   # overlapping ops once
    assert out["classes"]["decode"] == {"count": 1, "dur_s": pytest.approx(4e-3)}
    assert out["classes"]["prefill"] == {"count": 1, "dur_s": pytest.approx(2e-3)}
    assert out["classes"]["other"]["count"] == 3
    assert out["cut_by_the_edges"] == {"count": 2, "dur_s": pytest.approx(7e-3)}
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(10e-3)]
    with pytest.raises(ValueError):
        trace_reduce.reduce({"planes": [tr["planes"][1]]})       # nothing on a chip


# -- bytes and FLOPs against hand counts ------------------------------------------------------

def test_costs_against_hand_counts():
    q3 = load("configs", "qwen3-8b-l12.json")
    q25 = load("configs", "qwen2.5-7b-l12.json")
    # Qwen3-8B: qkv 4096*128*(32+16), wo 4096*4096, mlp 3*4096*12288
    layer3 = 4096 * 128 * 48 + 4096 * 4096 + 3 * 4096 * 12288
    assert costs.layer_matmul_params(costs.sizes(q3)) == layer3 == 192_937_984
    assert costs.kv_bytes_per_token(q3) == 2 * 12 * 8 * 128 * 2 == 49152
    w3 = 2 * (12 * (layer3 + 2 * 4096 + 2 * 128) + 2 * 151936 * 4096 + 4096)
    assert costs.weight_bytes(q3) == w3
    # Qwen2.5-7B: qkv 3584*128*(28+8), biases 128*36, mlp 3*3584*18944
    layer25 = 3584 * 128 * 36 + 3584 * 3584 + 3 * 3584 * 18944
    assert costs.layer_matmul_params(costs.sizes(q25)) == layer25
    assert costs.kv_bytes_per_token(q25) == 2 * 12 * 4 * 128 * 2 == 24576
    w25 = 2 * (12 * (layer25 + 2 * 3584 + 128 * 36) + 2 * 152064 * 3584 + 3584)
    assert costs.weight_bytes(q25) == w25
    # a decode step reads every weight but the embedding, B rows of it, live KV once
    assert costs.decode_step_bytes(q3, 8, 20000) == \
        w3 - 2 * 151936 * 4096 + 8 * 4096 * 2 + 20000 * 49152
    assert costs.decode_step_flops(q3, 8, 20000) == \
        2 * 8 * (12 * layer3 + 151936 * 4096) + 4 * 128 * 32 * 12 * 20000
    # prefill: 2 FLOPs per layer weight + causal attention at S/2 mean context
    f = costs.prefill_flops_per_token(q25, [(2048, 1.0)])
    assert f == 2 * 12 * layer25 + 4 * 128 * 28 * 12 * 1024
    assert costs.prefill_bytes_per_token(q25, 512) == 2 * 12 * layer25 / 512


def test_a_share_over_100_raises_and_an_unknown_device_is_an_error():
    assert costs.share_pct(1.0, 4.0, "x") == 25.0
    with pytest.raises(ValueError):
        costs.share_pct(1.01, 1.0, "x")
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")


# -- the manifest: every name resolves to a file -----------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_names_resolve_to_files_and_use_allowed_characters():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and m["command"][1].startswith("benchmarks/")
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and all(0 < x["bound"] <= 0.1 for x in e2e.values())
    for c in m["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(os.path.join(ROOT, c["file"]))
        spec = load("configs", f"{c['name']}.json")
        assert spec["source"] == c["source"]
        assert all(NAME.match(k) for k in c["reduced"])
        # the cut: depth, the experts held, the vocabulary; a share cut states
        # what was published and the deployment (test_family.py has the cases)
        assert family.cut_problems(c, spec) == []
        for key, where in (("costs", "counts"), ("rehearse", "configs")):
            if key in spec:
                assert os.path.exists(os.path.join(
                    BENCH, where, spec[key] + ("" if key == "rehearse" else ".py")))
        assert os.path.exists(os.path.join(
            BENCH, "reference", f"{family.reference_name(spec)}.py"))
    used = set()
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
        cell = load("workloads", f"{w['name']}.json")
        assert cell["config"] == w["config"] in configs and cell["traffic"] == w["traffic"]
        traffic = load("traffic", f"{w['traffic']}.json")
        assert os.path.exists(os.path.join(BENCH, "generators", f"{traffic['generator']}.py"))
        assert len(traffic["probes"]) * 4 * 5 >= 160
        used.add(w["config"])
    assert used == set(configs)
    cells = {w["name"] for w in m["workloads"]}
    for x in m["per_layer"]:
        assert NAME.match(x["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", x["unit"])
        spec = load("metrics", f"{x['name']}.json")
        assert {k: spec[k] for k in x} == x, f"{x['name']}: metrics/ file and manifest differ"
        assert callable(load_reader(
            os.path.join(BENCH, "readers", f"{spec['reader']}.py")).read)
        moved = e2e[x["moves"]]
        assert set(x.get("workloads", ())) <= cells
        for c in x.get("workloads", ()):   # the cell reports the metric it should move
            assert "workloads" not in moved or c in moved["workloads"]
    for cell in cells:             # every cell: setup_s, another, and a per-layer metric
        assert sum(1 for x in m["end_to_end"]
                   if "workloads" not in x or cell in x["workloads"]) >= 2
        assert any(cell in x.get("workloads", cells) for x in m["per_layer"])
    for path in glob.glob(os.path.join(BENCH, "**", "*"), recursive=True):
        rel = os.path.relpath(path, ROOT)
        if "__pycache__" not in rel:
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def load_reader(path):
    spec = importlib.util.spec_from_file_location("r", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_readers_return_nothing_when_there_is_nothing_to_read():
    ctx = {"prefix_delta": {}, "server_rows": [], "engine_before": None,
           "engine_after": None, "trace": None, "trace_span": None, "stats": stats,
           "config": load("configs", "tiny.json"), "traffic": load("traffic", "doc-reask.json"),
           "prefill_chunk": 512, "all_rows": [], "costs": costs, "peaks": {},
           "reader": lambda n: load_reader(os.path.join(BENCH, "readers", f"{n}.py"))}
    for path in glob.glob(os.path.join(BENCH, "readers", "*.py")):
        assert load_reader(path).read(ctx) is None, path
