"""A configuration of another model family is files and entries only.

The fixture family (``fixture_family/``: a reference that marks its answers, a
count module with a page and a weight count of its own, a ``model`` block, a
toy to rehearse with, a ``programs.d`` file) is laid over a temporary copy of
``benchmarks/``; no file the copy already had is edited.  Each of the six
seams (benchmarks/README.md, "Adding a configuration of another family") must
then resolve to the fixture's file, and the two accepted configurations to
what they always were.  CPU, run by hand like test_benchmark.py.
"""

from __future__ import annotations

import filecmp
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(BENCH, "tests", "fixture_family")
for sub in ("harness", "trace"):
    sys.path.insert(0, os.path.join(BENCH, sub))

import costs  # noqa: E402
import family  # noqa: E402
import stats  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ENTRIES = json.load(open(os.path.join(FIXTURE, "manifest_entries.json")))
FIXTURE_CELL = ENTRIES["workload"]["name"]


def load(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """<tmp>/BENCHMARK.json + <tmp>/benchmarks with the fixture's files laid
    over it and its two entries added: nothing else differs from the repo."""
    root = str(tmp_path_factory.mktemp("bench_copy"))
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.relpath(os.path.join(d, f), bench)
              for d, _, fs in os.walk(bench) for f in fs}
    shutil.copytree(FIXTURE, bench, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__", "README.md",
                                                  "manifest_entries.json"))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append(ENTRIES["config"])
    manifest["workloads"].append(ENTRIES["workload"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    for rel in before:            # every file the benchmark had is as it was
        assert filecmp.cmp(os.path.join(BENCH, rel), os.path.join(bench, rel),
                           shallow=False), rel
    return {"root": root, "bench": bench,
            "family": family.load_module(os.path.join(bench, "harness", "family.py")),
            "reduce": family.load_module(os.path.join(bench, "trace", "reduce.py"))}


# -- the six seams ------------------------------------------------------------------

def seam_reference(fam, bench, cfg, want):
    mod = fam.reference(cfg)
    import numpy as np

    marked = mod.compare([{"ids": [1], "top": [{"1": -0.5}]}],
                         [np.asarray([[-0.25, -0.5, -0.75, -1.0, -2.0, -3.0]])])
    return (os.path.relpath(mod.__file__, bench), fam.reference_name(cfg),
            marked.get("marked_by")) == want


def seam_counts(fam, bench, cfg, want):
    got = fam.counts(cfg)
    bt = cfg["serve"]["block_tokens"]
    return (os.path.relpath(got.module.__file__, bench), got.store_page_bytes(cfg, bt),
            got.weight_bytes(cfg), got.share_pct(1.0, 4.0, "x")) == want


SEAMS = {
    "reference": lambda c, cfg: seam_reference(
        c["family"], c["bench"], cfg,
        ("reference/marked.py", "marked", "fixture_family/reference/marked.py")),
    "counts": lambda c, cfg: seam_counts(
        c["family"], c["bench"], cfg,
        ("counts/fixture.py", 6 * 1024 * 16, 2 * 1_000_003 * 8, 25.0)),
    "model-file": lambda c, cfg: c["family"].model_file(cfg, 7)
    == cfg["model"] | {"seed": 7} and "share" in cfg["model"],
    "rehearsal": lambda c, cfg: c["family"].rehearsal_file(cfg)
    == os.path.join(c["bench"], "configs", "fixture-toy.json"),
    "program-classes": lambda c, cfg: [
        c["reduce"].program_class([n], 0, c["reduce"].load_table())
        for n in ("jit_fixture_decode(3)", "jit_fixture_select(9)",
                  "jit_decode_many(1)", "jit_prefill_forward(2)", "jit_iota(4)")]
    == ["decode", "select", "decode", "prefill", "other"],
    "cut": lambda c, cfg: c["family"].cut_problems(ENTRIES["config"], cfg) == [],
}


@pytest.mark.parametrize("seam", SEAMS)
def test_each_seam_resolves_to_the_fixture_familys_own_file(copy, seam):
    cfg = load(copy["bench"], "configs", "fixture-share.json")
    assert SEAMS[seam](copy, cfg), seam


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda e: e["name"])
def test_an_accepted_configuration_resolves_to_the_dense_defaults(copy, entry):
    """In the repo and in the copy that holds the fixture beside it."""
    for bench, fam in ((BENCH, family), (copy["bench"], copy["family"])):
        cfg = load(os.path.dirname(bench), entry["file"])
        assert not {"costs", "model", "rehearse"} & set(cfg)
        assert "reference" not in cfg["check"]
        assert seam_reference(fam, bench, cfg, ("reference/dense.py", "dense", None))
        assert fam.counts(cfg).module is costs
        bt = cfg["serve"]["block_tokens"]
        assert costs.store_page_bytes(cfg, bt) * cfg["num_hidden_layers"] // bt \
            == costs.kv_bytes_per_token(cfg) == costs.cache_bytes_per_token(cfg)
        assert fam.model_file(cfg, 7) == {
            "preset": cfg["preset"], "published": cfg["published"],
            "reduced": cfg["reduced"], "seed": 7}
        assert fam.rehearsal_file(cfg) == os.path.join(bench, "configs", "tiny.json")
        assert fam.cut_problems(entry, cfg) == []
    assert family.load_module(os.path.join(BENCH, "trace", "reduce.py")).load_table() \
        == load(BENCH, "trace", "programs.json")
    both = copy["reduce"].load_table()        # patterns added, none taken away
    for cls, patterns in load(BENCH, "trace", "programs.json").items():
        assert both[cls][:len(patterns)] == patterns


# -- a reader's count is the family's, or the metric is left out -----------------------

def traced_ctx(counts_mod, cfg):
    """A traced window by hand: two whole decode dispatches of 32 steps in
    0.64 s (10 ms a step), one request decoding all through the span."""
    run = family.load_module(os.path.join(BENCH, "run.py"))
    row = {"t_first": 0.5, "t_last": 20.0, "prompt_tokens": 1000}
    return run, {
        "cell": {}, "traffic": load(BENCH, "traffic", "doc-reask.json"), "config": cfg,
        "stats": stats, "costs": counts_mod,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "rows": [row], "all_rows": [row], "window": (0.0, 50.0), "prefix_delta": {},
        "engine_before": None, "engine_after": None, "server_rows": [],
        "trace": {"classes": {"decode": {"count": 2, "dur_s": 0.64}}},
        "trace_span": (5.0, 15.0), "prefill_chunk": 64,
        "reader": lambda n: family.load_module(os.path.join(BENCH, "readers", f"{n}.py"))}


def test_a_reader_takes_its_count_from_the_configurations_own_module(copy):
    cfg = load(copy["bench"], "configs", "fixture-toy.json")
    counts_mod = copy["family"].counts(cfg)
    run, ctx = traced_ctx(counts_mod, cfg)
    layer = run.read_layer_metrics(MANIFEST, MANIFEST["workloads"][0]["name"], ctx)
    assert layer["model.decode_step_ms"]["value"] == pytest.approx(10.0)
    need_bytes = 2 * 1_000_003 * 8 + 1000 * 6 * 1024 * 2      # the fixture's, marked
    assert counts_mod.decode_step_bytes(cfg, 1, 1000) == need_bytes
    assert layer["kernel.decode_roofline"]["value"] == pytest.approx(
        100 * (need_bytes / 819e9) / 10e-3)
    assert layer["kernel.decode_roofline"]["value"] != pytest.approx(
        100 * (costs.decode_step_bytes(cfg, 1, 1000) / 819e9) / 10e-3)


def test_a_quantity_the_family_does_not_count_leaves_the_metric_out(copy, tmp_path):
    src = open(os.path.join(FIXTURE, "counts", "fixture.py")).read()
    assert "def decode_step_bytes(" in src
    path = os.path.join(copy["bench"], "counts", "fixture_nobytes.py")
    with open(path, "w") as f:
        f.write(src.replace("def decode_step_bytes(", "def _not_counted("))
    cfg = dict(load(copy["bench"], "configs", "fixture-toy.json"), costs="fixture_nobytes")
    counts_mod = copy["family"].counts(cfg)
    with pytest.raises(costs.NotCounted):
        counts_mod.decode_step_bytes
    run, ctx = traced_ctx(counts_mod, cfg)
    layer = run.read_layer_metrics(MANIFEST, MANIFEST["workloads"][0]["name"], ctx)
    assert "kernel.decode_roofline" not in layer           # absent, not wrong
    assert layer["model.decode_step_ms"]["value"] == pytest.approx(10.0)
    assert layer["engine.decode_rows"]["value"] == pytest.approx(1.0)
    ctx["costs"] = family.counts({})                       # the dense module counts it
    assert "kernel.decode_roofline" in run.read_layer_metrics(
        MANIFEST, MANIFEST["workloads"][0]["name"], ctx)


# -- the whole sequence, rehearsed through the fixture's files ---------------------------

def test_a_rehearsal_walks_the_fixture_family_end_to_end(copy):
    """``run.py --rehearse 1`` from the copy, on the fixture's cell: the run
    directory shows whose reference, page, pool and model file were used."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [ROOT] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    out = subprocess.run(
        [sys.executable, os.path.join(copy["bench"], "run.py"), "--workload",
         FIXTURE_CELL, "--seed", str(2**31 + 28), "--seconds", "6", "--trace", "0",
         "--rehearse", "1"], capture_output=True, text=True, timeout=900, env=env,
        cwd=copy["root"])
    assert out.returncode == 3, out.stdout[-3000:] + out.stderr[-2000:]
    assert "REHEARSAL on platform: cpu" in out.stdout
    run_dir = os.path.join(copy["root"], "chiprun_out", "bench",
                           f"{FIXTURE_CELL}.s{2**31 + 28}.t0")
    toy = load(copy["bench"], "configs", "fixture-toy.json")
    check = load(run_dir, "check.json")
    assert check["reference"] == "marked"
    assert check["f32"]["marked_by"] == "fixture_family/reference/marked.py"
    assert check["f32"]["n_values"] >= 160
    assert f"reference marked: {check['f32']['rms']}" in out.stdout
    assert load(run_dir, "model.json") == toy["model"] | {"seed": (2**31 + 28) % (2**31 - 1)}
    store = load(run_dir, "store.argv.json")
    assert store[store.index("--minimal-allocate-size") + 1] == str(6 * 16)   # KB a page
    per_token = 6 * 1024 * toy["num_hidden_layers"]
    said = re.search(r"store pool (\d+) GiB for about (\d+) pushed tokens \((\d+) B each\)",
                     out.stdout)
    assert int(said.group(3)) == per_token
    assert store[store.index("--prealloc-size") + 1] == said.group(1)
    assert int(said.group(1)) == -(-int(said.group(2)) * per_token * 115 // (100 * 2**30)) + 1
    serve = load(run_dir, "serve.argv.json")
    assert serve[serve.index("--config") + 1] == os.path.join(
        copy["bench"], "configs", "fixture-toy.json")
    assert "check ok   re-ask probes paired" in out.stdout
    rows = load(run_dir, "rows.json")          # every comparison held, on the CPU
    assert rows["correct"] is True and all(ok for *_, ok in rows["checks"])


# -- the cut: what section 4 of the model-configs guide admits, and no more ----------------

SHARE = load(FIXTURE, "configs", "fixture-share.json")
CUTS = {
    "depth-only": (["num_hidden_layers"], {}, 0),
    "depth-experts-vocabulary": (ENTRIES["config"]["reduced"], {}, 0),
    "a-width": (["num_hidden_layers", "intermediate_size"], {}, 1),
    "a-head-size": (["head_dim"], {}, 1),
    "experts-per-token": (["num_experts_per_tok"], {}, 1),
    "a-latent-rank": (["kv_lora_rank"], {}, 1),
    "share-without-published": (["n_routed_experts"], {"published": {}}, 1),
    "share-larger-than-published": (["vocab_size"], {"published": {"vocab_size": 256}}, 1),
    "share-without-deployment": (["n_routed_experts"], {"stands_for": "one chip"}, 1),
    "share-of-one-chip": (["n_routed_experts"],
                          {"stands_for": {"chips_per_layer": 1, "how": "whole"}}, 1),
}


@pytest.mark.parametrize("case", CUTS)
def test_reduced_admits_depth_experts_held_and_vocabulary_and_no_width(case):
    reduced, change, n_problems = CUTS[case]
    got = family.cut_problems({"reduced": reduced}, dict(SHARE, **change))
    assert len(got) == n_problems, got
