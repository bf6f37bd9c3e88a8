"""The seam between a model family and everything above its module.

A family is ONE record (``models.llama.Family``, stated at the end of the
family's module, held in ``models.FAMILIES``); what its sequences keep is
stated by its config (``cfg.cache_kind``, read by ``kv.cache.cache_kind``);
the kind brings its engine (``engine.ENGINE_OF_KIND``), and the engine's class
the kind's cache config (``cache_cls``) and transfer engine
(``transfer_cls``).  The toy family below is the seam's definition: it is
registered HERE, and served, with no edit to ``models/llama.py``,
``kv/cache.py``, ``engine/*.py`` or ``serve.py``.
"""

import ast
import dataclasses
import json
import pathlib
import sys

import jax
import pytest

from infinistore_tpu import models
from infinistore_tpu.engine import ENGINE_OF_KIND
from infinistore_tpu.kv.cache import cache_kind
from infinistore_tpu.models.llama import Family

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmarks" / "harness"))
import family as harness  # noqa: E402


# ---- a toy family, registered in the test ----


@dataclasses.dataclass(frozen=True)
class ToyConfig(models.LlamaConfig):
    """The tiny dense sizes under a type of the toy family's own."""


def toy_prefill(params, cfg, tokens, **kw):
    return models.prefill_forward(params, cfg, tokens, **kw)


def toy_decode(params, cfg, *args, **kw):
    return models.decode_forward(params, cfg, *args, **kw)


def toy_config_from_file(path, spec):
    tiny = {f.name: getattr(models.TINY, f.name)
            for f in dataclasses.fields(models.TINY)}
    cfg = ToyConfig(**tiny | {"n_layers": spec["n_layers"]})
    return f"toy-l{cfg.n_layers}-seed{spec['seed']}", cfg, spec["seed"]


TOY = Family(name="toy", config_cls=ToyConfig,
             config_from_file=toy_config_from_file, init=models.init_params,
             prefill_fn=toy_prefill, decode_fn=toy_decode)


def test_a_toy_family_is_served_from_its_record_alone(tmp_path, monkeypatch):
    """Put into the table by ``monkeypatch`` (the program has no registration
    call), a ``"family": "toy"`` file is loaded by ``load_config_file``,
    resolved by ``family_of``, given its engine by its kind, and prefills and
    decodes four tokens: the tokens the dense engine decodes from the same
    weights, through the toy's own forwards."""
    monkeypatch.setattr(models, "FAMILIES", models.FAMILIES + (TOY,))
    path = tmp_path / "toy.json"
    path.write_text(json.dumps({"family": "toy", "n_layers": 2, "seed": 3}))

    model_id, cfg, seed = models.load_config_file(str(path))
    assert (model_id, type(cfg), seed) == ("toy-l2-seed3", ToyConfig, 3)
    fam = models.family_of(cfg)
    assert fam == {"init": models.init_params,
                   "fns": {"prefill_fn": toy_prefill, "decode_fn": toy_decode}}
    kind = cache_kind(cfg)
    assert kind == "pages"          # a config that says nothing keeps pages

    engine_cls = ENGINE_OF_KIND[kind]
    params = fam["init"](cfg, jax.random.PRNGKey(seed))
    prompt = [5, 17, 3, 99, 42, 7, 250, 11, 64, 2, 9, 31, 8, 77, 120, 6, 13]

    def four_tokens(cfg, fns):
        pc = engine_cls.cache_cls.for_model(cfg, 16, 16)
        eng = engine_cls(params, cfg, pc, model_id=model_id, kv_quant=None,
                         **fns)
        return eng.generate(prompt, 4)

    got = four_tokens(cfg, fam["fns"])
    assert len(got) == 4
    dense = dataclasses.replace(models.TINY, n_layers=2)
    assert got == four_tokens(dense, models.family_of(dense)["fns"])
    # out of the table again, the name is no family
    monkeypatch.undo()
    with pytest.raises(ValueError, match="family 'toy' is not one "
                                         "infinistore_tpu.models computes"):
        models.load_config_file(str(path))


# ---- every configuration of the benchmark through the tables ----

# file under benchmarks/configs -> (family's name in the table, config type,
# kind, engine, cache config, transfer engine), as literals: a wrong lookup
# anywhere along the seam fails here
RESOLVES_TO = {
    "qwen3-8b-l12": (None, "LlamaConfig", "pages", "InferenceEngine",
                     "PagedCacheConfig", "KVTransferEngine"),
    "qwen2.5-7b-l12": (None, "LlamaConfig", "pages", "InferenceEngine",
                       "PagedCacheConfig", "KVTransferEngine"),
    "kanana-2-30b-a3b-l8": ("deepseek_v3", "MlaMoeConfig", "pages",
                            "InferenceEngine", "PagedCacheConfig",
                            "KVTransferEngine"),
    "command-a-plus-l4-e16": ("cohere2_moe", "Cohere2MoeConfig", "pages",
                              "InferenceEngine", "PagedCacheConfig",
                              "KVTransferEngine"),
    "brumby-14b-l8": ("brumby", "RetentionConfig", "state", "StateEngine",
                      "StateCacheConfig", "StateTransferEngine"),
    "lfm2-24b-a2b-l10": ("lfm2_moe", "Lfm2MoeConfig", "hybrid",
                         "HybridEngine", "HybridCacheConfig",
                         "HybridTransferEngine"),
    "jamba2-3b": ("jamba", "JambaConfig", "hybrid", "HybridEngine",
                  "HybridCacheConfig", "HybridTransferEngine"),
    "mimo-v2-flash-l7-e16": ("mimo_v2_flash", "MimoV2Config", "pages",
                             "InferenceEngine", "PagedCacheConfig",
                             "KVTransferEngine"),
}


@pytest.mark.parametrize("name", sorted(RESOLVES_TO))
def test_a_benchmark_configuration_resolves_to_its_triple(name, tmp_path):
    """``load_config_file`` -> ``family_of`` -> ``cache_kind`` -> the mapping:
    the engine, cache config and transfer engine each configuration of
    BENCHMARK.json is served by, and the cache config sized as ``serve`` sizes
    it (``for_model`` with the flags the kind takes)."""
    fam_name, cfg_cls, kind, engine, cache, transfer = RESOLVES_TO[name]
    spec = json.loads((ROOT / "benchmarks" / "configs" / f"{name}.json")
                      .read_text())
    path = tmp_path / "model.json"
    path.write_text(json.dumps(harness.model_file(spec, 1)))

    _, cfg, seed = models.load_config_file(str(path))
    assert (type(cfg).__name__, seed) == (cfg_cls, 1)
    rec = next((f for f in models.FAMILIES if f.config_cls is type(cfg)),
               models.llama.FAMILY)
    assert rec.name == fam_name
    assert models.family_of(cfg) == {"init": rec.init, "fns": rec.fns}
    assert sorted(rec.fns) == ([] if fam_name is None
                               else ["decode_fn", "prefill_fn"])
    assert cache_kind(cfg) == kind
    engine_cls = ENGINE_OF_KIND[kind]
    assert (engine_cls.__name__, engine_cls.cache_cls.__name__,
            engine_cls.transfer_cls.__name__) == (engine, cache, transfer)
    sizes = ({} if kind == "pages" else {"stride": 512, "max_rows": 8})
    pc = engine_cls.cache_cls.for_model(cfg, 1024, 16, **sizes)
    assert type(pc) is engine_cls.cache_cls and pc.n_layers == cfg.n_layers


def test_the_table_names_every_family_once():
    names = [f.name for f in models.FAMILIES]
    assert names == ["deepseek_v3", "cohere2_moe", "brumby", "lfm2_moe",
                     "jamba", "mimo_v2_flash"]
    assert len({f.config_cls for f in models.FAMILIES}) == len(names)
    assert set(ENGINE_OF_KIND) == {"pages", "state", "hybrid"}


# ---- the layering, read off the source ----


def _imports(path):
    """Module names a file imports, ``from . import x`` as ``.x``."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mod = "." * node.level + (node.module or "")
            out.add(mod)
            out |= {f"{mod}.{a.name}" if node.module else f"{mod}{a.name}"
                    for a in node.names}
    return out


def test_lower_layers_do_not_know_the_families():
    """``models/llama.py`` imports no sibling family's module and names none;
    ``kv/cache.py`` guesses nothing from a model config's attributes (no
    ``hasattr``); ``models/__init__.py`` tells no family by ``isinstance``;
    ``serve.py`` holds no engine choice of its own."""
    pkg = ROOT / "infinistore_tpu"
    siblings = {f.init.__module__.rsplit(".", 1)[-1] for f in models.FAMILIES}
    assert siblings == {"mla_moe", "cohere2_moe", "retention", "lfm2_moe",
                        "jamba", "mimo_v2"}
    llama = pkg / "models" / "llama.py"
    for name in _imports(llama):
        assert name.split(".")[-1] not in siblings, name
    assert "importlib" not in _imports(llama)
    for node in ast.walk(ast.parse(llama.read_text())):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and not node.value.count(" "):      # a name, not a docstring
            assert node.value not in siblings, node.value

    def calls(path, fn):
        return [n.lineno for n in ast.walk(ast.parse(path.read_text()))
                if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                and n.func.id == fn]

    assert calls(pkg / "kv" / "cache.py", "hasattr") == []
    assert calls(pkg / "models" / "__init__.py", "isinstance") == []
    serve = (pkg / "serve.py").read_text()
    for word in ("keeps_state", "keeps_both", "StateEngine", "HybridEngine",
                 "StateCacheConfig", "HybridCacheConfig"):
        assert word not in serve, word
