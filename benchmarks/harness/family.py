"""What depends on the model's family, found by the names a configuration's
own file gives: its plain reference, its counts, the file its server is
started with, the toy it rehearses with.  Every default is the dense
grouped-query decoder the benchmark started with, so a configuration that
names nothing is served, counted and checked as before.  A family is new
files (README.md, "Adding a configuration of another family"); no file here
is edited for one.
"""

from __future__ import annotations

import importlib.util
import os
import types

import costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMON = ("peaks", "share_pct", "NotCounted")

# what `reduced` may name (model-configs guide, section 4): depth, the routed
# experts held here, the vocabulary held here; never a width.  The names are
# those the guide's catalog of public configs uses for them
DEPTH_KEYS = {"num_hidden_layers", "num_layers"}
SHARE_KEYS = {"n_routed_experts", "num_experts", "num_local_experts",
              "moe_num_experts", "vocab_size"}


def load_module(path: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Counts:
    """A count module beside the common table of peaks: what a reader gets as
    ``ctx["costs"]``.  A quantity the module does not define raises
    ``costs.NotCounted``: the dense count is never given under another
    model's name."""

    def __init__(self, mod: types.ModuleType):
        self.module = mod

    def __getattr__(self, name: str):
        if name in COMMON:
            return getattr(costs, name)
        try:
            return getattr(self.module, name)
        except AttributeError:
            raise costs.NotCounted(
                f"{self.module.__file__} does not count {name}") from None


def counts(config: dict) -> Counts:
    """The count module of a configuration: ``benchmarks/counts/<costs>.py``
    where its file names one, the dense counts of ``harness/costs.py`` where
    it does not."""
    name = config.get("costs")
    return Counts(costs if name is None else
                  load_module(os.path.join(BENCH, "counts", f"{name}.py")))


def reference_name(config: dict) -> str:
    return config.get("check", {}).get("reference", "dense")


def reference(config: dict) -> types.ModuleType:
    """The plain reference of a configuration:
    ``benchmarks/reference/<check.reference>.py``, ``dense`` by default."""
    return load_module(os.path.join(BENCH, "reference",
                                    f"{reference_name(config)}.py"))


def model_file(config: dict, seed: int) -> dict:
    """What the server's ``--model`` file holds: the configuration's ``model``
    block verbatim (it is between the configuration and the program's loader;
    nothing here reads it), or the dense three keys; and the weights' seed."""
    body = config["model"] if "model" in config else {
        k: config[k] for k in ("preset", "published", "reduced")}
    return body | {"seed": seed}


def rehearsal_file(config: dict) -> str:
    """The toy configuration of the same family that ``--rehearse 1`` serves
    on the CPU: ``configs/<rehearse>``, ``tiny.json`` by default."""
    return os.path.join(BENCH, "configs", config.get("rehearse", "tiny.json"))


def cut_problems(entry: dict, spec: dict) -> list:
    """What is wrong with how a configuration was cut, as sentences; empty
    when it keeps to section 4 of the model-configs guide.  ``entry`` is the
    configuration's entry in BENCHMARK.json, ``spec`` its file."""
    out = []
    for k in entry["reduced"]:
        if k not in DEPTH_KEYS | SHARE_KEYS:
            out.append(f"reduced names {k!r}: only depth, the routed experts "
                       f"held and the vocabulary may differ from the source; "
                       f"no width")
    share = [k for k in entry["reduced"] if k in SHARE_KEYS]
    if share:
        for k in share:
            pub = spec.get("published", {}).get(k)
            if not isinstance(pub, int) or k not in spec or pub < spec[k]:
                out.append(f"{k} is cut to this chip's share: the file states "
                           f"it as run and the source's value under published")
        stands = spec.get("stands_for")
        if not (isinstance(stands, dict)
                and isinstance(stands.get("chips_per_layer"), int)
                and stands["chips_per_layer"] >= 2
                and isinstance(stands.get("how"), str) and stands["how"]):
            out.append("a share cut states its deployment: stands_for = "
                       "{chips_per_layer: how many chips share a layer, "
                       "how: what each holds of it}")
    return out
