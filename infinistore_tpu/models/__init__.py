import json as _json

from .. import jaxcfg as _jaxcfg  # noqa: F401 -- process-wide jax config
from . import (cohere2_moe, jamba, lfm2_moe, llama, mimo_v2, mla_moe,
               retention)
from .llama import (
    GEMMA2_9B,
    LLAMA3_1B,
    LLAMA3_8B,
    LLAMA3_70B,
    MISTRAL_7B,
    QWEN3_8B,
    QWEN25_7B,
    TINY,
    LlamaConfig,
    decode_forward,
    init_params,
    loss_fn,
    prefill_forward,
    scaled,
    train_step_fn,
    verify_forward,
)
from .moe import (
    MIXTRAL_8X7B,
    TINY_MOE,
    MoEConfig,
    init_moe_params,
    moe_decode_forward,
    moe_loss_fn,
    moe_prefill_forward,
    moe_train_step_fn,
    moe_verify_forward,
    scaled_moe,
)
# the five families' names as their callers reach them through the package
# (tests/test_aot_tpu.py); a new family adds none: its ``FAMILY`` is its surface
from .mla_moe import (
    MlaMoeConfig,
    init_mla_moe_params,
    mla_moe_decode_forward,
    mla_moe_prefill_forward,
)
from .cohere2_moe import (
    Cohere2MoeConfig,
    cohere2_moe_decode_forward,
    cohere2_moe_prefill_forward,
    init_cohere2_moe_params,
)
from .retention import (
    RetentionConfig,
    init_retention_params,
    retention_decode_forward,
    retention_prefill_forward,
)
from .lfm2_moe import (
    Lfm2MoeConfig,
    init_lfm2_moe_params,
    lfm2_moe_decode_forward,
    lfm2_moe_prefill_forward,
)
from .jamba import (
    JambaConfig,
    init_jamba_params,
    jamba_decode_forward,
    jamba_prefill_forward,
)
from .attention import (
    apply_rope,
    causal_attention,
    paged_decode_attention,
    repeat_kv,
)
from .hf import (
    config_from_hf,
    moe_config_from_hf,
    moe_params_from_hf,
    params_from_hf,
)

# THE table of model families: a new one is its module and its line here
# (and, only where its sequences keep something new, its cache config).  The
# dense family (``llama.FAMILY``) is what every other config and file is.
FAMILIES = (mla_moe.FAMILY, cohere2_moe.FAMILY, retention.FAMILY,
            lfm2_moe.FAMILY, jamba.FAMILY, mimo_v2.FAMILY)


def family_of(cfg) -> dict:
    """What a model's config type brings besides the dense defaults: its
    ``init`` (weights from a key) and the engine's ``fns`` (the forwards the
    engine's hooks take).  A family with ``fns`` has no verify step, no LoRA
    threading and no mesh specs: ``serve`` refuses those at start-up.  What
    its sequences keep, and so its engine, is ``kv.cache.cache_kind(cfg)``."""
    fam = next((f for f in FAMILIES if f.config_cls is type(cfg)),
               llama.FAMILY)
    return {"init": fam.init, "fns": fam.fns}


def load_config_file(path: str):
    """Resolve a checked-in model config file (``configs/*.json``) to
    ``(model_id, cfg, seed)``.  A file that names a ``family`` states the
    source's sizes itself and is read by that family's ``config_from_file``;
    every other file names a dense preset (``llama.config_from_file``)."""
    with open(path) as f:
        spec = _json.load(f)
    name = spec.get("family")       # None: the dense family's record
    fam = next((f for f in (llama.FAMILY, *FAMILIES) if f.name == name), None)
    if fam is None:
        raise ValueError(f"{path}: family {name!r} is not one "
                         f"infinistore_tpu.models computes")
    return fam.config_from_file(path, spec)
