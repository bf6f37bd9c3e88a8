from .. import jaxcfg as _jaxcfg  # noqa: F401 -- process-wide jax config
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
    load_config_file,
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

def family_of(cfg) -> dict:
    """What a model's config type brings besides the dense defaults: its
    ``init`` (weights from a key) and the engine's ``fns`` (the forwards the
    engine's hooks take).  A family with ``fns`` has no verify step, no LoRA
    threading and no mesh specs: ``serve`` refuses those at start-up."""
    if isinstance(cfg, MlaMoeConfig):
        return {"init": init_mla_moe_params,
                "fns": {"prefill_fn": mla_moe_prefill_forward,
                        "decode_fn": mla_moe_decode_forward}}
    if isinstance(cfg, Cohere2MoeConfig):
        return {"init": init_cohere2_moe_params,
                "fns": {"prefill_fn": cohere2_moe_prefill_forward,
                        "decode_fn": cohere2_moe_decode_forward}}
    if isinstance(cfg, RetentionConfig):
        # a state a layer and no pages: ``serve`` gives it the engine over
        # state slots (engine/state_engine.py) by ``cfg.state_shape``
        return {"init": init_retention_params,
                "fns": {"prefill_fn": retention_prefill_forward,
                        "decode_fn": retention_decode_forward}}
    if isinstance(cfg, Lfm2MoeConfig):
        # pages for its attention layers AND a state for its conv layers:
        # ``serve`` gives it the hybrid engine (engine/hybrid_engine.py) by
        # ``kv.cache.cache_kind``
        return {"init": init_lfm2_moe_params,
                "fns": {"prefill_fn": lfm2_moe_prefill_forward,
                        "decode_fn": lfm2_moe_decode_forward}}
    if isinstance(cfg, JambaConfig):
        # pages for its two attention layers AND a float32 state for its
        # Mamba layers: the hybrid engine too
        return {"init": init_jamba_params,
                "fns": {"prefill_fn": jamba_prefill_forward,
                        "decode_fn": jamba_decode_forward}}
    return {"init": init_params, "fns": {}}


__all__ = [
    "MlaMoeConfig",
    "init_mla_moe_params",
    "mla_moe_prefill_forward",
    "mla_moe_decode_forward",
    "family_of",
    "JambaConfig",
    "init_jamba_params",
    "jamba_prefill_forward",
    "jamba_decode_forward",
    "Lfm2MoeConfig",
    "init_lfm2_moe_params",
    "lfm2_moe_prefill_forward",
    "lfm2_moe_decode_forward",
    "RetentionConfig",
    "init_retention_params",
    "retention_prefill_forward",
    "retention_decode_forward",
    "Cohere2MoeConfig",
    "init_cohere2_moe_params",
    "cohere2_moe_prefill_forward",
    "cohere2_moe_decode_forward",
    "MoEConfig",
    "MIXTRAL_8X7B",
    "TINY_MOE",
    "init_moe_params",
    "moe_prefill_forward",
    "moe_decode_forward",
    "moe_verify_forward",
    "moe_loss_fn",
    "moe_train_step_fn",
    "scaled_moe",
    "LlamaConfig",
    "LLAMA3_8B",
    "LLAMA3_70B",
    "LLAMA3_1B",
    "GEMMA2_9B",
    "MISTRAL_7B",
    "QWEN25_7B",
    "QWEN3_8B",
    "TINY",
    "init_params",
    "load_config_file",
    "prefill_forward",
    "decode_forward",
    "verify_forward",
    "loss_fn",
    "train_step_fn",
    "scaled",
    "apply_rope",
    "causal_attention",
    "paged_decode_attention",
    "repeat_kv",
    "config_from_hf",
    "params_from_hf",
    "moe_config_from_hf",
    "moe_params_from_hf",
]
