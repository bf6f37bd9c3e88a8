from .. import jaxcfg as _jaxcfg  # noqa: F401 -- process-wide jax config
from .connector import StoreConnector
from .engine import InferenceEngine, SequenceState
from .hybrid_engine import HybridEngine
from .scheduler import Request, Scheduler
from .speculative import SpeculativeDecoder
from .state_engine import StateEngine
from .stepprof import StepProfiler

# What a sequence keeps of a model (``kv.cache.cache_kind(cfg)``) brings its
# engine, and the engine's class the rest of the kind's triple: the cache
# config whose ``for_model`` sizes it (``cache_cls``) and the transfer engine
# that moves it to the store (``transfer_cls``).
ENGINE_OF_KIND = {"pages": InferenceEngine, "state": StateEngine,
                  "hybrid": HybridEngine}

__all__ = [
    "ENGINE_OF_KIND",
    "HybridEngine",
    "InferenceEngine",
    "Request",
    "Scheduler",
    "SequenceState",
    "SpeculativeDecoder",
    "StateEngine",
    "StepProfiler",
    "StoreConnector",
]
