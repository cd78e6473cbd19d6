from tfidf_tpu.utils.config import Config, load_config
from tfidf_tpu.utils.logging import get_logger
from tfidf_tpu.utils.metrics import Metrics, global_metrics
from tfidf_tpu.utils.tracing import trace_phase
from tfidf_tpu.utils.faults import FaultInjector, fault_point

__all__ = [
    "Config",
    "load_config",
    "get_logger",
    "Metrics",
    "global_metrics",
    "trace_phase",
    "FaultInjector",
    "fault_point",
]
