"""Serving layer: multi-tenant counting queries over cached engines.

``repro.serve.counting`` is the synchronous subgraph-counting service
(engine cache + cross-query batching + adaptive stopping);
``repro.serve.frontend`` is the async production front door above it
(futures, per-tenant QoS tiers and rate limits, cost-model backpressure,
streaming progress, background engine warming) with its QoS primitives in
``repro.serve.qos``, its retry and quarantine policy in
``repro.serve.resilience`` and the engine cache in ``repro.serve.cache``.
"""

from .cache import EngineCache
from .counting import CountingService, Query, QueryEstimate
from .frontend import (
    QoSRejected,
    QueryFuture,
    ServiceFrontend,
    TemplateProgress,
    make_frontend,
)
from .qos import ManualClock, SystemClock, TenantPolicy, TokenBucket
from .resilience import (
    QuarantinedError,
    RetryPolicy,
    ServiceError,
    classify_failure,
)
from .stopping import AdaptiveStopper, TemplateCI, adaptive_estimate, normal_quantile

__all__ = [
    "EngineCache",
    "CountingService",
    "Query",
    "QueryEstimate",
    "ServiceFrontend",
    "QueryFuture",
    "TemplateProgress",
    "QoSRejected",
    "make_frontend",
    "ManualClock",
    "SystemClock",
    "TenantPolicy",
    "TokenBucket",
    "ServiceError",
    "QuarantinedError",
    "RetryPolicy",
    "classify_failure",
    "AdaptiveStopper",
    "TemplateCI",
    "adaptive_estimate",
    "normal_quantile",
]
