"""AVERY engine: the intent-driven request/response front door.

  api         — Request / Response / StreamEvent / RequestFuture
  transport   — Transport protocol; ChannelTransport, LoopbackTransport
  policy      — ControlPolicy protocol; Adaptive / StaticTier / BestEffort;
                RetryPolicy (backoff + tier downshift on failure)
  scheduler   — admission policy: FifoScheduler (default), QoSScheduler
                (intent QoS classes, weighted-fair + strict-priority,
                rate limits, page-rollback preemption)
  faults      — chaos injection: FaultInjector (transport), FaultyExecutor
  inflight    — token-level continuous batching (join a running decode)
  speculative — Context-stream DraftModel + paged multi-token verify
  observability — Tracer (per-request spans -> Perfetto JSON),
                MetricsRegistry (counters/gauges/log-bucket histograms),
                FlightRecorder (bounded ring, crash dumps)
  engine      — AveryEngine + OperatorSession

All entry points (serving launcher, mission simulator, fleet runtime,
benchmarks) construct and drive the system through this package.
"""
from repro.engine.api import Request, RequestFuture, Response, StreamEvent
from repro.engine.engine import AveryEngine, OperatorSession
from repro.engine.faults import (CloudStageError, FaultInjector,
                                 FaultyExecutor)
from repro.engine.inflight import InflightDecoder
from repro.engine.observability import (Counter, FlightRecorder, Gauge,
                                        Histogram, MetricsRegistry,
                                        RequestTrace, Span, Tracer,
                                        validate_chrome_trace,
                                        validate_trace, validate_traces)
from repro.engine.policy import (AdaptivePolicy, BestEffortPolicy,
                                 ControlPolicy, RetryPolicy,
                                 StaticTierPolicy, TierDecision,
                                 policy_from_mode)
from repro.engine.scheduler import (QOS_LATENCY, QOS_THROUGHPUT,
                                    FifoScheduler, QoSScheduler,
                                    jain_index, qos_class)
from repro.engine.speculative import (DraftModel, SpecStats,
                                      SpeculativeConfig)
from repro.engine.transport import (ChannelTransport, LoopbackTransport,
                                    Transport)

__all__ = [
    "Request", "Response", "StreamEvent", "RequestFuture",
    "AveryEngine", "OperatorSession", "InflightDecoder",
    "ControlPolicy", "TierDecision", "AdaptivePolicy", "StaticTierPolicy",
    "BestEffortPolicy", "RetryPolicy", "policy_from_mode",
    "FifoScheduler", "QoSScheduler", "jain_index", "qos_class",
    "QOS_LATENCY", "QOS_THROUGHPUT",
    "CloudStageError", "FaultInjector", "FaultyExecutor",
    "DraftModel", "SpecStats", "SpeculativeConfig",
    "Transport", "ChannelTransport", "LoopbackTransport",
    "Tracer", "Span", "RequestTrace", "MetricsRegistry",
    "Counter", "Gauge", "Histogram", "FlightRecorder",
    "validate_trace", "validate_traces", "validate_chrome_trace",
]
