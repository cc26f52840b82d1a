"""Continuous-batching serving subsystem.

Layered as: KV pool (the page-table ``PagedKVCachePool``, with
refcounted pages) + ``PrefixCache`` (shared-prefix KV page-run reuse
over the pool) + ``Scheduler`` (admission, in-flight batching,
page-pressure preemption, per-request sampling, draft-then-verify
speculative decoding) +
``ServeEngine`` facade (tuner-sized pools, jitted steps, ``kv_kernel``
selection, ``spec_k``) + ``ReplicaRouter`` (N engines behind one
admission queue with pluggable routing policies, overflow re-routing,
open-loop arrival release, SLO-aware admission, and ``AutoscalePolicy``
fleet autoscaling) + ``telemetry`` (vstep-clocked ``Tracer`` spans and
ring events, the ``MetricsRegistry`` schema both ``to_metrics`` views
are built on, and the Prometheus / Chrome-trace exporters).
"""

from repro.serving.engine import SERVABLE_FAMILIES, ServeEngine
from repro.serving.pool import PagedKVCachePool, PoolExhausted
from repro.serving.prefill import PrefillManager
from repro.serving.prefix_cache import PrefixCache, prefix_key
from repro.serving.router import (ADMISSION_MODES, ROUTE_POLICIES,
                                  AutoscaleEvent, AutoscalePolicy,
                                  RejectedRequest, ReplicaRouter,
                                  RouterStats, prefix_replica,
                                  replay_peak_replicas)
from repro.serving.sampling import K_CAP, effective_top_k, make_sampler
from repro.serving.scheduler import (Request, RequestResult, Scheduler,
                                     ServeStats, VirtualClock,
                                     percentile_steps)
from repro.serving.spec import Drafter, NGramDrafter
from repro.serving.telemetry import (EVENT_KINDS, PHASES, ROUTER_SCHEMA,
                                     SERVE_SCHEMA, MetricSpec,
                                     MetricsRegistry, Span, TraceEvent,
                                     Tracer, chrome_trace, json_sanitize,
                                     prometheus_text, write_chrome_trace)
from repro.serving.trace import (ARRIVAL_MODES, bursty_arrivals,
                                 longprompt_trace, poisson_arrivals,
                                 repetitive_trace, sharedprefix_trace,
                                 trace_repetitiveness, uniform_trace,
                                 with_arrivals, zipf_trace)

__all__ = ["ServeEngine", "SERVABLE_FAMILIES", "PagedKVCachePool",
           "PoolExhausted", "PrefillManager",
           "PrefixCache", "prefix_key", "ReplicaRouter", "RouterStats",
           "ROUTE_POLICIES", "ADMISSION_MODES", "AutoscalePolicy",
           "AutoscaleEvent", "RejectedRequest", "prefix_replica",
           "Request", "RequestResult", "Scheduler", "ServeStats",
           "VirtualClock", "percentile_steps", "make_sampler",
           "K_CAP", "effective_top_k", "Drafter", "NGramDrafter",
           "ARRIVAL_MODES", "poisson_arrivals", "bursty_arrivals",
           "with_arrivals", "longprompt_trace", "repetitive_trace",
           "sharedprefix_trace", "trace_repetitiveness", "uniform_trace",
           "zipf_trace", "Tracer", "Span", "TraceEvent", "MetricSpec",
           "MetricsRegistry", "SERVE_SCHEMA", "ROUTER_SCHEMA", "PHASES",
           "EVENT_KINDS", "prometheus_text", "chrome_trace",
           "write_chrome_trace", "json_sanitize", "replay_peak_replicas"]
