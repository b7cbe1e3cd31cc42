"""Simulation-as-a-service: a shape-bucketed query broker over the
batched sweep engine.

The paper's evaluation — and the ROADMAP north star — is a large grid of
(policy, cost-model, workload) what-if simulations.  ``repro.core.sweep``
made one *hand-built* grid cheap; this package makes *arbitrary
concurrent* scenario traffic cheap:

  * :class:`SimQuery` — one independent question: a machine, a policy
    bundle, a cost model, and a trace (by value or by
    :class:`~repro.core.workloads.TraceSpec`), plus priority/deadline.
  * :class:`SimBroker` — admission-queues queries, buckets them by
    (machine, compiled-budget bound, trace shape), microbatches each
    bucket into a single ``sweep_lanes`` call across the policy-lane
    axis (optionally sharded over devices), and resolves per-query
    futures.  A content-addressed result cache answers repeats with zero
    XLA recompiles and zero device work.
  * :mod:`repro.service.search` — a client-side search driver (grid +
    successive halving over PolicyConfig space) that exercises the broker
    the way an architecture-search harness would.

Every layer reports into an optional :class:`repro.obs.Telemetry`
(``SimBroker(telemetry=...)``): lifecycle spans, queue-wait/flush
histograms, cache and migration counters — ``broker.snapshot()`` renders
the lot; the default is a no-op sink and results are identical either
way (see :mod:`repro.obs`).

``benchmarks/service_throughput.py`` measures the broker against naive
per-query execution; ``tests/test_service.py`` pins bit-identical
per-query results against direct sequential ``TieredMemSimulator`` runs.

The failure model lives in :mod:`repro.service.resilience` (typed error
taxonomy, TTL quarantine, per-bucket circuit breaker, retry/backoff and
admission-control knobs) and is chaos-tested through the deterministic
fault-injection harness in :mod:`repro.obs.inject` — see the README's
"Robustness" section for the taxonomy and degraded-mode semantics.
"""
from ..obs import FaultInjector, FaultRule, InjectedFault, NullTelemetry, \
    Telemetry, fail_lane, fail_n, fail_once, fail_rate
from .broker import BrokerStats, SimBroker
from .cache import DiskCacheTier, ResultCache
from .query import (SimFuture, SimQuery, lane_digest, query_cache_key,
                    spec_cache_key)
from .resilience import (BrokerOverloadedError, BrokerTimeoutError,
                         CircuitBreaker, DeadlineExceededError,
                         DeviceProgramError, PoisonedQueryError, Quarantine,
                         ResilienceConfig, ServiceError)
from .search import grid_search, policy_grid, successive_halving

__all__ = [
    "BrokerStats", "SimBroker", "DiskCacheTier", "ResultCache", "SimFuture",
    "SimQuery", "lane_digest", "query_cache_key", "spec_cache_key",
    "grid_search", "policy_grid", "successive_halving",
    "Telemetry", "NullTelemetry",
    "ServiceError", "PoisonedQueryError", "DeadlineExceededError",
    "BrokerOverloadedError", "BrokerTimeoutError", "DeviceProgramError",
    "ResilienceConfig", "Quarantine", "CircuitBreaker",
    "FaultInjector", "FaultRule", "InjectedFault",
    "fail_once", "fail_n", "fail_lane", "fail_rate",
]
