"""serve/: TPU-native continuous-batching inference engine + fleet.

Layers (each its own module, composable and separately testable):

- kv_pages.py  — PAGED KV-cache pool (vLLM-style): fixed-size blocks,
  host block and slot allocators + per-slot device page tables,
  slot-local positions — no shared clock, per-page release, contexts
  past max_len; the radix prefix cache over the blocks;
- engine.py    — PagedEngine, the one engine: bucketed jitted
  prefill-admit + one jitted batched decode step; static shapes, so
  batch composition churns with zero recompiles; per-slot finite-logits
  flag contains a NaN to one request; driven through
  admit_gate/admit/step_burst/release;
- spec.py      — speculative decoding drafts WITHOUT a draft model:
  DraftSource interface + the n-gram prompt-lookup drafter (host-side
  suffix match over prompt+generated tokens); the engine verifies
  k drafted tokens in ONE jitted forward (the s>1 paged-prefill path)
  with exact greedy acceptance and block-aware KV rollback —
  token-identical to plain decoding, fewer sequential steps;
- scheduler.py — FIFO queue, admission control (bounded queue sheds),
  per-request deadlines, EOS/length release, injectable clock
  (FakeClock for deterministic CPU tests) and fault hook;
- faults.py    — seeded, JSON-serializable FaultPlan (crash / latency /
  nan_logits / admit_fail) driving deterministic chaos tests;
- health.py    — per-replica HEALTHY/DEGRADED/DEAD state machine with a
  consecutive-failure circuit breaker and backoff half-open probes;
- slo.py       — declarative SLO targets (TTFT/TPOT p99, error rate,
  availability) evaluated as multi-window burn rates; alerts feed the
  router's brown-out, the telemetry stream, and PUSH sinks
  (AlertSinks: command/webhook/jsonl with retry backoff + a dead-sink
  breaker; FleetAlerts raises the same edges for dead/stale workers)
  (utils/telemetry.py exports the plane: JSONL streaming + /metrics
  /healthz /flight HTTP scrape endpoints; tools/check_slo.py is the
  offline verdict);
- router.py    — fault-tolerant least-loaded dispatch over N replicas:
  bounded retries with backoff+jitter, crash failover that migrates
  in-flight requests (prompt + tokens-so-far re-prefill,
  token-identical under greedy), brown-out degradation. The router
  drives a NARROW replica interface (submit/step/poll/evacuate +
  observables) — in-process handles and worker processes are
  indistinguishable to it;
- rpc.py       — the transport seam under that interface:
  length-prefixed JSON frames over localhost TCP, idempotent ops,
  per-call timeouts, shared-backoff reconnects, and a push-stream
  mode (the worker pushes completion/heartbeat snapshots; the
  router select()s on the stream fds — no polling in steady state);
- worker.py    — one replica as a real OS PROCESS: own single-process
  jax runtime, Scheduler+PagedEngine built from a JSON
  WorkerSpec, warmed before its WORKER_READY line, serving the RPC
  seam plus its own /metrics /healthz /flight endpoints;
- supervisor.py— worker lifecycles: spawn/waitpid, restart with
  exponential backoff + a restart-budget circuit breaker, graceful
  drain, orphan reaping (atexit + pytest fixture), the router-facing
  RemoteReplicaHandle (salvage-point failover, stale-heartbeat
  SIGKILL), and the fleet builder / telemetry federation glue
  (utils/telemetry.py ScrapeFederator, tools/check_fleet.py verdict);
- metrics.py   — TTFT/TPOT/queue-depth/occupancy per replica plus the
  fleet counters (retries, failovers, sheds-by-reason, breaker state,
  brown-out), emitted through the process-0 gate (utils/metrics.py
  render_text() serves the same registry as Prometheus exposition);
  request-lifecycle SPANS live in utils/trace.py: scheduler/engine/
  router all take an optional TraceRecorder (`--trace-out` exports
  Chrome trace JSON; tools/check_traces.py validates it), and every
  Completion carries a queue/prefill/decode/stall flight record;
- cli.py       — the `cli.py serve` entry point: serve --prompt strings
  from a trained LM checkpoint (measurement is perf/run.py, PERF.md);
- frontdoor.py — the HTTP/SSE wire surface over Router.stream: POST
  /v1/generate streams the typed tokens/resumed/end events as SSE
  frames (sse.py codec, shared by server and client), per-tenant
  admission at the door (admission.py token buckets + concurrency
  caps), auth/validation hooks, bounded-buffer slow-consumer shedding,
  and a SIGTERM-shaped graceful drain;
- fairshare.py — the tenant QoS ledgers: VTC-style weighted-fair
  service counters (least-served drives the scheduler's fair head
  pick, most-over-served drives the door's "fairness" refusal — both
  behind flags that degrade byte-identically to FIFO when off),
  per-tenant cost metering (the /tenants endpoint + fleet federation),
  and Jain's fairness index; slo.py's TenantSLORegistry gives each
  tenant its own error budget so a hostile tenant's burn pages as ITS
  alert and scopes the brown-out to ITS work;
- workload.py  — deterministic synthetic traffic: the single-tenant
  Poisson trace builders the router tests replay, and multi-tenant
  workload plans (per-tenant Poisson/bursty/diurnal arrivals,
  heavy-tailed lengths, multi-turn sessions, a hostile marker) —
  JSON-serializable and byte-replayable, judged offline by
  tools/check_qos.py.
"""

from ddp_practice_tpu.serve.admission import (
    AdmissionController,
    TenantPolicy,
)

from ddp_practice_tpu.serve.fairshare import (
    TenantLedger,
    VirtualTokenCounter,
    federate_tenant_reports,
    jains_index,
)
from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
from ddp_practice_tpu.serve.faults import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
    ReplicaCrashed,
)
from ddp_practice_tpu.serve.health import (
    BreakerConfig,
    CircuitBreaker,
    HealthState,
    ReplicaHealth,
)
from ddp_practice_tpu.serve.kv_pages import (
    BlockAllocator,
    RadixPrefixCache,
    SlotAllocator,
)
from ddp_practice_tpu.serve.frontdoor import (
    Frontdoor,
    FrontdoorConfig,
    RouterDriver,
    sse_request,
)
from ddp_practice_tpu.serve.metrics import (
    FrontdoorMetrics,
    RouterMetrics,
    ServeMetrics,
)
from ddp_practice_tpu.serve.router import (
    Router,
    RouterConfig,
    make_router,
)
from ddp_practice_tpu.serve.scheduler import (
    Completion,
    FakeClock,
    MonotonicClock,
    Request,
    Scheduler,
)
from ddp_practice_tpu.serve.rpc import (
    RpcClient,
    RpcError,
    RpcServer,
    RpcTimeout,
)
from ddp_practice_tpu.serve.spec import (
    DraftSource,
    PromptLookupDraft,
)
from ddp_practice_tpu.serve.slo import (
    AlertSinks,
    AlertSinkSpec,
    FleetAlerts,
    SLOConfig,
    SLOWatchdog,
    TenantSLORegistry,
)
from ddp_practice_tpu.serve.supervisor import (
    RemoteReplicaHandle,
    Supervisor,
    SupervisorConfig,
    make_fleet_router,
)
from ddp_practice_tpu.serve.worker import WorkerSpec
from ddp_practice_tpu.serve.workload import TenantSpec, WorkloadPlan

__all__ = [
    "AdmissionController",
    "AlertSinkSpec",
    "AlertSinks",
    "BlockAllocator",
    "BreakerConfig",
    "CircuitBreaker",
    "Completion",
    "DraftSource",
    "FleetAlerts",
    "EngineConfig",
    "FakeClock",
    "FaultInjector",
    "FaultPlan",
    "FaultSpec",
    "Frontdoor",
    "FrontdoorConfig",
    "FrontdoorMetrics",
    "HealthState",
    "MonotonicClock",
    "PagedEngine",
    "PromptLookupDraft",
    "RadixPrefixCache",
    "RemoteReplicaHandle",
    "ReplicaCrashed",
    "ReplicaHealth",
    "Request",
    "Router",
    "RouterConfig",
    "RouterDriver",
    "RouterMetrics",
    "RpcClient",
    "RpcError",
    "RpcServer",
    "RpcTimeout",
    "SLOConfig",
    "SLOWatchdog",
    "Scheduler",
    "ServeMetrics",
    "SlotAllocator",
    "Supervisor",
    "SupervisorConfig",
    "TenantLedger",
    "TenantPolicy",
    "TenantSLORegistry",
    "TenantSpec",
    "VirtualTokenCounter",
    "WorkerSpec",
    "WorkloadPlan",
    "federate_tenant_reports",
    "jains_index",
    "make_fleet_router",
    "make_router",
    "sse_request",
]
