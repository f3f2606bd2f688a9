"""TPU-native inference serving tier (docs/SERVING.md).

The first subsystem on the inference half of the north star: admit ->
batch -> compiled program -> respond, following the trainer's
checkpoints via atomic hot-reload.  Layers:

    engine.py    ServeSpec + InferenceEngine: AOT-compiled per-bucket
                 generate/predict programs, healthy-checkpoint load,
                 degrade-not-crash hot reload, pinned-fingerprint fleet
                 mode + explicit reload_to, honest health() verdicts;
                 cb=on adds the two continuous-batching programs
                 (paged prefill + fixed-slot decode step)
    batcher.py   MicroBatcher: bounded-queue admission with Backoff
                 shedding, deadline expiry, smallest-admissible-bucket
                 coalescing with left-pad masking (the static path;
                 predict always rides here)
    kvcache.py   PagedKVCache: fixed pool of (block, 2 Hkv, block_len,
                 D) KV blocks, per-slot block tables, refcounts, null
                 block 0 — slot memory O(active tokens)
    scheduler.py ContinuousScheduler + StreamTicket: admit a request
                 into a free slot at any decode step, retire on
                 EOS/max-new/deadline, free blocks immediately, ONE
                 compiled decode program per step
    server.py    InferenceServer: stdlib-HTTP + in-process frontends,
                 reload poll thread, /admin/reload command channel,
                 chunked-transfer streaming POST /generate under cb
    stats.py     ServeStats: QPS, p50/p95 latency + queue-wait/
                 service split, tok/s, occupancy (bucket and slot),
                 reload/shed counters (PipelineStats mold)
    router.py    Router + engine handles: least-loaded healthy
                 dispatch, retry-on-other-engine (streams: only
                 before the first byte), Backoff quarantine /
                 readmission, router-level shedding
    session.py   StreamSession + SessionManager: the durable decode
                 session journal behind mid-stream failover — every
                 emitted token recorded with an absolute sequence
                 number, resume-as-prefill on a same-fingerprint
                 sibling, at-most-once splice, idle-watchdog and
                 drain-kick triggers, singa_stream_* counters
    sessionlog.py  SessionWal + ControlStateStore: the crash-safe
                 control plane — append-only per-epoch session WAL
                 (group commit, CRC per record, torn-tail-tolerant
                 replay), atomically-snapshotted control state,
                 epoch claim/fence for restart and zero-downtime
                 handoff, singa_router_wal_* counters
    fleet.py     EngineFleet + RolloutController + FleetServer:
                 N workers behind one router, canary rollout with
                 auto-rollback, streaming passthrough, elastic
                 grow/retire membership
    autoscale.py AutoScaler + AutoScaleSpec: SLO-driven control loop
                 over the windowed stats — grow on pressure (shed
                 rate, p95 vs budget, queue depth, occupancy), drain
                 and retire after a quiet streak, Backoff cooldown
    traffic.py   TrafficGen + Phase scenarios: open-loop Poisson
                 load (steady/ramp/flash_crowd/diurnal), long-tail
                 prompt mixes, QoS priority mixes, slow readers,
                 chaos hooks (incl. stall_chaos stragglers) —
                 offered vs completed, shed rate, p50/p95/p99 per
                 phase and per class
    qos.py       request-lifecycle QoS vocabulary: end-to-end
                 deadline propagation (absolute in-process, remaining
                 -ms on the wire), priority classes interactive /
                 batch / best_effort, RetryBudget token bucket,
                 per-(tenant, class) Retry-After backoffs
    tenancy.py   TenantRegistry + TenantSpec + TenantBudget: per-
                 tenant QoS envelopes — retry-budget floors, queue/
                 slot/KV quotas, brownout overrides — with unknown
                 tenant ids folded into one bounded `other` envelope
                 (blast-radius containment for the multi-tenant
                 fleet)
    wire.py      zero-copy binary transport: length-prefixed framed
                 protocol over persistent sockets (BinaryEngineHandle
                 / BinaryTransportServer, multiplexed in-flight
                 requests), shared-memory TokenRing for the in-
                 process hop, batched token flushes (flush_tokens/
                 flush_ms) on both wire surfaces, per-engine
                 negotiation with automatic HTTP fallback
                 (NegotiatingEngineHandle), singa_wire_* counters
                 with a serialization-time split — HTTP/JSON stays
                 the always-on debug surface

Fault sites `serve.admit` / `serve.batch` / `serve.reload` /
`fleet.dispatch` / `fleet.rollout` / `scale.decide` / `serve.hedge` /
`engine.stall` / `serve.resume` (utils.faults) make every degradation
path — hedged tail-cutting and mid-stream failover included —
deterministic on CPU.
"""

from . import qos
from .autoscale import AutoScaler, AutoScaleSpec
from .batcher import (Cancelled, DeadlineExpired, MicroBatcher,
                      Overloaded, Ticket)
from .engine import InferenceEngine, ServeSpec
from .fleet import (EngineFleet, FleetServer, RolloutController,
                    RolloutSpec)
from .kvcache import PagedKVCache
from .router import (EngineUnavailable, HttpEngineHandle, LameDuck,
                     LocalEngineHandle, Router, RouterSpec,
                     RouterStats, UnknownSession)
from .scheduler import ContinuousScheduler, StreamTicket
from .server import InferenceServer
from .session import SessionManager, StreamSession, StreamStats
from .sessionlog import (ControlStateStore, SessionWal, WalStats,
                         replay_wal, reduce_sessions, walcheck)
from .router import UnknownModel
from .stats import ServeStats
from .qos import PRIORITIES, ClassBackoffs, RetryBudget
from .tenancy import (TenantBudget, TenantRegistry, TenantSpec)
from .traffic import (Phase, TrafficGen, diurnal, flash_crowd,
                      kill_chaos, ramp, stall_chaos, steady)
from .wire import (BinaryEngineHandle, BinaryTransportServer,
                   NegotiatingEngineHandle, TokenRing, WireError,
                   WireStats, WireUnavailable)

__all__ = ["AutoScaler", "AutoScaleSpec", "BinaryEngineHandle",
           "BinaryTransportServer", "Cancelled",
           "ClassBackoffs", "ContinuousScheduler",
           "ControlStateStore", "DeadlineExpired",
           "EngineFleet", "EngineUnavailable", "FleetServer",
           "HttpEngineHandle", "InferenceEngine", "InferenceServer",
           "LameDuck", "LocalEngineHandle", "MicroBatcher",
           "NegotiatingEngineHandle",
           "Overloaded", "PRIORITIES", "PagedKVCache", "Phase",
           "RetryBudget", "RolloutController", "RolloutSpec",
           "Router", "RouterSpec", "RouterStats", "ServeSpec",
           "ServeStats", "SessionManager", "SessionWal",
           "StreamSession", "StreamStats", "StreamTicket",
           "TenantBudget", "TenantRegistry", "TenantSpec", "Ticket",
           "TokenRing", "TrafficGen", "UnknownModel",
           "UnknownSession", "WalStats", "WireError", "WireStats",
           "WireUnavailable",
           "diurnal", "flash_crowd", "kill_chaos", "qos", "ramp",
           "reduce_sessions", "replay_wal", "stall_chaos", "steady",
           "walcheck"]
