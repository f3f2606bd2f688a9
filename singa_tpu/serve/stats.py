"""Serving-tier counters — the inference-side sibling of
`data.pipeline.PipelineStats`.

One `ServeStats` instance is shared by the `InferenceEngine` (compile /
reload accounting), the `MicroBatcher` (admission / batching / latency),
the `ContinuousScheduler` (the `cb_*` account of its steps) and the
`InferenceServer` (the /stats endpoint).  All mutation goes through the
lock; `snapshot()` is the single read surface, so the HTTP handler and
tests see the same semantics.

Every tally and every gauge is declared ONCE, in `TALLIES` below: its
name, its kind and what it means.  The zeros of `__init__`, the plain
part of `snapshot()` and the counters and gauges of `register_into`
(the /metrics Prometheus endpoint) are read from that table, so a new
counter is one line there and its increment in an `observe_*` method
(the scheduler moves `cb_chunked_prompts`, `cb_steps_between_chunks`
and `cb_admit_steps` itself, through `count()`).  What the table cannot
say in a line:

  * latency quantiles (p50/p95) come from a bounded reservoir of the
    most recent completions — a serving dashboard number, not an exact
    all-time percentile;
  * `qps` decays on an idle server — a health dashboard should read
    `qps_recent` next to `uptime_s`;
  * `compiles` is the zero-recompile acceptance gate: a warmed server
    must hold it constant;
  * where the model drafts, a layer wrote two cache rows a draft
    verified, and the next step writes over the second where the draft
    was rejected (`cb_drafts_made`, `cb_drafts_accepted`).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, NamedTuple, Optional

from .tenancy import TenantCounts


COUNTER, GAUGE, INTERNAL, DERIVED = "counter", "gauge", "internal", "derived"


class Tally(NamedTuple):
    """One line of `ServeStats`' accounting.  COUNTER and GAUGE are plain
    attributes, zeroed by `__init__`, moved under the lock, copied by
    `snapshot()` in the table's order and exported to /metrics as
    `<prefix>_<name>_total` / `<prefix>_<name>`; INTERNAL is such an
    attribute on neither surface, feeding the gauge its meaning names;
    DERIVED is a gauge `snapshot()` computes (no attribute; /metrics
    leaves it out while None).  `zero`: 0.0 marks a tally of seconds,
    rounded to the microsecond in `snapshot()`.  `late`: /metrics lists
    the gauges in the table's order, those with `late` behind the rest
    in its order: the endpoint's order was never /stats'."""
    name: str
    kind: str
    meaning: str
    zero: Any = 0
    late: int = 0


TALLIES = (
    # admission / completion
    Tally("submitted", COUNTER, "requests handed to submit()"),
    Tally("completed", COUNTER, "requests answered (observe_latency)"),
    Tally("failed", COUNTER, "engine / batch errors surfaced to requests"),
    Tally("expired", COUNTER, "deadline passed before dispatch"),
    Tally("expired_on_arrival", COUNTER,
          "dead on arrival: never queued, never prefilled (serve/qos.py)"),
    Tally("cancelled", COUNTER, "cancelled by the caller (hedge loser)"),
    Tally("shed", COUNTER, "admission rejected (queue full / fault)"),
    Tally("shed_interactive", COUNTER, "of `shed`, by class (brownout)"),
    Tally("shed_batch", COUNTER, "of `shed`, by class"),
    Tally("shed_best_effort", COUNTER, "of `shed`, by class"),
    Tally("rejected", COUNTER, "never-servable request (fast 400)"),
    Tally("resumed", COUNTER, "admitted with a resume_from prefix"),
    Tally("queue_depth", GAUGE, "requests waiting right now"),
    Tally("generated_tokens", COUNTER, "tokens of completed requests"),
    # micro-batching (serve/batcher.py)
    Tally("batches", COUNTER, "micro-batches dispatched"),
    Tally("batched_requests", COUNTER, "real requests they carried"),
    Tally("batch_slots", COUNTER, "sum of their buckets' batch sizes"),
    # continuous batching (serve/scheduler.py)
    Tally("cb_steps", COUNTER, "scheduler iterations run"),
    Tally("cb_prefills", COUNTER, "prefills that ran to their end"),
    Tally("cb_flash_prefills", COUNTER, "of them, by a flash-attention rung"),
    Tally("cb_prefill_rows", COUNTER, "prompt tokens they held"),
    Tally("cb_prefill_width_rows", COUNTER,
          "rows their programs ran: each prompt's rung of the ladder"),
    # where the engine chunks (observe_cb_chunk; docs/SERVING.md)
    Tally("cb_chunked_prompts", COUNTER, "prompts prefilled whole"),
    Tally("cb_prefill_chunks", COUNTER, "chunks handed to the device"),
    Tally("cb_chunk_tokens", COUNTER, "prompt tokens they held"),
    Tally("cb_prefix_rows", COUNTER, "pool rows their attention read"),
    Tally("cb_steps_between_chunks", COUNTER,
          "decode steps that went out while a prompt was in prefill"),
    Tally("cb_grouped_rows", COUNTER,
          "their rows multiplied by a held expert's weights (grouped form)"),
    Tally("cb_grouped_row_slots", COUNTER,
          "rows x held experts: what the dense walk would have multiplied"),
    Tally("cb_grouped_tile_rows", COUNTER,
          "rows of the tiles the grouped matmul visited, three products "
          "a layer: cb_grouped_rows x 3 over it is the tiles' fill"),
    Tally("cb_admit_steps", COUNTER,
          "iterations that admitted: each held every slot for its prefills"),
    Tally("cb_steps_ahead", COUNTER,
          "decode steps handed over before the step before them was read"),
    Tally("cb_collects_drained", COUNTER,
          "steps in flight read with none handed over behind them"),
    # the loop thread's stall account (observe_cb_stall)
    Tally("cb_stalls", COUNTER,
          "laps of a step over STALL_S: longer than any legitimate one"),
    Tally("cb_stall_seconds", COUNTER, "their seconds", zero=0.0),
    Tally("cb_stall_wait_seconds", COUNTER,
          "of them, inside waits on the device or the runtime", zero=0.0),
    Tally("cb_blocks_in_use", GAUGE, "blocks held right now", late=1),
    Tally("cb_blocks_total", GAUGE, "usable pool blocks", late=1),
    # what one slot's fixed state and one paged block cost, over all
    # layers (serve/kvcache.py state_bytes): with the slots in use and
    # the live blocks, the bytes a decode step has to move
    Tally("cb_slot_state_bytes", GAUGE, "one slot's fixed state", late=1),
    Tally("cb_block_bytes", GAUGE, "a growing block", late=1),
    Tally("cb_window_block_bytes", GAUGE,
          "a ring block, over the layers with a window", late=1),
    # what ONE copy of the paged kernel moves: a block of one layer's
    # pool, keys and values together (0: no such layer)
    Tally("cb_block_copy_bytes", GAUGE, "under the table", late=3),
    Tally("cb_window_block_copy_bytes", GAUGE, "in a ring", late=3),
    Tally("cb_ring_blocks", GAUGE, "ring blocks a slot", late=2),
    Tally("cb_extent_blocks", GAUGE,
          "consecutive blocks the free list deals in, a copy's", late=2),
    Tally("cb_live_block_steps", COUNTER, "table blocks decode steps walked"),
    Tally("cb_block_copies", COUNTER,
          "sum of the copies that brought them, a layer (an extent each)"),
    Tally("cb_window_block_steps", COUNTER,
          "sum of ring blocks they walked (layers with a window)"),
    # routing of the experts held here, summed over decode steps and
    # routed layers, busy slots only (observe_routing)
    Tally("cb_routed_layer_steps", COUNTER, "layers x steps counted"),
    Tally("cb_routed_assignments", COUNTER, "(token, held expert) pairs"),
    Tally("cb_routed_experts_touched", COUNTER, "held experts chosen"),
    Tally("cb_routed_max_load", COUNTER, "the busiest held expert's pairs"),
    # what the emit loop handed out (observe_cb_emit)
    Tally("cb_emit_slot_steps", COUNTER, "busy slots of fetched steps"),
    Tally("cb_tokens_emitted", COUNTER,
          "tokens they were handed: 1 each without drafts, 1 to 2 with"),
    Tally("cb_drafts_made", COUNTER, "drafts verified, one a busy slot-step"),
    Tally("cb_drafts_accepted", COUNTER, "of them, accepted (2 tokens)"),
    Tally("consecutive_batch_failures", GAUGE,
          "batches failed in a row: /healthz degrades past degraded_after"),
    # engine
    Tally("compiles", COUNTER, "engine program compilations"),
    Tally("reloads", COUNTER, "hot reloads that took new params"),
    Tally("reload_failures", COUNTER, "restore raised: kept old params"),
    Tally("reloads_refused", COUNTER, "nothing newer / unhealthy"),
    Tally("torn_polls", COUNTER, "poll raced a live writer: no change"),
    Tally("reload_poll_deaths", COUNTER,
          "poll daemon died on an unexpected exception (then restarted)"),
    # sums and sizes behind the cb shares
    Tally("cb_active_slot_steps", INTERNAL,
          "sum of active slots per step (cb_slot_occupancy)"),
    Tally("cb_block_use_steps", INTERNAL,
          "sum of blocks in use per step (cb_block_utilization)"),
    Tally("cb_decode_steps", INTERNAL,
          "iterations that ran a decode (cb_live_block_share)"),
    Tally("cb_table_blocks", INTERNAL,
          "slots x blocks per slot, set once (cb_live_block_share)"),
    Tally("cb_slot_capacity", INTERNAL,
          "compiled slot count S, set once (cb_slot_occupancy)"),
    # what snapshot() computes, each by the method or loop named
    Tally("qps", DERIVED, "completions over the object's lifetime: qps()"),
    Tally("qps_recent", DERIVED, "completions in the last qps_window_s"),
    Tally("uptime_s", DERIVED, "seconds since construction"),
    Tally("p50_latency_ms", DERIVED, "latency_quantile(), recent reservoir"),
    Tally("p95_latency_ms", DERIVED, "latency_quantile()"),
    Tally("p99_latency_ms", DERIVED, "latency_quantile()"),
    Tally("shed_rate_recent", DERIVED, "windowed(): sheds / attempts"),
    Tally("p95_latency_recent_ms", DERIVED, "windowed()"),
    Tally("p99_latency_recent_ms", DERIVED, "windowed()"),
    Tally("p50_queue_wait_ms", DERIVED, "split_quantile(): time queued"),
    Tally("p95_queue_wait_ms", DERIVED, "split_quantile()"),
    Tally("p50_service_ms", DERIVED, "split_quantile(): being served"),
    Tally("p95_service_ms", DERIVED, "split_quantile()"),
    Tally("p50_ttft_ms", DERIVED, "split_quantile(): submit to first token"),
    Tally("p95_ttft_ms", DERIVED, "split_quantile()"),
    Tally("p50_tokens_per_s", DERIVED, "split_quantile(): tokens / service"),
    Tally("p95_tokens_per_s", DERIVED, "split_quantile()"),
    Tally("batch_occupancy", DERIVED,
          "real requests / the batch slots dispatched (1.0: no padding)"),
    Tally("cb_slot_occupancy", DERIVED,
          "active slots / compiled slots averaged over scheduler steps"),
    Tally("cb_slot_occupancy_recent", DERIVED,
          "the same, time-weighted over a trailing window"),
    Tally("cb_block_utilization", DERIVED,
          "KV blocks in use / pool size averaged over steps"),
    Tally("cb_live_block_share", DERIVED,
          "table blocks the paged kernel walked / slots x table width over "
          "the steps that decoded: what of a whole-table read stays live"),
    Tally("cb_prefill_fill_share", DERIVED,
          "prompt tokens / rows of each prompt's rung of cb_prefill_widths"),
    Tally("cb_window_block_share", DERIVED,
          "ring blocks the windowed walk read / table blocks the growing "
          "walk read: under 1 once contexts pass the window", late=4),
)

_ZEROS = tuple((t.name, t.zero) for t in TALLIES if t.kind != DERIVED)
# (name, is a tally of seconds) of what snapshot() copies
_PLAIN = tuple((t.name, isinstance(t.zero, float)) for t in TALLIES
               if t.kind in (COUNTER, GAUGE))
_COUNTERS = tuple(t.name for t in TALLIES if t.kind == COUNTER)
_GAUGES = tuple(t.name for t in sorted(
    (t for t in TALLIES if t.kind in (GAUGE, DERIVED)),
    key=lambda t: t.late))


def _nearest_rank(ordered, q: float):
    """The value at quantile `q` of a sorted list; None of an empty one."""
    if not ordered:
        return None
    return ordered[min(int(q * len(ordered)), len(ordered) - 1)]


def _rounded(v: Optional[float], digits: int, scale: float = 1.0):
    return None if v is None else round(v * scale, digits)


def _share(part, whole) -> Optional[float]:
    return round(part / whole, 4) if whole else None


class ServeStats:
    """Thread-safe serving counters.  See module docstring."""

    def __init__(self, latency_window: int = 2048,
                 qps_window_s: float = 30.0):
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        # per-tenant engine-level accounting (serve/tenancy.py):
        # bounded-cardinality labels, exported as singa_tenant_* by
        # register_into.  Callers pass registry-FOLDED labels.
        self.tenants = TenantCounts(
            ("submitted", "completed", "shed"))
        window = max(int(latency_window), 1)
        self._latencies: deque = deque(maxlen=window)
        # the total-latency split (observe_request): time in queue
        # before dispatch/admission vs time being served, plus the
        # per-request generated-token count and tok/s — the
        # attribution a bare p50/p95 gap lacks
        self._queue_waits: deque = deque(maxlen=window)
        self._services: deque = deque(maxlen=window)
        # submit -> first token, one sample a request (observe_ttft)
        self._ttfts: deque = deque(maxlen=window)
        self._tok_rates: deque = deque(maxlen=window)
        # completion timestamps for the windowed QPS (bounded: at most
        # latency_window recent completions contribute)
        self.qps_window_s = max(float(qps_window_s), 0.001)
        self._completions: deque = deque(maxlen=window)
        # timestamped reservoirs for the windowed() view (autoscaler
        # control inputs): (stamp, latency) per completion, stamps per
        # shed
        self._timed_lats: deque = deque(maxlen=window)
        self._shed_t: deque = deque(maxlen=window)
        # (stamp, active_slots) per scheduler step: the lifetime
        # cb_slot_occupancy average can't fall after the scheduler
        # idles (no steps, no new samples), so the autoscaler reads
        # occupancy over a trailing window instead
        self._cb_t: deque = deque(maxlen=8192)
        for name, zero in _ZEROS:
            setattr(self, name, zero)
        # real Prometheus histograms (cumulative buckets + _sum/_count)
        # created by register_into(); None until then so the hot path
        # costs one attribute check when /metrics is not wired
        self._hist_latency = None
        self._hist_queue_wait = None
        self._hist_service = None
        self._hist_ttft = None

    # -- mutation ----------------------------------------------------------
    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)
            if field == "shed":
                self._shed_t.extend([time.monotonic()] * n)

    def gauge(self, field: str, value: int) -> None:
        with self._lock:
            # a typo'd field must fail loudly (AttributeError), not
            # silently create a new attribute no snapshot ever reads —
            # the same implicit validation count()'s getattr performs
            getattr(self, field)
            setattr(self, field, value)

    def observe_batch(self, requests: int, slots: int) -> None:
        with self._lock:
            self.batches += 1
            self.batched_requests += requests
            self.batch_slots += slots
            self.consecutive_batch_failures = 0

    def observe_batch_failure(self) -> None:
        with self._lock:
            self.consecutive_batch_failures += 1

    def observe_latency(self, seconds: float) -> None:
        with self._lock:
            self.completed += 1
            self._latencies.append(seconds)
            now = time.monotonic()
            self._completions.append(now)
            self._timed_lats.append((now, seconds))
        if self._hist_latency is not None:
            self._hist_latency.observe(float(seconds))

    def _observe_queue_wait(self, seconds: float) -> None:
        seconds = max(float(seconds), 0.0)
        with self._lock:
            self._queue_waits.append(seconds)
        if self._hist_queue_wait is not None:
            self._hist_queue_wait.observe(seconds)

    def observe_request(self, queue_wait_s: Optional[float],
                        service_s: float, ntokens: int) -> None:
        """Attribute one completed request: time queued before
        dispatch vs time being served, and its generated-token count
        (tok/s recorded when both are positive).  Called next to
        `observe_latency` by both the MicroBatcher and the
        ContinuousScheduler; the latter passes no queue wait, having
        observed it when the wait ended (`observe_admission`)."""
        if queue_wait_s is not None:
            self._observe_queue_wait(queue_wait_s)
        with self._lock:
            self._services.append(max(service_s, 0.0))
            self.generated_tokens += int(ntokens)
            if ntokens > 0 and service_s > 0:
                self._tok_rates.append(ntokens / service_s)
        if self._hist_service is not None:
            self._hist_service.observe(max(float(service_s), 0.0))

    def observe_admission(self, queue_wait_s: float) -> None:
        """One request left the queue for a slot: its queue wait is
        observed now, not at completion, so a backlog shows while it
        grows (a request that never completes still waited)."""
        self._observe_queue_wait(queue_wait_s)

    def observe_ttft(self, seconds: float) -> None:
        """Submit to first token of one request."""
        seconds = max(float(seconds), 0.0)
        with self._lock:
            self._ttfts.append(seconds)
        if self._hist_ttft is not None:
            self._hist_ttft.observe(seconds)

    def observe_cb_prefill(self, plen: int, width: int,
                           flash: bool = False) -> None:
        """One prefill that ran to its end: `plen` prompt tokens
        through the program compiled `width` rows wide; `flash`: that
        program's attention is the flash forward kernel
        (`InferenceEngine.cb_flash_widths`)."""
        with self._lock:
            self.cb_prefills += 1
            self.cb_flash_prefills += bool(flash)
            self.cb_prefill_rows += int(plen)
            self.cb_prefill_width_rows += int(width)

    def observe_cb_chunk(self, rows: int, start: int, grouped_rows: int,
                         grouped_row_slots: int,
                         grouped_tile_rows: int = 0) -> None:
        """One chunk of a prompt that is prefilled in several, read
        back: `rows` prompt tokens at positions `start` .. (its
        attention read `start` rows of the pool), of whose assignments
        `grouped_rows` fell on held experts where the dense walk would
        have multiplied `grouped_row_slots`, in tiles of
        `grouped_tile_rows` rows over the layers' three products."""
        with self._lock:
            self.cb_prefill_chunks += 1
            self.cb_chunk_tokens += int(rows)
            self.cb_prefix_rows += int(start)
            self.cb_grouped_rows += int(grouped_rows)
            self.cb_grouped_row_slots += int(grouped_row_slots)
            self.cb_grouped_tile_rows += int(grouped_tile_rows)

    def observe_cb_step(self, active_slots: int, blocks_in_use: int,
                        live_blocks: int = 0,
                        window_blocks: int = 0, ahead: int = 0,
                        drained: int = 0, copies: int = 0) -> None:
        """`live_blocks`: table blocks the step's decode program walked
        (0 for a step that ran none); `copies`: the descriptors one
        table-kind call issued for them (as many, or an extent each);
        `window_blocks`: ring blocks its
        windowed layers walked (`PagedKVCache.walked_blocks`);
        `ahead`: 1 where its decode step went to the device before the
        one before it was read; `drained`: steps in flight it read with
        none handed over behind them."""
        with self._lock:
            self.cb_steps += 1
            self.cb_steps_ahead += ahead
            self.cb_collects_drained += drained
            self.cb_active_slot_steps += int(active_slots)
            self.cb_block_use_steps += int(blocks_in_use)
            if live_blocks:
                self.cb_decode_steps += 1
                self.cb_live_block_steps += int(live_blocks)
                self.cb_block_copies += int(copies)
                self.cb_window_block_steps += int(window_blocks)
            self._cb_t.append((time.monotonic(), int(active_slots)))

    def observe_cb_emit(self, slots: int, tokens: int,
                        accepted: Optional[int] = None) -> None:
        """One fetched decode step handed out: `slots` busy slots got
        `tokens` tokens; where the model drafts each of them verified a
        draft and `accepted` of those were accepted (None: no drafts)."""
        with self._lock:
            self.cb_emit_slot_steps += int(slots)
            self.cb_tokens_emitted += int(tokens)
            if accepted is not None:
                self.cb_drafts_made += int(slots)
                self.cb_drafts_accepted += int(accepted)

    def observe_cb_stall(self, seconds: float, waited: bool) -> None:
        """One lap of a scheduler step that took `seconds`, longer than
        any legitimate lap; `waited`: the lap was a wait on the device
        or the runtime, not the host's own work."""
        with self._lock:
            self.cb_stalls += 1
            self.cb_stall_seconds += float(seconds)
            if waited:
                self.cb_stall_wait_seconds += float(seconds)

    def observe_routing(self, assignments: int, experts_touched: int,
                        layers: int, max_load: int = 0) -> None:
        """One decode step's routing counts, summed over its `layers`
        routed layers.  `max_load`: each layer's busiest held expert's
        assignments, where the layers count it; over `assignments` /
        experts held it is the imbalance (1 = every expert alike)."""
        with self._lock:
            self.cb_routed_layer_steps += int(layers)
            self.cb_routed_assignments += int(assignments)
            self.cb_routed_experts_touched += int(experts_touched)
            self.cb_routed_max_load += int(max_load)

    # -- reads -------------------------------------------------------------
    def latency_quantile(self, q: float) -> Optional[float]:
        """Seconds at quantile `q` (p50/p95/p99 in snapshot) over the
        recent-completion reservoir (nearest-rank), or None before any
        completion."""
        with self._lock:
            lats = sorted(self._latencies)
        return _nearest_rank(lats, q)

    def split_quantile(self, kind: str, q: float) -> Optional[float]:
        """Nearest-rank quantile over one of the observe_request
        reservoirs: kind in ("queue_wait", "service", "ttft",
        "tokens_per_s")."""
        src = {"queue_wait": self._queue_waits,
               "service": self._services, "ttft": self._ttfts,
               "tokens_per_s": self._tok_rates}[kind]
        with self._lock:
            vals = sorted(src)
        return _nearest_rank(vals, q)

    def cb_slot_occupancy_recent(
            self, window_s: float = 5.0) -> Optional[float]:
        """TIME-weighted slot occupancy over the trailing window:
        slot-seconds actually spent decoding / (window x capacity).
        The per-step lifetime average is wrong twice for a scale-down
        signal — it never falls once the scheduler idles (no steps, no
        new samples), and a scheduler that only steps while busy
        averages high even at 1 rps.  Here the gaps BETWEEN steps
        count as idle time (per-step credit capped at 0.25s so a
        stalled scheduler can't bank a giant interval), so this reads
        ~1.0 under saturation and decays toward 0.0 within `window_s`
        of the last request.  None before any cb step (cb off or not
        yet warmed)."""
        now = time.monotonic()
        with self._lock:
            if self.cb_steps == 0 or self.cb_slot_capacity == 0:
                return None
            window = min(float(window_s), max(now - self._t0, 1e-6))
            cutoff = now - window
            entries = [(t, a) for t, a in self._cb_t if t >= cutoff]
            capacity = self.cb_slot_capacity
        if not entries:
            return 0.0
        busy = 0.0
        prev = cutoff
        for t, a in entries:
            busy += a * min(max(t - prev, 0.0), 0.25)
            prev = t
        return min(busy / (window * capacity), 1.0)

    def occupancy(self) -> Optional[float]:
        with self._lock:
            if self.batch_slots == 0:
                return None
            return self.batched_requests / self.batch_slots

    def qps(self) -> float:
        with self._lock:
            dt = time.monotonic() - self._t0
            return self.completed / dt if dt > 0 else 0.0

    def uptime_s(self) -> float:
        return time.monotonic() - self._t0

    def qps_recent(self) -> float:
        """Completions within the last `qps_window_s` seconds over
        that window (capped at uptime while the server is younger than
        the window) — 0.0 the moment traffic stops, where the lifetime
        `qps` only decays asymptotically."""
        now = time.monotonic()
        with self._lock:
            window = min(self.qps_window_s, max(now - self._t0, 1e-6))
            cutoff = now - window
            n = sum(1 for t in self._completions if t >= cutoff)
        return n / window

    def windowed(self, window_s: Optional[float] = None) -> Dict[str, Any]:
        """Rates over the trailing window (default `qps_window_s`,
        capped at uptime) — the engine-level sibling of
        `RouterStats.windowed()`.  shed_rate is sheds over admission
        attempts (sheds + completions) inside the window."""
        now = time.monotonic()
        with self._lock:
            window = float(window_s if window_s is not None
                           else self.qps_window_s)
            window = min(window, max(now - self._t0, 1e-6))
            cut = now - window
            shed = sum(1 for t in self._shed_t if t >= cut)
            lats = sorted(l for t, l in self._timed_lats if t >= cut)

        def q(frac):
            return _rounded(_nearest_rank(lats, frac), 3, 1e3)
        return {
            "window_s": round(window, 3),
            "completed": len(lats),
            "shed": shed,
            "qps": round(len(lats) / window, 3),
            "shed_rate": round(shed / max(shed + len(lats), 1), 4),
            "p50_latency_ms": q(0.5),
            "p95_latency_ms": q(0.95),
            "p99_latency_ms": q(0.99),
        }

    def register_into(self, registry,
                      prefix: str = "singa_serve") -> None:
        """Register every snapshot field into an `obs.MetricsRegistry`
        as a pull-time collector (counters for the monotonic tallies,
        gauges for the derived/point-in-time values) — additive;
        snapshot() semantics are untouched, so /metrics and /stats
        agree by construction."""
        from ..obs.metrics import Sample

        def collect():
            snap = self.snapshot()
            out = [Sample(f"{prefix}_{k}_total", "counter",
                          f"serving counter {k!r}", float(snap[k]))
                   for k in _COUNTERS]
            out += [Sample(f"{prefix}_{k}", "gauge",
                           f"serving gauge {k!r}", float(snap[k]))
                    for k in _GAUGES if snap.get(k) is not None]
            return out

        registry.register_collector(collect)
        # per-tenant labeled series (bounded cardinality — see
        # tenancy.TenantCounts); engine-level registries never collide
        # with the router's because each server owns its own registry
        self.tenants.register_into(registry)
        # real histograms (cumulative le buckets + _sum/_count) next
        # to the reservoir quantiles: the reservoir gives honest
        # recent p50/p95, the histogram aggregates across scrapes and
        # fleet members the way Prometheus expects
        self._hist_latency = registry.histogram(
            f"{prefix}_request_latency_seconds",
            "end-to-end request latency on this engine")
        self._hist_queue_wait = registry.histogram(
            f"{prefix}_queue_wait_seconds",
            "time queued before dispatch/admission")
        self._hist_service = registry.histogram(
            f"{prefix}_service_seconds",
            "time being served after dispatch")
        self._hist_ttft = registry.histogram(
            f"{prefix}_ttft_seconds",
            "submit to first token (continuous batching)")

    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready view for /stats: the table's counters and gauges
        in its order, then what is computed from them."""
        cb_occ_recent = self.cb_slot_occupancy_recent()
        with self._lock:
            out = {name: (round(getattr(self, name), 6) if seconds
                          else getattr(self, name))
                   for name, seconds in _PLAIN}
            shares = {
                "batch_occupancy": _share(self.batched_requests,
                                          self.batch_slots),
                "cb_slot_occupancy": _share(
                    self.cb_active_slot_steps,
                    self.cb_steps * self.cb_slot_capacity),
                "cb_slot_occupancy_recent": _rounded(cb_occ_recent, 4),
                "cb_block_utilization": _share(
                    self.cb_block_use_steps,
                    self.cb_steps * self.cb_blocks_total),
                "cb_live_block_share": _share(
                    self.cb_live_block_steps,
                    self.cb_decode_steps * self.cb_table_blocks),
                "cb_prefill_fill_share": _share(
                    self.cb_prefill_rows, self.cb_prefill_width_rows),
                "cb_window_block_share": _share(
                    self.cb_window_block_steps, self.cb_live_block_steps)
                if self.cb_ring_blocks else None,
            }
        out["qps"] = round(self.qps(), 3)
        out["qps_recent"] = round(self.qps_recent(), 3)
        win = self.windowed()
        out["shed_rate_recent"] = win["shed_rate"]
        out["p95_latency_recent_ms"] = win["p95_latency_ms"]
        out["p99_latency_recent_ms"] = win["p99_latency_ms"]
        out["uptime_s"] = round(self.uptime_s(), 3)
        for q, pre in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
            out[f"{pre}_latency_ms"] = _rounded(
                self.latency_quantile(q), 3, 1e3)
        for kind, label, scale in (("queue_wait", "queue_wait_ms", 1e3),
                                   ("service", "service_ms", 1e3),
                                   ("ttft", "ttft_ms", 1e3),
                                   ("tokens_per_s", "tokens_per_s", 1.0)):
            for q, pre in ((0.50, "p50"), (0.95, "p95")):
                out[f"{pre}_{label}"] = _rounded(
                    self.split_quantile(kind, q), 3, scale)
        out.update(shares)
        out["by_tenant"] = self.tenants.snapshot()
        return out
