"""Continuous-batching scheduler: per-slot admission into a running
decode batch over the paged KV cache.

The static MicroBatcher ties a request's fate to its batch: the
compiled bucket program decodes all `max_new_tokens` for every row,
so one long generation holds every co-batched short request hostage
(head-of-line blocking).  Here a request occupies one of `cb_slots`
SLOTS instead:

  admit    a free slot at ANY decode step — reserve its worst-case
           blocks (ceil((plen + max_new) / block_len), so pool
           exhaustion is an admission decision, never a mid-decode
           OOM), run the ONE compiled prefill program into them, and
           join the running batch on the next step;
  step     the ONE compiled fixed-slot-count decode program advances
           every active slot a token; inactive slots ride along
           pointing at the null block (garbage out, masked, ignored).
           Where the model has a multi-token prediction module
           (`engine.drafts`) the step verifies the slot's draft and
           makes the next one, and a slot advances by ONE OR TWO
           tokens: how far is known on the device first (it rides from
           step to step there) and reaches the host with the tokens;
           `_hand_out` is the one place that hands tokens to requests;
  retire   on EOS / max-new / deadline the slot's blocks return to
           the free pool immediately and the slot is free for the
           next admission that very step.

Where `engine.chunks_prompts` (the cap lies past the widest compiled
prefill, `ServeSpec.cb_prefill_len`) a prompt is admitted the same way,
its blocks reserved whole before its first chunk, and then PREFILLED IN
CHUNKS over several steps (`_prefill_chunk`); one within the widest
rung is a single last chunk, by the same programs.  The rule is fixed here
and is no setting: while a prompt is in prefill one chunk goes out,
then one decode step of the running slots, and so on; ONE prompt is in
prefill at a time, and nothing is admitted behind it meanwhile (FIFO
holds).  Its slot is not active: it is not in a decode step's busy
set, the step sees the null block in its table row
(`PagedKVCache.table_array(hide=)`), and its state, tails and K/V rows
are written by its own chunks only.  A chunk but the last is read back
when the next one's turn comes (it ran while the decode step did); the
last is waited for as a whole-prompt prefill is, and the slot joins the
batch.  A cancel or a deadline between two chunks frees the blocks; the
state needs nothing (a first chunk starts from zeros).

Control plane vs data plane ("RPC Considered Harmful"): everything in
this file is host-side numpy bookkeeping; device work is exactly one
compiled-program invocation per prefill and one per decode step, both
AOT-compiled at warmup with (slots, blocks-per-slot, block_len, pool
size) as the only geometry — zero recompiles after warmup, same
guarantee as the bucket path.

Params atomicity: the loop reads `engine.params` ONCE per iteration
and threads it through that iteration's prefills and decode step, so
a hot-reload swap can never tear a step.  A stream that spans a
reload finishes on the new params from the next step on — each step
is internally consistent, which is the no-tear guarantee the static
path makes per batch.

Admission is strict FIFO: when the queue head cannot get a slot or
its blocks, nothing behind it jumps ahead (no starvation of long
prompts).  Shedding (`Overloaded` + Backoff retry_after) happens only
when the pending queue itself is full — the same story as the
MicroBatcher, with the block pool as the second bounded resource.

Fault sites: `serve.admit` (shed one submission), `serve.batch` (fail
one decode step — its active requests fail, the loop and server stay
up, `consecutive_batch_failures` moves toward the degraded verdict).

The stall account.  The loop thread's time is a row of LAPS, each
closed by one clock read where the next begins: `rest` (expire, the
admission's bookkeeping, the walk's counts, the table, the account of
the step before, the loop's lock), per admitted request `prefill` (its
hand-over) and `first_token` (the wait for it), the decode step's
`handover`, its `wait` (the host waiting on the device or the runtime
for the tokens) and its `emit` loop.  A lap over `STALL_S` is a stall:
counted (`ServeStats.observe_cb_stall`), and told with the step's
other laps in one `serve.cb_stall` event.  Always on: a trace of a
window's last seconds seldom holds the stall that cost the run.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from .. import obs
from ..utils import faults
from . import qos
from .batcher import Cancelled, DeadlineExpired, Overloaded
from .engine import InferenceEngine
from .kvcache import PagedKVCache
from .stats import ServeStats
from .tenancy import TenantRegistry


#: a lap of the loop longer than this is a stall: clear of every
#: legitimate lap (the longest is one prefill at a 2,048-row rung, some
#: 55 ms on a v5e; several admissions are several laps; nothing
#: compiles after warmup)
STALL_S = 0.5
#: the laps in which the host waits on the device or the runtime
WAIT_LAPS = ("wait", "first_token")
#: what a collect's time before its wait belongs to, by its `why`: 0 =
#: behind the next step's hand-over, 1 = a slot fell free and nothing
#: took it, 2 = inside an admission, behind the prefill's hand-over
COLLECT_BEHIND, COLLECT_DRAIN, COLLECT_ADMIT = 0, 1, 2
_LAP_BEFORE_COLLECT = ("handover", "rest", "prefill")


class StreamTicket:
    """One request's future, streaming edition: tokens are observable
    as they are produced (`events()` / `tokens()`), and `wait()`
    blocks for the final result dict exactly like `Ticket.wait`."""

    def __init__(self, corr: Optional[str] = None,
                 first_index: int = 0):
        self.corr = corr
        # absolute sequence number of the FIRST token this ticket will
        # emit: 0 for a fresh stream, `resume_from` for a failover
        # re-admission — the k-th emitted token is index
        # first_index + k, so both legs of a spliced stream number
        # consistently and the router can dedupe by index
        self.first_index = int(first_index)
        self._q: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self._result: Optional[Dict[str, Any]] = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done.is_set()

    # -- producer side (scheduler thread) -----------------------------------
    def _emit(self, token: int) -> None:
        self._q.put(("tok", int(token)))

    def _resolve(self, result: Dict[str, Any]) -> None:
        self._result = result
        self._done.set()
        self._q.put(("done", result))

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._done.set()
        self._q.put(("err", exc))

    # -- consumer side ------------------------------------------------------
    def events(self, timeout: Optional[float] = None):
        """Yield ("tok", int) per produced token, then one ("done",
        result).  Raises the failure; raises TimeoutError when no
        event arrives within `timeout` seconds."""
        while True:
            try:
                kind, payload = self._q.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError("stream stalled") from None
            if kind == "err":
                raise payload
            yield kind, payload
            if kind == "done":
                return

    def tokens(self, timeout: Optional[float] = None):
        """Yield produced token ids; returns at end-of-stream."""
        for kind, payload in self.events(timeout=timeout):
            if kind == "tok":
                yield payload

    def drain_events(self, max_n: int = 1,
                     timeout: Optional[float] = None,
                     linger_s: float = 0.0):
        """Batched drain for the flushed transports (serve/wire.py):
        block up to `timeout` for the FIRST event, then greedily take
        whatever is already queued — lingering at most `linger_s` for
        stragglers — up to `max_n` events per call.  One queue wakeup
        amortizes over the whole batch instead of one lock round-trip
        per token.  Returns a list of (kind, payload) tuples ending
        early at any non-"tok" event; raises the stream's failure and
        TimeoutError exactly like `events()`.  `max_n=1, linger_s=0`
        reproduces the unbatched behavior bit-for-bit."""
        try:
            evs = [self._q.get(timeout=timeout)]
        except queue.Empty:
            raise TimeoutError("stream stalled") from None
        if evs[0][0] == "err":
            raise evs[0][1]
        limit = max(int(max_n), 1)
        wait_until = (time.monotonic() + max(float(linger_s), 0.0)
                      if linger_s and linger_s > 0 else None)
        while len(evs) < limit and evs[-1][0] == "tok":
            try:
                if wait_until is None:
                    ev = self._q.get_nowait()
                else:
                    rem = wait_until - time.monotonic()
                    if rem <= 0:
                        ev = self._q.get_nowait()
                    else:
                        ev = self._q.get(timeout=rem)
            except queue.Empty:
                break
            if ev[0] == "err":
                # surface the failure only after the caller has
                # consumed the tokens drained before it: a mid-batch
                # error must not eat already-produced tokens
                evs.append(("failed", ev[1]))
                break
            evs.append(ev)
        return evs

    def wait(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        if not self._done.wait(timeout):
            raise TimeoutError("request still queued/running")
        if self._error is not None:
            raise self._error
        return self._result


@dataclass
class _CBRequest:
    tokens: np.ndarray            # (plen,) int32
    plen: int
    max_new: int
    ticket: StreamTicket
    t_submit: float
    deadline: Optional[float]
    corr: str
    priority: str = "interactive"
    tenant: str = "default"
    cancel_event: Optional[threading.Event] = None
    t_admit: float = 0.0
    # the conservative reservation: what the cache holds for the
    # request's whole life (`PagedKVCache.blocks_for`), once admitted
    nblocks: int = 0
    produced: List[int] = field(default_factory=list)
    # where the model drafts: the main model's log-probability of each
    # produced token, and every draft made for this request as (the
    # index in `produced` it stood for, its token, the module's
    # log-probability of it)
    logprobs: List[float] = field(default_factory=list)
    drafts: List[tuple] = field(default_factory=list)
    # trace context captured at submit — the prefill runs on the
    # scheduler loop thread, so its span needs an explicit anchor to
    # land in the submitting request's trace
    link: Any = None


@dataclass
class _Prefill:
    """The one prompt that is in prefill over several steps."""
    req: _CBRequest
    slot: int
    chunks: tuple                 # (start, real rows, width) of each
    trace: tuple                  # (trace id, parent) of its spans
    queued: float                 # seconds it waited for admission
    done: int = 0                 # chunks handed over
    flying: Any = None            # the last of them, not yet read


class ContinuousScheduler:
    """See module docstring.  One daemon loop thread; `submit` is
    called from any number of frontend threads."""

    def __init__(self, engine: InferenceEngine,
                 stats: Optional[ServeStats] = None, log_fn=print,
                 backoff: Optional[faults.Backoff] = None,
                 tenancy: Optional[TenantRegistry] = None):
        if not engine.spec.cb_on:
            raise ValueError("ContinuousScheduler needs a cb=on "
                             "ServeSpec")
        self.engine = engine
        self.spec = engine.spec
        self.stats = stats if stats is not None else engine.stats
        self.log = log_fn
        self._backoff = backoff if backoff is not None else \
            faults.Backoff(base=0.05, cap=2.0, seed=self.spec.seed)
        self.tenancy = tenancy if tenancy is not None \
            else TenantRegistry()
        self.kv: Optional[PagedKVCache] = None
        self._pending: deque = deque()
        self._cv = threading.Condition()
        self._req_ids = itertools.count(1)
        # per-class shed streaks/backoffs (see serve/qos.py); the
        # interactive stream matches the old single-class behavior
        self._class_backoffs = qos.ClassBackoffs(
            base=getattr(self._backoff, "base", 0.05),
            cap=getattr(self._backoff, "cap", 2.0),
            seed=getattr(self._backoff, "seed", self.spec.seed))
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # slot state (numpy, scheduler-thread-owned)
        s = self.spec.cb_slots
        self._active = np.zeros((s,), bool)
        self._ntoks = np.zeros((s,), np.int32)
        self._last = np.zeros((s,), np.int32)
        self._slot_req: List[Optional[_CBRequest]] = [None] * s
        # a decode step handed to the device whose tokens are not read
        # yet: (its tokens on the device, who held each slot then).
        # Only a step in which every slot was busy is left so
        self._flying: Optional[tuple] = None
        # the prompt that is prefilled in chunks, if one is
        self._prefilling: Optional[_Prefill] = None
        # the stall account (module docstring): the laps since the step
        # before was accounted, when the last one closed, whether one
        # of them was long; and how this step's decode went out
        self._laps: List[tuple] = []
        self._lap_at = 0.0
        self._stalled = False
        self._step_ahead = 0
        self._step_drained = 0

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ContinuousScheduler":
        if self._thread is not None:
            return self
        if self.engine.params is None:
            raise RuntimeError("engine has no params; call load()")
        spec = self.spec
        if spec.cb_pool_blocks - 1 < spec.cb_blocks_per_slot:
            # a pool that cannot hold even one worst-case request
            # would wedge every admission; refuse loudly at startup
            raise ValueError(
                f"cb_blocks={spec.cb_pool_blocks} cannot hold one "
                f"worst-case request ({spec.cb_blocks_per_slot} "
                f"blocks + null)")
        if self.kv is None:
            import jax
            dtype = jax.tree_util.tree_leaves(self.engine.params)[0].dtype
            self.kv = PagedKVCache(
                self.engine.net, num_slots=spec.cb_slots,
                max_blocks_per_slot=spec.cb_blocks_per_slot,
                num_blocks=spec.cb_pool_blocks,
                block_len=spec.cb_block_len, dtype=dtype)
            self.stats.gauge("cb_slot_capacity", spec.cb_slots)
            self.stats.gauge("cb_blocks_total", self.kv.usable_blocks)
            self.stats.gauge("cb_table_blocks",
                             spec.cb_slots * spec.cb_blocks_per_slot)
            # MemoryWatch: the pools just allocated, from the same
            # block geometry init_pools used (analytic == actual here)
            from ..obs import perf
            from .kvcache import pool_bytes, state_bytes
            perf.set_memory(
                "kv_pool",
                pool_bytes(self.engine.net, spec.cb_pool_blocks,
                           spec.cb_block_len, dtype, spec.cb_slots),
                scope=getattr(self.engine, "_perf_scope", "scheduler"))
            per = state_bytes(self.engine.net, spec.cb_block_len, dtype)
            self.stats.gauge("cb_slot_state_bytes", per["slot"])
            self.stats.gauge("cb_block_bytes", per["block"])
            self.stats.gauge("cb_window_block_bytes", per["window_block"])
            self.stats.gauge("cb_block_copy_bytes", per["block_copy"])
            self.stats.gauge("cb_window_block_copy_bytes",
                             per["window_block_copy"])
            self.stats.gauge("cb_ring_blocks", self.kv.ring_blocks)
            self.stats.gauge("cb_extent_blocks", self.kv.extent_blocks)
            # no request's first token waits on a program's first run
            self.kv.pools = self.engine.run_cb_prefill_rungs(
                self.engine.params, self.kv.pools)
        self._stop = False
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-cb", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        with self._cv:
            leftovers = list(self._pending)
            self._pending.clear()
            self.stats.gauge("queue_depth", 0)
        for r in leftovers:
            self.stats.count("failed")
            r.ticket._fail(RuntimeError("server shutting down"))
        self._flying = None
        if self._prefilling is not None:
            self.stats.count("failed")
            self._drop_prefill(RuntimeError("server shutting down"))
        for s, r in enumerate(self._slot_req):
            if r is not None:
                self._retire(s, "shutdown", self.engine.params_step)

    # -- admission ----------------------------------------------------------
    def submit(self, tokens, timeout: Optional[float] = None,
               max_new: Optional[int] = None,
               deadline: Optional[float] = None,
               priority: str = "interactive",
               tenant: Optional[str] = None,
               cancel_event: Optional[threading.Event] = None,
               resume_from: int = 0) -> StreamTicket:
        """Admit one generate request.  `max_new` caps this request's
        generation (clamped to spec.max_new_tokens).  `deadline`
        (absolute monotonic; wins over `timeout`) is the request's
        end-to-end budget — dead on arrival is refused before any
        queue or engine work (`expired_on_arrival`); `priority` drives
        brownout admission; a set `cancel_event` drops the request at
        the next scheduler touch (queued or mid-decode, counted
        `cancelled`).  Raises ValueError for a never-servable prompt
        or unknown priority (fail fast, the HTTP layer's 400),
        `Overloaded` when the pending queue is full or brownout sheds
        this class.

        `resume_from=n` re-admits a failed-over stream: `tokens` is
        (original prompt ‖ the n tokens already emitted), the fresh
        prefill re-derives the continuation (greedy decode is
        bit-deterministic given fingerprint + prefix, the PR 8 parity
        property), and the ticket numbers its output from absolute
        index n so the router can splice and dedupe.  Only
        max_new - n MORE tokens are generated and the block
        reservation covers exactly (grown prompt + remainder).  A
        resume past `max_new` or past an already-emitted EOS is a
        fast 400 (counted `rejected`, zero engine steps) — the
        original stream was already complete."""
        spec = self.spec
        tenant = self.tenancy.label(tenant)
        arr = np.asarray(tokens, np.int32).reshape(-1)
        if arr.size < 1:
            self.stats.count("rejected")
            raise ValueError("empty prompt")
        if arr.size > spec.cb_max_prompt_len:
            self.stats.count("rejected")
            raise ValueError(
                f"prompt length {arr.size} exceeds the cb prompt cap "
                f"({spec.cb_max_prompt_len}); not servable")
        if arr.size > self.engine.cb_prompt_limit:
            self.stats.count("rejected")
            raise ValueError(
                f"prompt length {arr.size} exceeds the widest prefill "
                f"program ({spec.cb_prefill_len} rows), and a longer "
                f"prompt goes in chunks only where every layer can carry "
                f"a chunk on: this model's "
                f"{', '.join(self.engine.cb_unchunked)} cannot; not "
                f"servable")
        mn = int(max_new) if max_new is not None else \
            int(spec.max_new_tokens)
        if mn < 1:
            self.stats.count("rejected")
            raise ValueError(f"max_new must be >= 1, got {mn}")
        mn = min(mn, int(spec.max_new_tokens))
        resume_from = int(resume_from)
        if resume_from < 0:
            self.stats.count("rejected")
            raise ValueError(f"resume_from must be >= 0, got "
                             f"{resume_from}")
        if resume_from > 0:
            if resume_from >= mn:
                self.stats.count("rejected")
                raise ValueError(
                    f"resume_from {resume_from} is past max_new {mn}; "
                    f"the stream already completed")
            if resume_from > arr.size:
                self.stats.count("rejected")
                raise ValueError(
                    f"resume_from {resume_from} exceeds the "
                    f"{arr.size}-token prompt+prefix")
            if spec.eos_id is not None and \
                    np.any(arr[-resume_from:] == int(spec.eos_id)):
                self.stats.count("rejected")
                raise ValueError(
                    f"resume_from {resume_from} is past EOS: the "
                    f"emitted prefix already contains eos_id "
                    f"{spec.eos_id}")
            mn = mn - resume_from     # only the remainder decodes
            self.stats.count("resumed")
        deadline = qos.resolve_deadline(timeout, deadline,
                                        spec.request_timeout_s)
        now = time.monotonic()
        if deadline is not None and now >= deadline:
            # dead on arrival: refuse before it queues — zero engine
            # steps burned on a client that already gave up
            self.stats.count("expired_on_arrival")
            raise DeadlineExpired(
                f"dead on arrival: deadline passed "
                f"{now - deadline:.3f}s before admission")
        # inherit the caller's correlation chain when one is open on
        # this thread (the HTTP handler's serve.request span) instead
        # of unconditionally minting a fresh cbreq-N — the old mint
        # silently severed router→scheduler correlation on every hop
        corr = obs.current_corr() or f"cbreq-{next(self._req_ids)}"
        link = obs.trace_context()
        req = _CBRequest(tokens=arr, plen=int(arr.size), max_new=mn,
                         ticket=StreamTicket(corr,
                                             first_index=resume_from),
                         t_submit=now, deadline=deadline, corr=corr,
                         priority=priority, tenant=tenant,
                         cancel_event=cancel_event, link=link)
        quota = self.tenancy.queue_quota(tenant, spec.queue_capacity)
        with obs.span("scheduler.admit", corr=corr,
                      plen=int(arr.size), max_new=mn,
                      priority=priority, tenant=tenant):
            try:
                faults.maybe_fault("serve.admit")
            except faults.FaultError as e:
                self._shed(f"admission fault: {e}", corr=corr,
                           priority=priority, tenant=tenant)
            with self._cv:
                if self._stop:
                    raise RuntimeError("scheduler is stopped")
                depth = len(self._pending)
                tdepth = sum(1 for r in self._pending
                             if r.tenant == tenant)
                if depth >= spec.queue_capacity or \
                        tdepth >= quota or \
                        not self._brownout_admits(priority, depth,
                                                  tenant):
                    pass          # shed outside the happy path below
                else:
                    self._pending.append(req)
                    self._class_backoffs.reset(priority,
                                               tenant=tenant)
                    self.stats.count("submitted")
                    self.stats.tenants.count("submitted", tenant)
                    self.stats.gauge("queue_depth", len(self._pending))
                    self._cv.notify()
                    return req.ticket
            if depth >= spec.queue_capacity:
                why = f"queue full ({spec.queue_capacity} requests)"
            elif tdepth >= quota:
                why = (f"tenant {tenant} queue quota full "
                       f"({tdepth}/{quota} of {spec.queue_capacity})")
            else:
                why = (f"brownout: queue {depth}/"
                       f"{spec.queue_capacity} sheds {priority}")
            self._shed(why, corr=corr, priority=priority,
                       tenant=tenant)

    def _brownout_admits(self, priority: str, depth: int,
                         tenant: str = "default") -> bool:
        """Class-aware admission under pressure: best_effort is shed
        once the pending queue is `brownout_be_frac` full, batch at
        `brownout_batch_frac`; interactive rides to the cap.  A
        tenant's spec can tighten either fraction for ITS traffic."""
        if priority == "interactive":
            return True
        be, batch = self.tenancy.brownout_fracs(
            tenant, self.spec.brownout_be_frac,
            self.spec.brownout_batch_frac)
        frac = be if priority == "best_effort" else batch
        return depth < max(int(frac * self.spec.queue_capacity), 1)

    def _shed(self, why: str, corr: Optional[str] = None,
              priority: str = "interactive",
              tenant: str = "default") -> None:
        self.stats.count("shed")
        self.stats.count(f"shed_{priority}")
        self.stats.tenants.count("shed", tenant)
        retry = self._class_backoffs.shed_delay(priority,
                                                tenant=tenant)
        obs.emit_event("serve.shed", why=why, corr=corr,
                       priority=priority, tenant=tenant,
                       retry_after=round(retry, 4))
        raise Overloaded(f"request shed ({why}); retry after "
                         f"{retry:.3f}s", retry_after=retry)

    # -- the loop -----------------------------------------------------------
    def _loop(self) -> None:
        self._lap_at = time.perf_counter()
        while True:
            with self._cv:
                idle = False
                while (not self._pending and not self._active.any()
                       and self._flying is None
                       and self._prefilling is None and not self._stop):
                    idle = True
                    # the profiler's alone: an idle server's twenty
                    # waits a second would fill a session's tracer
                    with obs.device_span("scheduler.wait", {}):
                        self._cv.wait(0.05)
                if self._stop:
                    return
            if idle:
                self._lap_at = time.perf_counter()   # no lap of a step
            self._iterate()

    def _lap(self, name: str) -> None:
        """The loop's time since the last lap closed is `name`'s."""
        now = time.perf_counter()
        took = now - self._lap_at
        self._lap_at = now
        self._laps.append((name, took))
        if took > STALL_S:
            self._stalled = True

    def _lap_wait(self, before: str, wait: str) -> None:
        """After an engine call that ended in a wait on the device: up
        to where the wait began the time is `before`'s, the wait itself
        `wait`'s.  The engine stamped both ends (`cb_wait`); where it
        did not (a stand-in for the call), the whole is the wait's."""
        t, now = self.engine.cb_wait
        if t < self._lap_at:
            return self._lap(wait)
        lead, waited = t - self._lap_at, now - t
        self._lap_at = now
        self._laps += ((before, lead), (wait, waited))
        if lead > STALL_S or waited > STALL_S:
            self._stalled = True

    def _account_laps(self) -> None:
        """Close the step's laps; tell of each that was long."""
        if self._stalled:
            self._stalled = False
            total: Dict[str, float] = {}
            for name, took in self._laps:
                total[name] = total.get(name, 0.0) + took
            for name, took in self._laps:
                if took > STALL_S:
                    self.stats.observe_cb_stall(took, name in WAIT_LAPS)
                    obs.emit_event(
                        "serve.cb_stall", lap=name, seconds=round(took, 4),
                        laps={k: round(v, 4) for k, v in total.items()},
                        active=int(self._active.sum()),
                        pending=len(self._pending))
        self._laps.clear()

    def _iterate(self) -> None:
        """One scheduler step: expire, admit, decode, account.  The
        step, its admissions, its decode, each read of a step in
        flight and each emit loop are spans (docs/OBSERVABILITY.md, the
        per-token path): what the host does between two decode programs
        is read off a device trace by these names.  While something
        records, the step carries the stall account's running values,
        so that a trace of a run's last seconds holds the whole run's."""
        st = self.stats
        attrs = ({"stalls": st.cb_stalls,
                  "stall_ms": int(1e3 * st.cb_stall_seconds),
                  "stall_wait_ms": int(1e3 * st.cb_stall_wait_seconds)}
                 if obs.tracing() else {})
        self._step_ahead = self._step_drained = 0
        with obs.span("scheduler.step", **attrs):
            # ONE params read covers this step's prefills AND decode —
            # the per-step no-tear guarantee (see module docstring)
            params = self.engine.params
            step_no = self.engine.params_step
            self._expire_pending(time.monotonic())
            try:
                # an unlocked peek: a submit that lands just after it
                # is admitted by the next step, as it always was
                if (self._pending and self._prefilling is None
                        and not self._active.all()):
                    with obs.span("scheduler.admit_pending"):
                        admitted = self._admit_pending(params, step_no)
                    if admitted:
                        self.stats.count("cb_admit_steps")
                if self._prefilling is not None:
                    # an admission's hand-over, a chunk a step
                    with obs.span("scheduler.admit_pending"):
                        self._prefill_chunk(params, step_no)
                if self._flying is not None and not self._active.all():
                    # a slot fell free and nothing took it
                    self._collect(step_no, COLLECT_DRAIN)
                active = int(self._active.sum())
                walked = {"table": 0, "window": 0, "copies": 0}
                if active:
                    # what the decode program walks, once a kind of
                    # paged layer: every slot's table row up to its
                    # write position (an idle slot one block), and of
                    # its ring what the window touches
                    walked = self.kv.walked_blocks(self._ntoks)
                    self._decode_step(params, step_no, active)
            except Exception as e:  # noqa: BLE001 — fail step, keep serving
                self._fail_step(e)
                return
            self._account_laps()
            if self.kv is not None:
                self.stats.observe_cb_step(
                    int(self._active.sum()), self.kv.blocks_in_use,
                    walked["table"], walked["window"],
                    ahead=self._step_ahead, drained=self._step_drained,
                    copies=walked["copies"])
                self.stats.gauge("cb_blocks_in_use", self.kv.blocks_in_use)

    def _expire_pending(self, now: float) -> None:
        with self._cv:
            keep: deque = deque()
            expired: List[_CBRequest] = []
            cancelled: List[_CBRequest] = []
            for r in self._pending:
                if r.cancel_event is not None and \
                        r.cancel_event.is_set():
                    cancelled.append(r)
                elif r.deadline is not None and now > r.deadline:
                    expired.append(r)
                else:
                    keep.append(r)
            self._pending = keep
            self.stats.gauge("queue_depth", len(self._pending))
        for r in cancelled:
            self.stats.count("cancelled")
            r.ticket._fail(Cancelled(
                "cancelled by caller while queued"))
        for r in expired:
            self.stats.count("expired")
            r.ticket._fail(DeadlineExpired(
                f"deadline passed after {now - r.t_submit:.3f}s in "
                f"queue"))

    def _admit_pending(self, params, step_no: int) -> int:
        """Admit the queue head while a slot AND its blocks are free;
        returns how many requests were admitted (prefilled).
        FIFO with one tenancy carve-out: a head blocked ONLY by its
        own tenant's slot/KV quota is stepped over (its quota is its
        own blast radius — it must not wedge the other tenants), but
        a head blocked by a GLOBAL resource (block pool too empty)
        still holds everything behind it, preserving the
        no-starvation guarantee for long prompts."""
        spec = self.spec
        admitted = 0
        while True:
            free = np.flatnonzero(~self._active)
            with self._cv:
                if (not self._pending or free.size == 0
                        or self._prefilling is not None):
                    return admitted
                # per-tenant occupancy among the ACTIVE slots (slot
                # count + conservative block reservations), once per
                # admission round
                slots_t: Dict[str, int] = {}
                blocks_t: Dict[str, int] = {}
                for r in self._slot_req:
                    if r is not None:
                        slots_t[r.tenant] = \
                            slots_t.get(r.tenant, 0) + 1
                        blocks_t[r.tenant] = \
                            blocks_t.get(r.tenant, 0) + r.nblocks
                req = None
                for i, cand in enumerate(self._pending):
                    # the cache reckons a reservation, rounding and all
                    need = self.kv.blocks_for(cand.plen + cand.max_new)
                    if not self.kv.can_admit(need):
                        # global pool pressure: the effective head
                        # waits, nothing overtakes it
                        return admitted
                    squota = self.tenancy.slot_quota(
                        cand.tenant, spec.cb_slots)
                    bquota = self.tenancy.kv_quota(
                        cand.tenant, self.kv.usable_blocks)
                    if slots_t.get(cand.tenant, 0) + 1 > squota or \
                            blocks_t.get(cand.tenant, 0) + \
                            need > bquota:
                        continue  # ITS quota, not ours: step over
                    req = cand
                    req.nblocks = need
                    del self._pending[i]
                    break
                if req is None:
                    return admitted   # every pending head is quota-held
                self.stats.gauge("queue_depth", len(self._pending))
            # last-instant guard AFTER the pop, BEFORE any blocks or
            # engine work: an engine never prefills a request that is
            # already dead or cancelled
            now = time.monotonic()
            if req.cancel_event is not None and \
                    req.cancel_event.is_set():
                self.stats.count("cancelled")
                req.ticket._fail(Cancelled(
                    "cancelled by caller before prefill"))
                continue
            if req.deadline is not None and now >= req.deadline:
                self.stats.count("expired")
                req.ticket._fail(DeadlineExpired(
                    f"deadline passed after {now - req.t_submit:.3f}s "
                    f"in queue"))
                continue
            slot = int(free[0])
            req.t_admit = now
            queued = now - req.t_submit
            self.stats.observe_admission(queued)
            trace_id, parent = req.link if req.link else (None, None)
            session = obs.active()
            if session is not None:
                # the wait is over only now, so it is recorded post hoc
                session.tracer.add_span(
                    "scheduler.queue", time.perf_counter() - queued,
                    queued, corr=req.corr, trace=trace_id,
                    parent=parent, plen=req.plen, tenant=req.tenant)
            self.kv.alloc(slot, req.nblocks)
            if self.engine.chunks_prompts:
                # by the chunk programs, from this step on (a prompt
                # within the widest rung is one last chunk), and
                # nothing is admitted behind it meanwhile
                self._prefilling = _Prefill(
                    req, slot, spec.cb_chunks(req.plen),
                    (trace_id, parent), queued)
                continue
            # the prompt's own rung of the prefill ladder
            width = spec.cb_prefill_width(req.plen)
            toks = np.zeros((1, width), np.int32)
            toks[0, :req.plen] = req.tokens
            self._lap("rest")
            try:
                with obs.span("scheduler.prefill", corr=req.corr,
                              trace=trace_id, parent=parent,
                              slot=slot, plen=req.plen, width=width,
                              queue_ms=queued * 1e3):
                    row = self.kv.prefill_target(
                        slot, width // spec.cb_block_len)
                    if self._flying is None:
                        tok0, self.kv.pools = self.engine.run_cb_prefill(
                            params, self.kv.pools, toks, req.plen, row)
                    else:
                        # behind the step in flight, whose tokens are
                        # read (in time) while the prefill runs
                        first, self.kv.pools = \
                            self.engine.dispatch_cb_prefill(
                                params, self.kv.pools, toks, req.plen, row)
                        self._collect(step_no, COLLECT_ADMIT)
                        tok0 = self.engine.fetch_cb_prefill(first)
                    self._lap_wait("prefill", "first_token")
            except Exception as e:  # noqa: BLE001 — fail req, keep going
                # the slot is not in _slot_req yet: clean it here so
                # the blocks cannot leak, fail only this request
                self.kv.free(slot)
                self.stats.count("failed")
                self.stats.observe_batch_failure()
                self.log(f"warning: cb prefill failed "
                         f"({type(e).__name__}: {e}); request "
                         f"{req.corr} failed, server continues")
                req.ticket._fail(RuntimeError(f"prefill failed: {e}"))
                return admitted
            admitted += 1
            self.stats.observe_cb_prefill(
                req.plen, width, width in self.engine.cb_flash_widths)
            if self.engine.drafts:
                first, tok0 = tok0, tok0.token
                req.logprobs.append(first.logprob)
                req.drafts.append((1, first.draft, first.draft_logprob))
            self._join(slot, req, tok0, step_no)

    def _join(self, slot: int, req: _CBRequest, tok0: int,
              step_no: int) -> None:
        """A prefilled request takes its slot in the running batch and
        is handed its first token."""
        self._slot_req[slot] = req
        self._active[slot] = True
        self._ntoks[slot] = req.plen
        self._last[slot] = tok0
        req.produced.append(tok0)
        req.ticket._emit(tok0)
        now = time.monotonic()
        self.stats.observe_ttft(now - req.t_submit)
        self._maybe_retire(slot, tok0, step_no, now)

    def _prefill_chunk(self, params, step_no: int) -> None:
        """The step's one chunk of the prompt in prefill (module
        docstring): the chunk before is read (it ran while the decode
        step did), the next goes out; the last is waited for, and the
        request joins the batch."""
        pf, engine = self._prefilling, self.engine
        req, slot = pf.req, pf.slot
        try:
            if pf.flying is not None:
                flying, pf.flying = pf.flying, None
                self._chunk_read(pf, *engine.fetch_cb_chunk(flying)[1:])
            now = time.monotonic()
            if req.cancel_event is not None and req.cancel_event.is_set():
                self.stats.count("cancelled")
                return self._drop_prefill(Cancelled(
                    f"cancelled by caller after {pf.done} of "
                    f"{len(pf.chunks)} prefill chunks"))
            if req.deadline is not None and now >= req.deadline:
                self.stats.count("expired")
                return self._drop_prefill(DeadlineExpired(
                    f"deadline passed after {pf.done} of "
                    f"{len(pf.chunks)} prefill chunks"))
            start, rows, width = pf.chunks[pf.done]
            last = pf.done == len(pf.chunks) - 1
            toks = np.zeros((1, width), np.int32)
            toks[0, :rows] = req.tokens[start:start + rows]
            attrs = {"queue_ms": pf.queued * 1e3} if pf.done == 0 else {}
            self._lap("rest")
            with obs.span("scheduler.prefill", corr=req.corr,
                          trace=pf.trace[0], parent=pf.trace[1], slot=slot,
                          plen=req.plen, width=width, start=start,
                          chunk=pf.done, of=len(pf.chunks), **attrs):
                flying, self.kv.pools = engine.dispatch_cb_chunk(
                    params, self.kv.pools, toks, rows, start, last,
                    self.kv.chunk_target(slot))
                pf.done += 1
                if self._flying is not None:
                    # behind the step in flight, as an admission's prefill
                    self._collect(step_no, COLLECT_ADMIT)
                if not last:
                    pf.flying = flying
                    return self._lap("prefill")
                tok0, *grouped = engine.fetch_cb_chunk(flying)
                self._lap_wait("prefill", "first_token")
        except Exception as e:  # noqa: BLE001 — fail req, keep going
            self.stats.count("failed")
            self.stats.observe_batch_failure()
            self.log(f"warning: cb prefill chunk failed "
                     f"({type(e).__name__}: {e}); request {req.corr} "
                     f"failed, server continues")
            return self._drop_prefill(RuntimeError(f"prefill failed: {e}"))
        self._chunk_read(pf, *grouped)
        self._prefilling = None
        self.stats.count("cb_chunked_prompts")
        self.stats.count("cb_admit_steps")
        self.stats.observe_cb_prefill(
            req.plen, sum(c[2] for c in pf.chunks),
            all(c[2] in engine.cb_flash_widths for c in pf.chunks))
        self._join(slot, req, tok0, step_no)

    def _chunk_read(self, pf: _Prefill, grouped_rows: int,
                    tile_rows: int) -> None:
        """The account of the chunk of `pf` that was just read back."""
        start, rows, width = pf.chunks[pf.done - 1]
        slots = self.engine.grouped_row_slots(width, rows)
        # a chunk the dense walk took multiplied every row by every expert
        self.stats.observe_cb_chunk(rows, start,
                                    grouped_rows if slots else 0, slots,
                                    tile_rows)

    def _drop_prefill(self, exc: BaseException) -> None:
        """The prompt in prefill is given up between two chunks: its
        blocks go back; its state needs nothing (a first chunk starts
        from zeros)."""
        pf, self._prefilling = self._prefilling, None
        self.kv.free(pf.slot)
        pf.req.ticket._fail(exc)

    def _decode_step(self, params, step_no: int, active: int) -> None:
        full = active == len(self._active)
        # ahead: it goes to the device before the step before it is read
        self._step_ahead = int(full and self._flying is not None)
        with obs.span("scheduler.decode", active=active,
                      ahead=self._step_ahead):
            faults.maybe_fault("serve.batch")
            if full:
                self._decode_ahead(params, step_no)
                return
            pf = self._prefilling
            tables = self.kv.table_array(hide=None if pf is None else pf.slot)
            if pf is not None and pf.done:
                self.stats.count("cb_steps_between_chunks")
            self._lap("rest")
            nxt, self.kv.pools = self.engine.run_cb_decode(
                params, self.kv.pools, self._last, self._ntoks, tables)
            self._lap_wait("handover", "wait")
            busy = np.flatnonzero(self._active)
            if not self.engine.drafts:
                self._ntoks[busy] += 1
            self._hand_out(nxt, [(int(s), self._slot_req[s]) for s in busy],
                           step_no)

    def _decode_ahead(self, params, step_no: int) -> None:
        """Every slot is busy: nothing can be admitted before one
        retires, so no request waits on this step, and it goes to the
        device BEFORE the step before it is read (its tokens go in as
        they lie on the device; where the model drafts, so do the rows
        each slot holds, which the host does not know yet).  The host's
        part of a step then runs while the device works.  A slot that
        the step before retires has made one step too many in this one:
        `_hand_out` drops its tokens, and what it wrote lies in blocks
        and state that an admission overwrites."""
        before = self._flying
        # copies: the host's arrays change before the device has run
        tokens = self._last.copy() if before is None else before[0]
        ntoks, tables = self._ntoks.copy(), self.kv.table_array()
        self._lap("rest")
        nxt, self.kv.pools = self.engine.dispatch_cb_decode(
            params, self.kv.pools, tokens, ntoks, tables)
        if not self.engine.drafts:
            self._ntoks += 1
        self._flying = (nxt, list(self._slot_req))
        if before is not None:
            self._collect(step_no, COLLECT_BEHIND, before)
        else:
            self._lap("handover")

    def _collect(self, step_no: int, why: int,
                 flying: Optional[tuple] = None) -> None:
        """Read a dispatched step's tokens and hand them out: to the
        requests that held their slots then and still do.  `why`: one
        of the `COLLECT_` three; but for the first the step in flight
        is taken down with none handed over behind it."""
        with obs.span("scheduler.collect", why=why):
            if flying is None:
                flying, self._flying = self._flying, None
                self._step_drained += 1
            nxt = self.engine.fetch_cb_decode(flying[0])
            self._lap_wait(_LAP_BEFORE_COLLECT[why], "wait")
            self._hand_out(nxt, list(enumerate(flying[1])), step_no)

    def _hand_out(self, step, holders, step_no: int) -> None:
        """THE emit loop: a fetched step's tokens to the requests that
        held their slots when it went out (`holders`, (slot, request))
        and still do; one a slot, or where the model drafts the one or
        two the step yielded (`StepTokens`), each with its
        log-probability, and the rows the slot holds now.  A request
        that retires on its first of two tokens drops the second; one
        retired since the step went out made a step too many."""
        drafts = self.engine.drafts
        now = time.monotonic()
        # plain lists: an index into one costs a fifth of one into an array
        if drafts:
            tokens, counts = step.tokens.tolist(), step.count.tolist()
            logprobs = step.logprobs.tolist()
        else:
            tokens = step.tolist()
        live, last = self._slot_req, self._last
        slots = emitted = accepted = 0
        with obs.span("scheduler.emit", slots=len(holders)) as sp:
            for slot, req in holders:
                if req is not live[slot]:
                    continue
                slots += 1
                if drafts:
                    mine = tokens[slot][:counts[slot]]
                    accepted += len(mine) - 1
                    self._ntoks[slot] = step.ntoks[slot]
                else:
                    mine = (tokens[slot],)
                for j, tok in enumerate(mine):
                    last[slot] = tok
                    req.produced.append(tok)
                    if drafts:
                        req.logprobs.append(logprobs[slot][j])
                    req.ticket._emit(tok)
                    emitted += 1
                    self._maybe_retire(slot, tok, step_no, now)
                    if live[slot] is not req:
                        break
                else:
                    if drafts:
                        req.drafts.append((len(req.produced),
                                           int(step.draft[slot]),
                                           float(step.draft_logprob[slot])))
            sp.set(tokens=emitted)
        self.stats.observe_cb_emit(slots, emitted,
                                   accepted if drafts else None)
        self._lap("emit")

    def _maybe_retire(self, slot: int, tok: int, step_no: int,
                      now: float) -> None:
        req = self._slot_req[slot]
        eos = self.spec.eos_id
        if req.cancel_event is not None and req.cancel_event.is_set():
            # hedge loser mid-decode: free the slot THIS step — the
            # winner's fleet keeps the capacity, not a dead stream
            self._retire(slot, "cancelled", step_no)
        elif eos is not None and tok == eos:
            self._retire(slot, "eos", step_no)
        elif len(req.produced) >= req.max_new:
            self._retire(slot, "length", step_no)
        elif req.deadline is not None and now > req.deadline:
            self._retire(slot, "deadline", step_no)

    def _retire(self, slot: int, finish: str, step_no: int) -> None:
        req = self._slot_req[slot]
        self.kv.free(slot)
        self._active[slot] = False
        self._ntoks[slot] = 0
        self._last[slot] = 0
        self._slot_req[slot] = None
        now = time.monotonic()
        if finish == "shutdown":
            self.stats.count("failed")
            req.ticket._fail(RuntimeError("server shutting down"))
            return
        if finish == "cancelled":
            # not a completion, not a failure: no latency sample, no
            # strike — the caller asked for it (hedge loser)
            self.stats.count("cancelled")
            obs.emit_event("serve.cb_retire", corr=req.corr,
                           finish=finish, tokens=len(req.produced),
                           slot=slot)
            req.ticket._fail(Cancelled(
                "cancelled by caller mid-decode"))
            return
        self.stats.observe_latency(now - req.t_submit)
        # the queue wait was observed at admission (observe_admission)
        self.stats.observe_request(None, now - req.t_admit,
                                   len(req.produced))
        self.stats.tenants.count("completed", req.tenant)
        self.stats.tenants.observe_latency(now - req.t_submit,
                                           req.tenant)
        obs.emit_event("serve.cb_retire", corr=req.corr,
                       finish=finish, tokens=len(req.produced),
                       slot=slot, tenant=req.tenant)
        result = {"tokens": list(req.produced), "step": step_no,
                  "finish": finish, "slots": self.spec.cb_slots}
        if self.engine.drafts:
            result.update(logprobs=list(req.logprobs),
                          drafts=list(req.drafts))
        req.ticket._resolve(result)

    def _fail_step(self, e: BaseException) -> None:
        """A compiled call raised: fail every in-flight request, free
        everything, keep the loop alive (the batcher's degrade
        story)."""
        n = int(self._active.sum())
        self._flying = None
        self._account_laps()
        if self._prefilling is not None:
            # its chunks wrote the pools the failed call held
            n += 1
            self._drop_prefill(RuntimeError(f"decode step failed: {e}"))
        self.stats.count("failed", n)
        self.stats.observe_batch_failure()
        self.log(f"warning: cb decode step failed "
                 f"({type(e).__name__}: {e}); {n} request(s) failed, "
                 f"server continues")
        err = (e if isinstance(e, faults.FaultError)
               else RuntimeError(f"decode step failed: {e}"))
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            req = self._slot_req[slot]
            self.kv.free(slot)
            self._active[slot] = False
            self._ntoks[slot] = 0
            self._last[slot] = 0
            self._slot_req[slot] = None
            req.ticket._fail(err)

    # -- reads --------------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        out = {"pending": len(self._pending),
               "active_slots": int(self._active.sum()),
               "slots": self.spec.cb_slots}
        if self.kv is not None:
            out["kv"] = self.kv.snapshot()
        return out
