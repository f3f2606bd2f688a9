"""Inference engine: compiled-per-bucket decode programs over the
latest *healthy* checkpoint, with atomic hot-reload.

The serving hot path must never trace ("RPC Considered Harmful" — keep
per-request overhead off the device path): the engine AOT-compiles one
generate and/or predict executable per (batch, prompt_len) shape
bucket (`jax.jit(...).lower(...).compile()`), and thereafter only ever
invokes Compiled executables — a hard guarantee of zero recompiles,
made observable through `ServeStats.compiles` (incremented ONLY inside
`_compile`, so a warmed server must hold the counter constant).

Variable-length prompts are LEFT-padded to the bucket length with a
per-key validity mask (see `_attn_cached`'s `kmask`): RoPE rotations
are relative, so left-padding preserves every attended (query, key)
distance, the last real prompt token sits at a uniform position P-1
across the batch, and masked pad keys contribute exactly zero after
softmax.  Padded batched decode therefore matches unpadded decode
bit-for-bit in f32.

Hot reload (`poll_reload`) is cheap-poll + atomic-swap: compare
`CheckpointManager.fingerprint()` (two stats, no reads); on change,
`restore(skip_unhealthy=True)` walks back past numerically suspect
snapshots, the new params are placed on device and swapped in with a
single attribute assignment.  Dispatchers read `engine.params` once
per micro-batch, so in-flight batches finish on the params they
started with — a reload never drops a request.  Every degradation is
a counted non-event: a failed restore keeps the old params live
(`reload_failures`, fingerprint unchanged so the next poll retries);
a walk-back that lands on the already-served step is `reloads_refused`
(fingerprint recorded so it is not re-attempted every poll); a poll
that races a LIVE writer (a step list or MANIFEST.json caught
mid-rename/half-written) is `torn_polls` — surfaced as "no change",
never an exception and never a reload off the torn read, so a trainer
publishing into the served workspace is safe by construction
(docs/PIPELINE.md).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..obs import perf
from ..models.generate import (_sample, draft_cached, draft_paged,
                               forward_cached, forward_chunk, forward_paged,
                               init_cache, mtp_module, project_head,
                               sample_logprob, scatter_prefill,
                               unchunked_layers, verify_draft)
from ..ops.attention import singa_flash_part, singa_flash_prefill
from ..ops.moe import grouped_run
from ..utils import faults
from ..utils.checkpoint import CheckpointManager
from .kvcache import init_pools, slot_behind_row
from .stats import ServeStats

MODES = ("generate", "predict")


# The narrowest compiled cb prefill (`ServeSpec.cb_prefill_widths`).
# Under about 240 rows (197 TFLOP/s over 819 GB/s, 2 FLOP and 2 bytes a
# parameter) a prefill is bound by reading the weights, so a narrower
# program is no faster; and each rung costs set-up time in every run.
CB_PREFILL_FLOOR = 256


@dataclass(frozen=True)
class ServeSpec:
    """Serving configuration.  `buckets` is the closed set of compiled
    (batch, prompt_len) shapes — every request is padded into one of
    them, so after `warmup()` no program is ever compiled again.
    `bucket_for` picks the smallest admissible bucket: fewest padded
    slots first, then shortest prompt padding."""
    buckets: Tuple[Tuple[int, int], ...] = ((1, 16), (4, 16), (8, 32))
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    eos_id: Optional[int] = None
    pad_id: int = 0
    queue_capacity: int = 64
    batch_window_s: float = 0.01
    request_timeout_s: float = 5.0
    reload_poll_s: float = 1.0
    degraded_after: int = 3   # consecutive failed batches -> degraded
    seed: int = 0
    # engine.stall fault site: the host-side sleep the silent "stall"
    # kind latches onto this engine's every compiled call — the
    # deterministic straggler for the hedging bench
    stall_fault_s: float = 0.25
    # priority-aware brownout (serve/qos.py): under queue pressure
    # admission sheds lowest class first.  best_effort is shed once the
    # queue is `brownout_be_frac` full, batch at `brownout_batch_frac`;
    # interactive sheds only when the queue is actually full
    brownout_be_frac: float = 0.5
    brownout_batch_frac: float = 0.75
    # continuous batching (serve/scheduler.py): cb=on replaces the
    # static generate buckets with a paged-KV slot scheduler.  The
    # compiled geometry is (cb_slots, blocks-per-slot, cb_block_len,
    # pool size) ONLY — one decode step and a short ladder of prefill
    # programs (`cb_prefill_widths`) regardless of traffic mix, so the
    # zero-recompile guarantee holds
    cb: str = "off"           # "on" | "off"
    cb_slots: int = 8         # concurrent decode slots (S)
    cb_block_len: int = 16    # tokens per KV block
    cb_blocks: int = 0        # pool size incl. null block; 0 = auto
    cb_prompt_cap: int = 0    # longest admissible prompt; 0 = widest
                              # bucket prompt_len
    cb_prefill_rung: int = 0  # widest compiled prefill; 0 = the cap.  A
                              # cap past it is served in chunks of it
                              # (`cb_chunks`), where every layer of the
                              # model can carry a chunk on
    # model family this engine serves: half of the (family, step)
    # serving fingerprint.  Engines advertise it on /healthz, the
    # router dispatches a request's `model` onto matching members
    # only, and a failover resume must match BOTH halves.  Parsed
    # lowercase by the str branch of `parse`
    family: str = "default"
    # token flush batching (serve/wire.py): streamed tokens go out in
    # frames/chunks of up to `flush_tokens`, lingering `flush_ms` for
    # stragglers — on both the binary and HTTP ndjson surfaces.  The
    # first token of a stream always flushes alone (first-token
    # latency is a gated stage).  flush_tokens=1 disables batching
    flush_tokens: int = 8
    flush_ms: float = 4.0

    def __post_init__(self):
        norm = []
        for b in self.buckets:
            bb, pp = int(b[0]), int(b[1])
            if bb < 1 or pp < 1:
                raise ValueError(f"bad bucket {b!r}: batch and "
                                 f"prompt_len must be >= 1")
            norm.append((bb, pp))
        if not norm:
            raise ValueError("ServeSpec needs at least one bucket")
        object.__setattr__(self, "buckets",
                           tuple(sorted(set(norm),
                                        key=lambda c: (c[1], c[0]))))
        if int(self.max_new_tokens) < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{self.max_new_tokens}")
        if int(self.queue_capacity) < 1:
            raise ValueError(f"queue_capacity must be >= 1, got "
                             f"{self.queue_capacity}")
        if int(self.degraded_after) < 1:
            raise ValueError(f"degraded_after must be >= 1, got "
                             f"{self.degraded_after}")
        if self.cb not in ("on", "off"):
            raise ValueError(f"cb must be 'on' or 'off', got "
                             f"{self.cb!r}")
        if int(self.cb_slots) < 1 or int(self.cb_block_len) < 1:
            raise ValueError("cb_slots and cb_block_len must be >= 1")
        if min(int(self.cb_blocks), int(self.cb_prompt_cap),
               int(self.cb_prefill_rung)) < 0:
            raise ValueError("cb_blocks, cb_prompt_cap and cb_prefill_rung "
                             "must be >= 0 (0 = auto)")
        if float(self.stall_fault_s) < 0:
            raise ValueError(f"stall_fault_s must be >= 0, got "
                             f"{self.stall_fault_s}")
        be, ba = (float(self.brownout_be_frac),
                  float(self.brownout_batch_frac))
        if not (0 < be <= ba <= 1):
            raise ValueError(
                f"brownout fractions must satisfy 0 < be_frac <= "
                f"batch_frac <= 1, got be={be} batch={ba}")
        fam = str(self.family).strip().lower()
        if not fam:
            raise ValueError("family must be a non-empty name")
        object.__setattr__(self, "family", fam)
        if int(self.flush_tokens) < 1:
            raise ValueError(f"flush_tokens must be >= 1, got "
                             f"{self.flush_tokens}")
        if float(self.flush_ms) < 0:
            raise ValueError(f"flush_ms must be >= 0, got "
                             f"{self.flush_ms}")

    @property
    def max_prompt_len(self) -> int:
        return max(p for _, p in self.buckets)

    # -- continuous-batching geometry (all derived, all static) -------------
    @property
    def cb_on(self) -> bool:
        return self.cb == "on"

    @property
    def cb_prefill_len(self) -> int:
        """The WIDEST compiled prefill P, rounded UP to a block multiple
        (prefill scatters whole blocks): `cb_prefill_rung` where that is
        set and under the cap, else the prompt cap `cb_max_prompt_len`
        itself, and then the two are one number.  A prompt no longer
        than P is prefilled whole, a longer one (up to the cap) in
        chunks of P (`cb_chunks`)."""
        cap = self.cb_max_prompt_len
        if int(self.cb_prefill_rung):
            cap = min(cap, int(self.cb_prefill_rung))
        bl = int(self.cb_block_len)
        return -(-cap // bl) * bl

    @property
    def cb_chunked(self) -> bool:
        """Whether the cap lies past the widest compiled prefill."""
        return self.cb_max_prompt_len > self.cb_prefill_len

    def cb_chunks(self, plen: int) -> Tuple[Tuple[int, int, int], ...]:
        """(start, real rows, width) of the chunks a prompt of `plen`
        tokens is prefilled in where the engine chunks: whole chunks of
        the widest rung, then the rest at the narrowest rung that holds
        it (a prompt within the widest rung: that one chunk)."""
        wide = self.cb_prefill_len
        whole, rest = divmod(int(plen), wide)
        out = [(i * wide, wide, wide) for i in range(whole)]
        if rest:
            out.append((whole * wide, rest, self.cb_prefill_width(rest)))
        return tuple(out)

    @property
    def cb_prefill_widths(self) -> Tuple[int, ...]:
        """The ladder of compiled prefill widths: the floor doubled
        while it is under the cap, then the cap `cb_prefill_len`
        itself; every rung a block multiple.  A cap at or under the
        floor is the only rung."""
        cap, bl = self.cb_prefill_len, int(self.cb_block_len)
        rungs = []
        width = -(-CB_PREFILL_FLOOR // bl) * bl
        while width < cap:
            rungs.append(width)
            width *= 2
        return tuple(rungs) + (cap,)

    def cb_prefill_width(self, plen: int) -> int:
        """The rung a prompt of `plen` tokens is prefilled at: the
        narrowest that holds it."""
        for width in self.cb_prefill_widths:
            if width >= plen:
                return width
        raise ValueError(
            f"prompt length {plen} exceeds the widest prefill program "
            f"({self.cb_prefill_len} rows); the prompt cap is "
            f"{self.cb_max_prompt_len}, and a prompt between the two goes "
            f"in chunks (cb_chunks)")

    @property
    def cb_max_prompt_len(self) -> int:
        """The prompt CAP: the longest prompt `submit` admits under cb
        (fail-fast bound).  It may lie past the widest compiled prefill
        `cb_prefill_len`; the tables and the pool are sized by it."""
        return int(self.cb_prompt_cap) or self.max_prompt_len

    @property
    def cb_blocks_per_slot(self) -> int:
        """Table width T: worst-case blocks one slot can ever hold
        (full prefill + a full generation).  A prompt in chunks writes
        whole chunks, so the cap counts in whole widest rungs."""
        bl, wide = int(self.cb_block_len), self.cb_prefill_len
        span = -(-self.cb_max_prompt_len // wide) * wide
        return -(-(span + int(self.max_new_tokens)) // bl)

    @property
    def cb_pool_blocks(self) -> int:
        """Pool size incl. the null block.  Auto (cb_blocks=0) sizes
        for every slot at worst case — exhaustion then needs an
        explicit smaller cb_blocks (the shed tests use one)."""
        n = int(self.cb_blocks)
        if n == 0:
            n = int(self.cb_slots) * self.cb_blocks_per_slot + 1
        return n

    @property
    def max_batch(self) -> int:
        return max(b for b, _ in self.buckets)

    def bucket_for(self, n: int, prompt_len: int) -> Tuple[int, int]:
        """Smallest admissible bucket for `n` requests whose longest
        prompt is `prompt_len`.  When no bucket holds all `n`, the
        widest admissible one is returned (the caller dispatches a full
        batch and re-queues the overflow)."""
        cands = [c for c in self.buckets if c[1] >= prompt_len]
        if not cands:
            raise ValueError(
                f"prompt_len={prompt_len} exceeds every bucket "
                f"{self.buckets}; admission should have rejected it")
        fit = [c for c in cands if c[0] >= n]
        if fit:
            return min(fit, key=lambda c: (c[0], c[1]))
        return min(cands, key=lambda c: (-c[0], c[1]))

    @classmethod
    def parse(cls, spec: str) -> "ServeSpec":
        """CLI grammar (HealthSpec mold): comma/semicolon-separated
        `key=value`.  Buckets are `/`-separated BxP entries, e.g.
        `"buckets=1x8/4x16,max_new_tokens=8,eos_id=2"`.  `eos_id=none`
        clears the eos."""
        kw: Dict[str, Any] = {}
        types = {f.name: f.type for f in dataclasses.fields(cls)}
        for part in spec.replace(";", ",").split(","):
            part = part.strip()
            if not part:
                continue
            try:
                key, _, val = part.partition("=")
                key, val = key.strip(), val.strip()
                if key not in types:
                    raise ValueError(f"unknown key {key!r}")
                if key == "buckets":
                    kw[key] = tuple(
                        tuple(int(x) for x in item.lower().split("x"))
                        for item in val.split("/") if item)
                elif key == "eos_id":
                    kw[key] = None if val.lower() in ("none", "") \
                        else int(val)
                elif "str" in str(types[key]):
                    kw[key] = val.lower()
                elif "float" in str(types[key]):
                    kw[key] = float(val)
                else:
                    kw[key] = int(val)
            except ValueError as e:
                raise ValueError(f"bad serve spec entry {part!r} "
                                 f"(want key=value): {e}") from e
        return cls(**kw)


def _left_pad_mask(prompt_len: int, max_len: int,
                   plens: jnp.ndarray) -> jnp.ndarray:
    """(B, max_len) bool: key position j of row i is attendable iff
    j >= prompt_len - plens[i].  Prompt tokens occupy the RIGHT end of
    the padded prompt region; every generated position (>= prompt_len)
    is attendable for all rows."""
    kpos = jnp.arange(max_len)[None, :]
    return kpos >= (prompt_len - plens)[:, None]


class FirstToken(NamedTuple):
    """What the prefill of a model that drafts hands the scheduler: the
    first token and the main model's log-probability of it, the first
    draft and the module's of that."""
    token: int
    logprob: float
    draft: int
    draft_logprob: float


class StepTokens(NamedTuple):
    """A fetched verify-and-draft step, (S,) or (S, 2) host arrays: the
    one or two tokens each slot yielded (`count` of them) with the main
    model's log-probabilities, the rows each slot holds after the step,
    and the draft made for its next step with the module's
    log-probability of it."""
    tokens: np.ndarray
    count: np.ndarray
    logprobs: np.ndarray
    ntoks: np.ndarray
    draft: np.ndarray
    draft_logprob: np.ndarray


# rows of a verify-and-draft step's (STEP_ROWS * S + tail,) int32 result,
# S wide each: the token to step from next and the rows held then (what
# the NEXT step takes as they lie on the device), the two tokens, how
# many of them count, the draft; and as float32 bits the two tokens'
# log-probabilities and the draft's
STEP_ROWS = 9


#: the name the module's part of the two cb programs is traced under
MTP_SCOPE = "mtp_module"


def _bits(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def _tree_spec(tree):
    return jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(jnp.asarray(a).dtype)), tree)


class InferenceEngine:
    """Loads params from the latest healthy checkpoint, compiles one
    executable per (mode, batch, prompt_len) bucket, runs padded
    micro-batches, and hot-reloads checkpoints without dropping
    in-flight work.  See the module docstring for the swap/degrade
    contract.  Thread-safe: `_compile` is serialized; `run_batch`
    callers pass the params they captured."""

    def __init__(self, net, spec: ServeSpec,
                 workspace: Optional[str] = None,
                 params: Optional[Dict[str, Any]] = None,
                 stats: Optional[ServeStats] = None, log_fn=print,
                 pinned: bool = False):
        if workspace is None and params is None:
            raise ValueError("InferenceEngine needs a checkpoint "
                             "workspace or explicit params")
        self.net = net
        self.spec = spec
        # what the cb programs are shaped by, read off the layers'
        # declared serving state: a state or a ring per slot (the
        # prefill program takes the slot's index), routing counts (the
        # decode program returns them behind its tokens)
        self._per_slot_state = slot_behind_row(net)
        routed = {name: entry["routed"].shape[0]
                  for name, entry in jax.eval_shape(
                      lambda: init_pools(net, 2, 1, jnp.float32, 1)).items()
                  if "routed" in entry}
        self._routed_layers = tuple(routed)
        self._routed_widths = routed
        # how many routing counts ride behind a decode step's tokens:
        # assignments, experts touched and, where a layer counts it,
        # the busiest expert's assignments
        self._cb_tail = max(routed.values(), default=0)
        # a model with a multi-token prediction module drafts with it:
        # the prefill hands back a first draft, the decode program is
        # the verify-and-draft step (two rows a slot, one or two tokens)
        self._mtp = mtp_module(net)
        # the kinds of layers that keep this model to prompts of ONE
        # chunk, whatever the spec's cap (`cb_prompt_limit`)
        self.cb_unchunked = unchunked_layers(net)
        # (experts held, experts a token chooses) of every routed layer:
        # what says which runs it takes grouped (`grouped_row_slots`)
        self._routed_sizes = tuple(
            (layer.n_held, layer.k) for layer in
            (net.layers[name] for name in self._routed_layers))
        self.stats = stats if stats is not None else ServeStats()
        self.log = log_fn
        self.ckpt = (CheckpointManager(workspace, log_fn=log_fn)
                     if workspace is not None else None)
        self._params = (jax.device_put(params)
                        if params is not None else None)
        # the fresh-init fallback, kept forever: `reload_to(step=-1)`
        # restores it, so a fleet rollback works even when the pinned
        # step is -1 (cold start — nothing was ever promoted, yet a
        # canaried-then-rejected first checkpoint must still be
        # unseated from the canary)
        self._init_params = self._params
        self.params_step: int = -1
        self._fingerprint: Optional[tuple] = None
        # pinned-fingerprint mode (fleet members): the engine never
        # follows the workspace on its own — poll_reload is a no-op
        # and only an explicit reload_to (the rollout controller's
        # command channel) moves the served params
        self.pinned = bool(pinned)
        # honest /healthz: a refused/failed reload leaves the engine
        # serving STALE params; recorded here (and cleared by the next
        # successful reload) so the router sees a degraded verdict
        # instead of an unconditional ok
        self._stale_reason: Optional[str] = None
        # the params served immediately before the last EXPLICIT
        # reload (the fleet rollout's command channel).  The pinned
        # snapshot on disk can be GC'd (max_to_keep) while the fleet
        # still serves it, so a canary rollback to the pinned step
        # must be satisfiable from memory — one extra params copy per
        # fleet engine is the price of an instant, disk-independent
        # rollback.  Solo (polling) engines never populate it.
        self._prev_params = None
        self._prev_step: Optional[int] = None
        self._compiled: Dict[Tuple[str, int, int], Any] = {}
        # rungs of the cb prefill ladder whose program holds the flash
        # forward kernel (read off the lowered program, `_compile_cb`)
        self.cb_flash_widths: set = set()
        self._compile_lock = threading.Lock()
        # CompileWatch scope: per-engine, so a fleet member (or a
        # fresh autoscaled engine) warming up after its siblings never
        # reads as a recompile anomaly — only a compile AFTER this
        # engine's own warmup() trips the invariant
        self._perf_scope = f"engine-{id(self):x}"
        self._key_counter = 0
        self._key_lock = threading.Lock()
        # injected straggler latency (engine.stall / set_stall): a
        # host-side sleep before every compiled call.  The engine stays
        # healthy — probes pass, requests complete — it is just SLOW,
        # which is exactly the failure mode hedging exists for.
        self.stall_s = 0.0
        # when the loop thread's last wait on the device began and
        # ended (`np.asarray` of a step's tokens, `int` of a prefill's
        # first): the scheduler's stall account reads it after the
        # call (serve/scheduler.py, `_lap_wait`)
        self.cb_wait = (0.0, 0.0)
        # when a decode step's tokens were last read, and when the
        # steps still in flight were handed over: a step's period
        self._cb_read_at = 0.0
        self._cb_flying_at: deque = deque(maxlen=2)
        # reload-poll supervision (server._poll_loop): consecutive
        # unexpected poll deaths — /healthz degrades once the streak
        # crosses degraded_after, because an engine whose poller
        # cannot stay alive is quietly going stale
        self._poll_death_streak = 0

    @property
    def drafts(self) -> bool:
        """Whether a decode step verifies a draft and makes the next (the
        net has an MTP module): a slot then yields one or two tokens a
        step, with their log-probabilities (docs/SERVING.md)."""
        return self._mtp is not None

    @property
    def chunks_prompts(self) -> bool:
        """Whether a prompt past the widest compiled prefill is served,
        in chunks: the spec's cap lies past it and every layer of the
        model can carry a chunk on (docs/SERVING.md).  Such an engine
        has ONE ladder, the chunk programs `cb_chunk_<width>`, and every
        prompt takes it: one within the widest rung is a single last
        chunk at start 0.  An engine that does not chunk keeps the
        whole-prompt programs `cb_prefill_<width>` and no others."""
        return self.spec.cb_chunked and not self.cb_unchunked

    @property
    def cb_prompt_limit(self) -> int:
        """The longest prompt this engine serves: the spec's cap, or the
        widest compiled prefill where the model cannot go in chunks."""
        spec = self.spec
        return (spec.cb_max_prompt_len if self.chunks_prompts
                else min(spec.cb_max_prompt_len, spec.cb_prefill_len))

    def grouped_row_slots(self, width: int, rows: int) -> int:
        """Expert products the dense walk would cost `rows` real rows of
        a run `width` wide, over the routed layers that take such a run
        in the grouped form (0 where none does)."""
        return rows * sum(held for held, k in self._routed_sizes
                          if grouped_run(width, held, k))

    def note_poll_death(self) -> int:
        self._poll_death_streak += 1
        return self._poll_death_streak

    def note_poll_ok(self) -> None:
        self._poll_death_streak = 0

    # -- params lifecycle ---------------------------------------------------
    @property
    def params(self):
        """The live params tree.  Read ONCE per micro-batch and pass to
        `run_batch` — that single read is what makes the hot-reload
        swap atomic with respect to in-flight work."""
        return self._params

    def _swap(self, params, step: int) -> None:
        new = jax.device_put(params)
        if self._params is not None and \
                _tree_spec(new) != _tree_spec(self._params):
            raise RuntimeError(
                f"checkpoint step {step} has a different parameter "
                f"geometry than the serving model; refusing the swap")
        self._params = new            # atomic: one attribute store
        self.params_step = step
        perf.set_memory_tree("serve_params", new,
                             scope=self._perf_scope)

    def load(self) -> int:
        """Initial load: latest healthy checkpoint (walks back past
        unhealthy/corrupt snapshots).  Falls back to constructor params
        when the workspace has nothing restorable.  Returns the served
        step (-1 = constructor params)."""
        if self.ckpt is not None:
            restored = self.ckpt.restore(skip_unhealthy=True)
            self._fingerprint = self.ckpt.fingerprint()
            if restored is not None:
                p, _, step = restored
                self._swap(p, step)
            elif self._params is None:
                raise RuntimeError(
                    f"no restorable healthy checkpoint under "
                    f"{self.ckpt.dir} and no fallback params")
        if self._params is not None:   # constructor-params path never
            perf.set_memory_tree(      # went through _swap
                "serve_params", self._params, scope=self._perf_scope)
        return self.params_step

    def poll_reload(self) -> str:
        """One hot-reload attempt; returns "reloaded" | "unchanged" |
        "refused" | "failed".  Never raises and never unseats the live
        params on failure — the degrade contract the server's poll
        thread relies on (the process stays up, old params keep
        serving)."""
        if self.ckpt is None:
            return "unchanged"
        if self.pinned:
            # fleet member: the rollout controller owns reloads
            return "pinned"
        with obs.span("engine.reload") as sp:
            outcome = self._poll_reload()
            sp.set(outcome=outcome, step=self.params_step)
        if outcome != "unchanged":
            obs.emit_event("serve.reload", outcome=outcome,
                           step=self.params_step)
        return outcome

    def _poll_reload(self) -> str:
        try:
            faults.maybe_fault("serve.reload")
            torn_before = self.ckpt.torn_polls
            fp = self.ckpt.fingerprint()
            if self.ckpt.torn_polls > torn_before:
                # the poll raced a live writer (mid-rename / partial
                # MANIFEST.json): a counted non-event, NOT a failure —
                # fingerprint returned the previous token, so the next
                # tick simply retries once the write completes.  Never
                # reload off a torn read.
                self.stats.count("torn_polls")
                return "unchanged"
            if fp == self._fingerprint:
                return "unchanged"
            restored = self.ckpt.restore(skip_unhealthy=True)
            if restored is None or restored[2] == self.params_step:
                # nothing newer that is healthy (the walk-back landed on
                # what we already serve, or on nothing).  Record the
                # fingerprint so the refusal is not re-litigated every
                # poll tick; a future save changes it again.
                self._fingerprint = fp
                self.stats.count("reloads_refused")
                self._stale_reason = (
                    f"reload refused: newer checkpoint on disk is not "
                    f"healthy/restorable; serving stale step "
                    f"{self.params_step}")
                self.log("serve: reload refused — no newer healthy "
                         f"checkpoint (serving step {self.params_step})")
                return "refused"
            p, _, step = restored
            self._swap(p, step)
            self._fingerprint = fp
            self._stale_reason = None
            self.stats.count("reloads")
            self.log(f"serve: hot-reloaded checkpoint step {step}")
            return "reloaded"
        except Exception as e:  # noqa: BLE001 — degrade, never crash
            # fingerprint deliberately NOT updated: the next poll
            # retries the same reload instead of wedging on old params
            self.stats.count("reload_failures")
            self._stale_reason = (
                f"reload failed ({type(e).__name__}); serving stale "
                f"step {self.params_step}")
            self.log(f"warning: serve reload failed "
                     f"({type(e).__name__}: {e}); keeping params from "
                     f"step {self.params_step}")
            return "failed"

    def reload_to(self, step: Optional[int] = None,
                  skip_unhealthy: bool = False) -> str:
        """Explicit reload — the fleet rollout controller's command
        channel (works on a pinned engine; that is its point).  Loads
        checkpoint `step` (None = latest on disk), by default WITHOUT
        the healthy-verdict walk-back: a canary deliberately serves
        the exact target snapshot and the rollout verdict — not the
        manifest alone — decides its fate.  `restore` still walks back
        past a torn/corrupt target, so the caller must verify
        `params_step` landed where it asked.  Returns "reloaded" |
        "unchanged" | "refused" | "failed"; never raises and never
        unseats the live params on failure."""
        if self.ckpt is None:
            return "refused"
        with obs.span("engine.reload", target=step) as sp:
            outcome = self._reload_to(step, skip_unhealthy)
            sp.set(outcome=outcome, step=self.params_step)
        if outcome != "unchanged":
            obs.emit_event("serve.reload", outcome=outcome,
                           step=self.params_step, target=step)
        return outcome

    def _reload_to(self, step: Optional[int],
                   skip_unhealthy: bool) -> str:
        try:
            faults.maybe_fault("serve.reload")
            if step is not None and int(step) < 0:
                # rollback target "-1": the fresh-init fallback params
                # (cold-start fleets pin there before any promotion)
                if self._init_params is None:
                    self.stats.count("reloads_refused")
                    self.log("serve: reload to step -1 refused — no "
                             "fresh-init fallback params")
                    return "refused"
                if self.params_step < 0:
                    self._stale_reason = None
                    return "unchanged"
                self._prev_params = self._params
                self._prev_step = self.params_step
                self._params = self._init_params
                self.params_step = -1
                self._stale_reason = None
                self.stats.count("reloads")
                self.log("serve: reloaded to fresh-init params "
                         "(step -1)")
                return "reloaded"
            if step is not None and int(step) == self.params_step:
                # already serving the requested step — e.g. restoring
                # a refused canary to a pinned step the checkpoint GC
                # has since deleted.  The params are live in memory, so
                # touching disk could only fail; by definition the
                # engine is not stale either.
                self._stale_reason = None
                return "unchanged"
            fp = self.ckpt.fingerprint()
            restored = self.ckpt.restore(step=step,
                                         skip_unhealthy=skip_unhealthy)
            if restored is None:
                if (step is not None and self._prev_params is not None
                        and int(step) == self._prev_step):
                    # the requested snapshot was GC'd off disk
                    # (max_to_keep) but it is what this engine served
                    # immediately before the current params — a canary
                    # being restored to the pinned step.  Swap back
                    # from memory; disk owes us nothing.
                    prev_p, prev_s = self._prev_params, self._prev_step
                    self._prev_params = self._params
                    self._prev_step = self.params_step
                    self._params = prev_p
                    self.params_step = prev_s
                    self._fingerprint = fp
                    self._stale_reason = None
                    self.stats.count("reloads")
                    self.log(f"serve: reloaded to step {step} from "
                             f"in-memory previous params (snapshot no "
                             f"longer on disk)")
                    return "reloaded"
                self.stats.count("reloads_refused")
                self._stale_reason = (
                    f"explicit reload to step {step} found nothing "
                    f"restorable; serving stale step {self.params_step}")
                self.log(f"serve: explicit reload to step {step} "
                         f"refused — nothing restorable")
                return "refused"
            p, _, got = restored
            if got == self.params_step:
                # already serving it (e.g. a rollback to the pinned
                # step that never left it) — success, not a refusal
                self._fingerprint = fp
                self._stale_reason = None
                return "unchanged"
            self._prev_params = self._params
            self._prev_step = self.params_step
            self._swap(p, got)
            self._fingerprint = fp
            self._stale_reason = None
            self.stats.count("reloads")
            self.log(f"serve: reloaded to checkpoint step {got}"
                     + (f" (asked for {step})"
                        if step is not None and got != step else ""))
            return "reloaded"
        except Exception as e:  # noqa: BLE001 — degrade, never crash
            self.stats.count("reload_failures")
            self._stale_reason = (
                f"reload to step {step} failed ({type(e).__name__}); "
                f"serving stale step {self.params_step}")
            self.log(f"warning: explicit reload to step {step} failed "
                     f"({type(e).__name__}: {e}); keeping params from "
                     f"step {self.params_step}")
            return "failed"

    # -- health -------------------------------------------------------------
    def health(self) -> Dict[str, Any]:
        """Honest liveness verdict for /healthz and the fleet router.
        Degrades (ok=False) when the engine is *wedged* — `spec.
        degraded_after` consecutive failed batches — or *stale* — a
        refused/failed reload left it serving params older than what
        the workspace holds.  A healthy report is earned, not
        unconditional."""
        reasons = []
        k = int(self.spec.degraded_after)
        streak = self.stats.consecutive_batch_failures
        if streak >= k:
            reasons.append(f"{streak} consecutive failed batches "
                           f"(threshold {k})")
        if self._stale_reason is not None:
            reasons.append(self._stale_reason)
        if self._poll_death_streak >= k:
            reasons.append(
                f"reload poll died {self._poll_death_streak} times "
                f"in a row (threshold {k}); params may be going "
                f"stale")
        return {"ok": not reasons,
                "status": "ok" if not reasons else "degraded",
                "step": self.params_step,
                "family": self.spec.family,
                "pinned": self.pinned,
                "reasons": reasons}

    # -- compiled programs --------------------------------------------------
    def _build_generate(self, batch: int, prompt_len: int):
        net, spec = self.net, self.spec
        max_new = int(spec.max_new_tokens)
        max_len = prompt_len + max_new
        temperature, top_k, top_p = (float(spec.temperature),
                                     int(spec.top_k), float(spec.top_p))
        eos_id = spec.eos_id

        def fn(params, tokens, plens, key):
            dtype = jax.tree_util.tree_leaves(params)[0].dtype
            cache = init_cache(net, batch, max_len, dtype)
            kmask = _left_pad_mask(prompt_len, max_len, plens)
            logits, cache = forward_cached(net, params, tokens, cache,
                                           0, kmask=kmask)
            keys = jax.random.split(key, max_new)
            tok0 = _sample(logits[:, -1], keys[0], temperature, top_k,
                           top_p)
            done0 = (jnp.zeros((batch,), jnp.bool_) if eos_id is None
                     else tok0 == eos_id)

            def step(carry, k):
                tok, cache, pos, done = carry
                lg, cache = forward_cached(net, params, tok[:, None],
                                           cache, pos, kmask=kmask)
                nxt = _sample(lg[:, -1], k, temperature, top_k, top_p)
                if eos_id is not None:
                    nxt = jnp.where(done, eos_id, nxt)
                    done = done | (nxt == eos_id)
                return (nxt, cache, pos + 1, done), nxt

            (_, _, _, _), rest = jax.lax.scan(
                step, (tok0, cache, jnp.int32(prompt_len), done0),
                keys[1:])
            return jnp.concatenate([tok0[:, None], rest.T], axis=1)

        return fn

    def _build_predict(self, batch: int, prompt_len: int):
        net, spec = self.net, self.spec
        max_len = prompt_len + 1

        def fn(params, tokens, plens):
            dtype = jax.tree_util.tree_leaves(params)[0].dtype
            cache = init_cache(net, batch, max_len, dtype)
            kmask = _left_pad_mask(prompt_len, max_len, plens)
            logits, _ = forward_cached(net, params, tokens, cache, 0,
                                       kmask=kmask)
            # left-padding puts every row's last real token at P-1, so
            # one static slice reads the next-token distribution
            return jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), axis=-1)

        return fn

    # -- continuous-batching programs ---------------------------------------
    def _build_cb_prefill(self, p_len: Optional[int] = None):
        """The prefill at fixed (1, P), P one rung of
        `spec.cb_prefill_widths` (the cap when not given): the prompt is
        RIGHT-padded to P (the causal mask alone keeps pad keys out of
        every real query's horizon; pad K/V garbage lands in reserved
        or null blocks and is masked/overwritten downstream), runs
        through the ordinary contiguous `forward_cached`, samples the
        first token from the last REAL position, and scatters the
        contiguous cache into the slot's pool blocks."""
        net, spec = self.net, self.spec
        if p_len is None:
            p_len = spec.cb_prefill_len
        temperature, top_k, top_p = (float(spec.temperature),
                                     int(spec.top_k), float(spec.top_p))

        def prefill(params, pools, tokens, plen, row, slot, key):
            dtype = jax.tree_util.tree_leaves(params)[0].dtype
            cache = init_cache(net, 1, p_len, dtype)
            # a recurrence does not forgive padding: the layers are told
            # how many rows are real (attention needs no telling)
            logits, cache = forward_cached(net, params, tokens, cache, 0,
                                           plen=plen)
            last = jax.lax.dynamic_index_in_dim(logits[0], plen - 1,
                                                axis=0, keepdims=True)
            tok0 = _sample(last, key, temperature, top_k, top_p)[0]
            return tok0, scatter_prefill(pools, cache, row, slot, net)

        mtp = self._mtp

        def prefill_and_draft(params, pools, tokens, plen, row, slot, key):
            """Both caches filled: the main stack's as `prefill` fills
            it, the module's from the main stack's output beside the
            tokens shifted by one, the first token behind the prompt's
            last.  Returns ([first token, first draft, the bits of their
            two log-probabilities], pools)."""
            dtype = jax.tree_util.tree_leaves(params)[0].dtype
            cache = init_cache(net, 1, p_len, dtype)
            logits, cache, hidden = forward_cached(
                net, params, tokens, cache, 0, plen=plen, with_hidden=True)
            at_last = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
                a[0], plen - 1, axis=0, keepdims=True)
            k_tok, k_draft = jax.random.split(key)
            tok0, lp0, _ = sample_logprob(at_last(logits), k_tok,
                                          temperature, top_k, top_p)
            nxt = jax.lax.dynamic_update_slice(
                jnp.roll(tokens, -1, axis=1), tok0[None], (0, plen - 1))
            with jax.named_scope(MTP_SCOPE):
                logits, cache = draft_cached(net, params, hidden, nxt, cache,
                                             0, plen=plen)
            draft, lq, q = sample_logprob(at_last(logits), k_draft,
                                          temperature, top_k, top_p)
            pools = scatter_prefill(pools, cache, row, slot, net)
            state = dict(pools[mtp.entry])
            state["draft"] = state["draft"].at[slot].set(draft[0])
            if q is not None:
                state["q"] = state["q"].at[slot].set(q[0])
            pools[mtp.entry] = state
            return jnp.concatenate([tok0, draft, _bits(lp0), _bits(lq)]), pools

        if mtp is not None:
            prefill = prefill_and_draft

        # the function's name is the program's in a device trace
        # (`jit_cb_prefill`, every rung).  A state per slot goes to the
        # slot's own place: there `row` carries the slot's index behind
        # the table row (`PagedKVCache.prefill_target`)
        if self._per_slot_state:
            def cb_prefill(params, pools, tokens, plen, row, key):
                return prefill(params, pools, tokens, plen, row[:-1],
                               row[-1], key)
        else:
            def cb_prefill(params, pools, tokens, plen, row, key):
                return prefill(params, pools, tokens, plen, row, None, key)

        return cb_prefill

    def _build_cb_chunk(self, p_len: int):
        """A chunk of a prompt (all of one that a rung holds), at fixed
        (1, P), P one rung of the ladder: `forward_chunk` straight
        against the pools (the slot's own state and tails, the rows
        already in its blocks), `meta` = [real rows, start, last].  The
        head runs on the last real row only, and only in the prompt's
        last chunk; any other chunk's token is 0.  Returns ([token, the
        chunk's routing counts, where it went grouped its tiles' rows],
        pools)."""
        net, spec = self.net, self.spec
        piece = spec.cb_prefill_len
        temperature, top_k, top_p = (float(spec.temperature),
                                     int(spec.top_k), float(spec.top_p))

        def cb_chunk(params, pools, tokens, meta, row, key):
            rows, start, last = meta[0], meta[1], meta[2]
            hidden, pools = forward_chunk(net, params, tokens, pools,
                                          row[:-1], row[-1], start, rows,
                                          piece)

            def first_token():
                at = jax.lax.dynamic_slice_in_dim(hidden, rows - 1, 1, axis=1)
                return _sample(project_head(net, params, at)[0], key,
                               temperature, top_k, top_p)[0].astype(jnp.int32)

            out = [jax.lax.cond(last > 0, first_token,
                                lambda: jnp.int32(0))[None]]
            if self._routed_layers:
                counts, pools = self._chunk_counts(pools)
                out.append(counts)
            return jnp.concatenate(out), pools

        return cb_chunk

    def _chunk_counts(self, pools):
        """(A chunk's routing counts summed over the routed layers, the
        pools with every layer's counts as wide as a decode step leaves
        them).  A layer whose chunk went grouped left the rows of the
        tiles its products visited behind its counts: their sum rides
        LAST, in the program of a width some layer takes grouped and in
        no other."""
        pools, tiles = dict(pools), []
        for name in self._routed_layers:
            counts, width = pools[name]["routed"], self._routed_widths[name]
            if counts.shape[0] > width:
                tiles.append(counts[width])
                pools[name] = {**pools[name], "routed": counts[:width]}
        counts = self._routed_counts(pools)
        if tiles:
            counts = jnp.concatenate([counts, sum(tiles)[None]])
        return counts, pools

    def _build_cb_decode(self):
        """ONE compiled decode step at fixed slot count S: every
        active slot advances one token against its paged blocks
        (forward_paged), one `_sample` call produces all S next
        tokens.  Join/retire is pure host bookkeeping in the
        scheduler — the program never changes shape."""
        net, spec = self.net, self.spec
        temperature, top_k, top_p = (float(spec.temperature),
                                     int(spec.top_k), float(spec.top_p))

        def cb_decode(params, pools, tokens, ntoks, tables, key):
            logits, pools = forward_paged(net, params, tokens[None],
                                          pools, tables, ntoks)
            nxt = _sample(logits[0], key, temperature, top_k, top_p)
            return nxt, pools

        if self._mtp is not None:
            return self._build_cb_verify()
        if not self._routed_layers:
            return cb_decode
        step = cb_decode

        # the step's routing counts ride behind its tokens: one array,
        # one fetch; and what comes out can go in again as it is (the
        # step dispatched before this one's tokens are read)
        def cb_decode(params, pools, tokens, ntoks, tables, key):  # noqa: F811
            nxt, pools = step(params, pools, tokens[:spec.cb_slots], ntoks,
                              tables, key)
            return jnp.concatenate([nxt, self._routed_counts(pools)]), pools

        return cb_decode

    def _routed_counts(self, pools):
        """The step's routing counts, summed over the routed layers."""
        tail = self._cb_tail
        return sum(c if c.shape[0] == tail else jnp.pad(
            c, (0, tail - c.shape[0]))
            for c in (pools[name]["routed"] for name in self._routed_layers))

    def _build_cb_verify(self):
        """The decode step of a model that drafts: ONE compiled program
        at fixed slot count S, two rows a slot.  Slot s holds its last
        token t_n (not yet cached, n = ntoks[s]) and, on the device,
        the draft d for t_{n+1} with the distribution q it was drawn
        from.  The main stack runs [t_n @ n, d @ n + 1];
        `verify_draft` accepts d or draws t_{n+1} anew and, behind an
        accepted draft, draws t_{n+2} from the second row; the module
        then runs rows n and n + 1 (beside t_{n+1} and t_{n+2}) and
        drafts from the last row that counts.  A slot advances by 1 or
        2; a rejected draft's rows (the main stack's and the module's at
        n + 1) stay in the pool and are overwritten by the next step,
        which starts there.

        `carry` is a step's result as it lies on the device, or the
        host's (last tokens, rows held) in its first two rows: how far
        a slot advanced need not come to the host before the next step
        goes out."""
        net, spec, mtp = self.net, self.spec, self._mtp
        temperature, top_k, top_p = (float(spec.temperature),
                                     int(spec.top_k), float(spec.top_p))
        s = int(spec.cb_slots)

        def cb_decode(params, pools, carry, tables, key):
            last, ntoks = carry[:s], carry[s:2 * s]
            state = pools[mtp.entry]
            k_verify, k_draft = jax.random.split(key)
            both = lambda a, b: jnp.stack([a, b], 1).reshape(1, 2 * s)  # noqa: E731
            logits, pools, hidden = forward_paged(
                net, params, both(last, state["draft"]), pools, tables,
                ntoks, with_hidden=True)
            first, bonus, accepted, lp1, lp2 = verify_draft(
                logits[0].reshape(s, 2, -1), state["draft"], state["q"],
                k_verify, temperature, top_k, top_p)
            with jax.named_scope(MTP_SCOPE):
                logits, pools = draft_paged(
                    net, params, hidden, both(first, bonus), pools, tables,
                    ntoks)
            logits = logits[0].reshape(s, 2, -1)
            draft, lq, q = sample_logprob(
                jnp.where(accepted[:, None], logits[:, 1], logits[:, 0]),
                k_draft, temperature, top_k, top_p)
            pools[mtp.entry] = {"draft": draft,
                                "q": state["q"] if q is None else q}
            count = jnp.where(ntoks > 0, 1 + accepted.astype(jnp.int32), 0)
            rows = [jnp.where(accepted, bonus, first), ntoks + count, first,
                    bonus, count, draft, _bits(lp1), _bits(lp2), _bits(lq)]
            assert len(rows) == STEP_ROWS
            if self._routed_layers:
                rows.append(self._routed_counts(pools))
            return jnp.concatenate(rows), pools

        return cb_decode

    @property
    def serve_dtype(self):
        """The dtype the compiled programs compute and cache in: the
        params' own (every program reads it off the params tree)."""
        return jax.tree_util.tree_leaves(self._params)[0].dtype

    def _pools_spec(self):
        return jax.eval_shape(lambda: init_pools(
            self.net, self.spec.cb_pool_blocks, self.spec.cb_block_len,
            self.serve_dtype, self.spec.cb_slots))

    @property
    def _cb_width(self) -> int:
        """Length of the decode program's token argument and result: the
        slots' tokens (a verify step's `STEP_ROWS` rows of them) and the
        routing counts behind."""
        rows = STEP_ROWS if self.drafts else 1
        return rows * int(self.spec.cb_slots) + self._cb_tail

    def _cb_prefill_name(self, p_len: int) -> str:
        """What the compile and cost accounts (`obs.perf`) call the
        prefill program of width `p_len`: the FLOPs and the step time
        that make an MFU have to be one executable's, so each rung
        under the cap has a name of its own."""
        if p_len == self.spec.cb_prefill_len:
            return "cb_prefill"
        if p_len not in self.spec.cb_prefill_widths:
            raise ValueError(
                f"no cb prefill program is {p_len} rows wide (the "
                f"rungs are {self.spec.cb_prefill_widths})")
        return f"cb_prefill_{p_len}"

    def _compile_cb(self, which: str, p_len: Optional[int] = None):
        """AOT-compile the cb decode program or the prefill program of
        width `p_len` (the cap when not given; same lock, same
        `compiles` accounting as `_compile` — the counter still moves
        ONLY inside the two compile paths).  Pools are donated:
        the scheduler threads the returned pools into the next call,
        so the pool never exists twice on device."""
        spec = self.spec
        name = f"cb_{which}"
        if which == "prefill":
            if p_len is None:
                p_len = spec.cb_prefill_len
            name = self._cb_prefill_name(p_len)
        elif which == "chunk":
            self._cb_prefill_name(p_len)          # a rung, or it raises
            name = f"cb_chunk_{p_len}"
        key = (name, spec.cb_slots, spec.cb_blocks_per_slot)
        got = self._compiled.get(key)
        if got is not None:
            perf.lookup_hit(key[0])
            return got
        with self._compile_lock:
            got = self._compiled.get(key)
            if got is not None:
                perf.lookup_hit(key[0])
                return got
            if self._params is None:
                raise RuntimeError("engine has no params; call load()")
            geometry = (f"slots={spec.cb_slots},"
                        f"blocks={spec.cb_pool_blocks},"
                        f"block_len={spec.cb_block_len}")
            with obs.span("engine.compile", mode=name,
                          slots=spec.cb_slots,
                          blocks=spec.cb_pool_blocks), \
                 perf.compile_span(key[0], geometry=geometry,
                                   scope=self._perf_scope,
                                   family="generate"):
                p_spec = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    self._params)
                pools = self._pools_spec()
                rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
                if which == "prefill":
                    fn = self._build_cb_prefill(p_len)
                    tok = jax.ShapeDtypeStruct((1, p_len), jnp.int32)
                    plen = jax.ShapeDtypeStruct((), jnp.int32)
                    row = jax.ShapeDtypeStruct(
                        (p_len // spec.cb_block_len
                         + int(self._per_slot_state),), jnp.int32)
                    lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                        p_spec, pools, tok, plen, row, rng)
                    # what `attend_cache` took at this rung's shapes
                    if singa_flash_prefill.__name__ in lowered.as_text():
                        self.cb_flash_widths.add(p_len)
                    compiled = lowered.compile()
                elif which == "chunk":
                    fn = self._build_cb_chunk(p_len)
                    lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                        p_spec, pools,
                        jax.ShapeDtypeStruct((1, p_len), jnp.int32),
                        jax.ShapeDtypeStruct((3,), jnp.int32),
                        jax.ShapeDtypeStruct(
                            (spec.cb_blocks_per_slot + 1,), jnp.int32), rng)
                    if singa_flash_part.__name__ in lowered.as_text():
                        self.cb_flash_widths.add(p_len)
                    compiled = lowered.compile()
                elif which == "decode":
                    fn = self._build_cb_decode()
                    s = spec.cb_slots
                    tok = jax.ShapeDtypeStruct((self._cb_width,), jnp.int32)
                    small = [tok, jax.ShapeDtypeStruct(
                        (s, spec.cb_blocks_per_slot), jnp.int32)]
                    if not self.drafts:  # else the rows held ride in `tok`
                        small.insert(1, jax.ShapeDtypeStruct((s,),
                                                             jnp.int32))
                    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
                        p_spec, pools, *small, rng).compile()
                else:
                    raise ValueError(f"unknown cb program {which!r}")
            self.stats.count("compiles")
            self._compiled[key] = compiled
            perf.harvest(key[0], compiled)
            # analytic MemoryWatch component: the pool spec carries
            # the exact shapes init_pools allocates
            perf.set_memory_tree("kv_pool", pools,
                                 scope=self._perf_scope)
            return compiled

    def run_cb_prefill(self, params, pools, tokens: np.ndarray,
                       plen: int, row: np.ndarray):
        """One slot prefill: `tokens` (1, P) int32 RIGHT-padded to a
        rung P of `spec.cb_prefill_widths` (its width picks the
        program), `row` what `PagedKVCache.prefill_target` gives: the
        first P//block_len entries of the slot's block table and, where
        some layer keeps a state per slot, the slot's index behind them.
        Returns (first sampled token (int), new pools) — `pools` was
        donated; callers must use the returned tree."""
        flying, pools = self.dispatch_cb_prefill(params, pools, tokens,
                                                 plen, row)
        return self.fetch_cb_prefill(flying), pools

    def dispatch_cb_prefill(self, params, pools, tokens: np.ndarray,
                            plen: int, row: np.ndarray):
        """`run_cb_prefill` up to the hand-over to the device: returns
        (what `fetch_cb_prefill` takes, new pools) without waiting for
        the first token."""
        self._maybe_stall()
        width = int(tokens.shape[1])
        compiled = self._compile_cb("prefill", width)
        t0 = time.perf_counter()
        with obs.span("engine.cb_prefill", width=width):
            # host arrays as they are, for the call's own transfer
            # (`_cb_decode_args`)
            tok0, pools = compiled(params, pools,
                                   np.asarray(tokens, np.int32),
                                   np.int32(plen),
                                   np.asarray(row, np.int32),
                                   self._next_key())
            tok0.copy_to_host_async()
        return (tok0, t0, width), pools

    def dispatch_cb_chunk(self, params, pools, tokens: np.ndarray,
                          rows: int, start: int, last: bool,
                          row: np.ndarray):
        """One chunk of a prompt that is prefilled in several, handed to
        the device: `tokens` (1, P) int32 the chunk's, RIGHT-padded to a
        rung P, its first `rows` real, the first at position `start` of
        the prompt; `row` what `PagedKVCache.chunk_target` gives (the
        slot's whole table row, the slot's index behind it).  `last`:
        the prompt ends in this chunk, and its token is the request's
        first.  Returns (what `fetch_cb_chunk` takes, new pools) without
        waiting; `pools` was donated."""
        self._maybe_stall()
        width = int(tokens.shape[1])
        compiled = self._compile_cb("chunk", width)
        t0 = time.perf_counter()
        with obs.span("engine.cb_prefill", width=width, start=int(start),
                      rows=int(rows)):
            out, pools = compiled(
                params, pools, np.asarray(tokens, np.int32),
                np.array([rows, start, int(bool(last))], np.int32),
                np.asarray(row, np.int32), self._next_key())
            out.copy_to_host_async()
        return (out, t0, width, bool(last)), pools

    def fetch_cb_chunk(self, flying) -> Tuple[int, int, int]:
        """(the chunk's token, 0 but for a prompt's last chunk; the
        assignments of its real rows that fell on held experts, layers
        summed; the rows of the tiles its grouped products visited, 0
        where it took the dense walk) of a dispatched chunk (waits)."""
        out, t0, width, last = flying
        t = time.perf_counter()
        with obs.span("engine.cb_prefill_fetch"):
            got = np.asarray(out)
        now = time.perf_counter()
        self.cb_wait = (t, now)
        if last:       # the one chunk nothing else ran behind
            perf.observe_step(f"cb_chunk_{width}", now - t0)
            perf.mark_serving_ready()
        tiles = 1 + self._cb_tail
        return (int(got[0]), int(got[1]) if got.shape[0] > 1 else 0,
                int(got[tiles]) if got.shape[0] > tiles else 0)

    def run_cb_prefill_rungs(self, params, pools):
        """Every rung of the ladder run once, on pools no request
        holds yet: a program's first run on a device costs more than
        its later ones, and traffic reaches a wide rung only with its
        first long prompt.  One pad row at table entries of zeros
        writes the null block; a state per slot lands in slot 0, which
        its first admission overwrites whole.  Nothing is counted,
        and no key of the sampling stream is spent.  Returns the new
        pools (`pools` was donated)."""
        spec = self.spec
        for width in spec.cb_prefill_widths:
            if self.chunks_prompts:      # a last chunk of one row at 0
                _, pools = self._compile_cb("chunk", width)(
                    params, pools, jnp.zeros((1, width), jnp.int32),
                    jnp.array([1, 0, 1], jnp.int32),
                    jnp.zeros((spec.cb_blocks_per_slot + 1,), jnp.int32),
                    jnp.zeros((2,), jnp.uint32))
                continue
            row = jnp.zeros((width // spec.cb_block_len
                             + int(self._per_slot_state),), jnp.int32)
            _, pools = self._compile_cb("prefill", width)(
                params, pools, jnp.zeros((1, width), jnp.int32),
                jnp.int32(1), row, jnp.zeros((2,), jnp.uint32))
        return pools

    def fetch_cb_prefill(self, flying):
        """The first sampled token of a dispatched prefill (waits): an
        int, or where the model drafts a `FirstToken`."""
        tok0, t0, width = flying
        t = time.perf_counter()
        with obs.span("engine.cb_prefill_fetch"):
            if self.drafts:
                got = np.asarray(tok0)
                lps = got[2:].view(np.float32)
                tok0 = FirstToken(int(got[0]), float(lps[0]), int(got[1]),
                                  float(lps[1]))
            else:
                tok0 = int(tok0)
        now = time.perf_counter()
        self.cb_wait = (t, now)
        perf.observe_step(self._cb_prefill_name(width), now - t0)
        perf.mark_serving_ready()      # first warm token (latch)
        return tok0

    def run_cb_decode(self, params, pools, tokens: np.ndarray,
                      ntoks: np.ndarray, tables: np.ndarray):
        """One decode step for all S slots: `dispatch_cb_decode` and
        `fetch_cb_decode` under one span.  Returns ((S,) int32 next
        tokens on host, or a `StepTokens` where the model drafts; new
        pools).  `pools` was donated."""
        with obs.span("engine.cb_decode"):
            flying, pools = self._hand_over(params, pools, tokens, ntoks,
                                            tables)
            return self._fetch(flying), pools

    def _cb_decode_args(self, tokens, ntoks, tables):
        """The decode program's small inputs as the call takes them.
        `tokens` is the host's (S,) array or, as it is, what the step
        before gave the device.  Host arrays go in as they are and the
        call's own transfer moves them: three `jnp.asarray` cost a
        step 0.8 ms of Python with the device idle (PERF.md 6, PR 28).
        The caller keeps them unchanged until the step is read, or
        hands over copies.  Where the model drafts the rows each slot
        holds travel in `tokens` (behind the host's tokens, or as the
        step before left them on the device) and `ntoks` is not an
        argument of the program."""
        if isinstance(tokens, np.ndarray):
            behind = self._cb_width - tokens.shape[0]
            if self.drafts:
                tokens = np.concatenate(
                    [tokens, ntoks,
                     np.zeros((behind - ntoks.shape[0],), np.int32)])
            elif behind:
                tokens = np.concatenate(
                    [tokens, np.zeros((behind,), tokens.dtype)])
            tokens = np.asarray(tokens, np.int32)
        args = [tokens, np.asarray(tables, np.int32), self._next_key()]
        if not self.drafts:
            args.insert(1, np.asarray(ntoks, np.int32))
        return args

    def _cb_tokens(self, nxt: np.ndarray):
        """A fetched step's (S,) tokens, or its `StepTokens`; the
        routing counts behind them go to the stats."""
        s = self.spec.cb_slots
        if self._cb_tail:
            tail = nxt[-self._cb_tail:]
            self.stats.observe_routing(int(tail[0]), int(tail[1]),
                                       len(self._routed_layers),
                                       int(tail[2:].sum()))
        if not self.drafts:
            return nxt[:s]
        (_, ntoks, first, bonus, count, draft, lp1, lp2,
         lq) = nxt[:STEP_ROWS * s].reshape(STEP_ROWS, s)
        return StepTokens(np.stack([first, bonus], 1), count,
                          np.stack([lp1, lp2], 1).view(np.float32), ntoks,
                          draft, lq.view(np.float32))

    def _hand_over(self, params, pools, tokens, ntoks, tables):
        self._maybe_stall()
        compiled = self._compile_cb("decode")
        self._cb_flying_at.append(time.perf_counter())
        with obs.span("engine.upload"):
            args = self._cb_decode_args(tokens, ntoks, tables)
        with obs.span("engine.dispatch"):
            nxt, pools = compiled(params, pools, *args)
            # on its way to the host as soon as the device has it
            nxt.copy_to_host_async()
            return nxt, pools

    def dispatch_cb_decode(self, params, pools, tokens, ntoks: np.ndarray,
                           tables: np.ndarray):
        """`run_cb_decode` up to the hand-over to the device: returns
        (the step's tokens as they lie on the device, new pools)
        without waiting.  `tokens` may be such a return of the step
        before: then that step's tokens go in unread (and, where the
        model drafts, the rows each slot holds behind them: `ntoks` is
        then the host's last knowledge and is not used)."""
        with obs.span("engine.cb_decode"):
            return self._hand_over(params, pools, tokens, ntoks, tables)

    def fetch_cb_decode(self, flying):
        """The (S,) host tokens of a dispatched step, or its
        `StepTokens` (waits)."""
        return self._fetch(flying)

    def _fetch(self, flying):
        """The step's period goes to the step account: from when the
        tokens before it were read, or from its own hand-over if that
        was later, to now."""
        t = time.perf_counter()
        with obs.span("engine.fetch"):
            nxt = self._cb_tokens(np.asarray(flying))
        now = time.perf_counter()
        self.cb_wait = (t, now)
        handed = self._cb_flying_at.popleft() if self._cb_flying_at else t
        perf.observe_step("cb_decode", now - max(handed, self._cb_read_at))
        self._cb_read_at = now
        return nxt

    def _compile(self, mode: str, batch: int, prompt_len: int):
        key = (mode, batch, prompt_len)
        got = self._compiled.get(key)
        if got is not None:
            perf.lookup_hit(mode)
            return got
        with self._compile_lock:
            got = self._compiled.get(key)
            if got is not None:
                perf.lookup_hit(mode)
                return got
            if self._params is None:
                raise RuntimeError("engine has no params; call load()")
            if mode not in MODES:
                raise ValueError(f"unknown mode {mode!r}; modes are "
                                 f"{MODES}")
            with obs.span("engine.compile", mode=mode, batch=batch,
                          plen=prompt_len), \
                 perf.compile_span(mode,
                                   geometry=f"b{batch}_p{prompt_len}",
                                   scope=self._perf_scope,
                                   family=mode):
                p_spec = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    self._params)
                tok = jax.ShapeDtypeStruct((batch, prompt_len),
                                           jnp.int32)
                pl = jax.ShapeDtypeStruct((batch,), jnp.int32)
                if mode == "generate":
                    fn = self._build_generate(batch, prompt_len)
                    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
                    compiled = jax.jit(fn).lower(p_spec, tok, pl,
                                                 rng).compile()
                else:
                    fn = self._build_predict(batch, prompt_len)
                    compiled = jax.jit(fn).lower(p_spec, tok,
                                                 pl).compile()
            self.stats.count("compiles")
            self._compiled[key] = compiled
            perf.harvest(mode, compiled)
            return compiled

    def warmup(self, modes=("generate",)) -> int:
        """Compile every (mode, bucket) executable up front.  Returns
        the number of compiles performed; after this, steady-state
        serving never compiles again (stats.compiles stays put)."""
        before = self.stats.compiles
        for mode in modes:
            if mode == "generate" and self.spec.cb_on:
                # cb replaces the generate buckets with the prefill
                # ladder and the decode step, whatever the bucket list
                # says; predict stays on buckets.  One after another: on
                # a TPU their compiles, and the persistent cache's
                # fetches, run slower side by side (PERF.md 6, PR 28)
                # (an engine that chunks sends EVERY prompt through the
                # chunk programs, a short one as a single last chunk:
                # one ladder either way)
                which = "chunk" if self.chunks_prompts else "prefill"
                for width in self.spec.cb_prefill_widths:
                    self._compile_cb(which, width)
                self._compile_cb("decode")
                continue
            for b, p in self.spec.buckets:
                self._compile(mode, b, p)
        for mode in modes:
            # from here on, a compile in this engine's scope for a
            # warmed mode family is a perf.recompile_anomaly
            perf.mark_warm(self._perf_scope, mode)
        return self.stats.compiles - before

    def harvest_costs(self) -> int:
        """CostWatch sweep: re-harvest `cost_analysis()` off every
        already-compiled executable.  Reads cached objects only —
        never lowers or compiles — so `stats.compiles` is unchanged
        (tests/test_perf_obs.py).  Returns programs harvested."""
        with self._compile_lock:
            items = list(self._compiled.items())
        for key, compiled in items:
            perf.harvest(key[0], compiled)
        return len(items)

    # -- execution ----------------------------------------------------------
    def set_stall(self, seconds: float) -> None:
        """Latch `seconds` of host-side sleep onto every compiled call
        (0 clears it).  Benches/tests use this for deterministic
        per-engine targeting; the `engine.stall` fault site latches
        `spec.stall_fault_s` on whichever engine's thread it fires in."""
        self.stall_s = max(float(seconds), 0.0)

    def _maybe_stall(self) -> None:
        kind = faults.maybe_fault("engine.stall")
        if kind == "stall":
            self.stall_s = max(self.stall_s,
                               float(self.spec.stall_fault_s))
        if self.stall_s > 0:
            time.sleep(self.stall_s)

    def _next_key(self) -> np.ndarray:
        # raw threefry key data, built host-side: no jax dispatch (and
        # no trace) on the per-batch path
        with self._key_lock:
            n = self.spec.seed * 1000003 + self._key_counter
            self._key_counter += 1
        return np.array([(n >> 32) & 0xFFFFFFFF, n & 0xFFFFFFFF],
                        np.uint32)

    def run_batch(self, mode: str, tokens: np.ndarray,
                  plens: np.ndarray, params=None) -> np.ndarray:
        """Run one padded micro-batch through the bucket's compiled
        executable.  `tokens` (B, P) int32 LEFT-padded with
        spec.pad_id, `plens` (B,) int32 real prompt lengths.  `params`
        is the tree the dispatcher captured from `self.params` (falls
        back to the live tree for direct callers).  Returns (B,
        max_new_tokens) int32 for generate, (B, V) float32 next-token
        log-probs for predict."""
        if params is None:
            params = self._params
        b, p = tokens.shape
        # on the dispatch thread this nests under batcher.dispatch and
        # inherits its batch-M correlation id
        with obs.span("engine.run_batch", mode=mode, batch=b, plen=p):
            self._maybe_stall()
            compiled = self._compile(mode, b, p)
            tokens = jnp.asarray(tokens, jnp.int32)
            plens = jnp.asarray(plens, jnp.int32)
            t0 = time.perf_counter()
            if mode == "generate":
                out = compiled(params, tokens, plens, self._next_key())
            else:
                out = compiled(params, tokens, plens)
            out = np.asarray(out)
            perf.observe_step(mode, time.perf_counter() - t0)
            if mode == "generate":
                perf.mark_serving_ready()   # first warm token (latch)
        return out
