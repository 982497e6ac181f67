"""Continuous-batching KV-cache decode scheduler (Orca, OSDI'22).

Static request batching decodes a gang of requests until the LAST one
finishes: a 5-token reply waits for the 200-token reply it shares a batch
with, and its slot emits padding the whole time. Iteration-level
("continuous") batching reschedules at TOKEN granularity instead — a
fixed-slot decode program (`models.zoo.transformer.make_slot_decode_fn`)
runs one token for every occupied slot per dispatch, and requests join or
leave slots BETWEEN dispatches. Prefill and decode are separated: a
joining request's prompt runs through a per-prompt-length-bucket prefill
program (`make_prefill_fn`) whose cache rows are scattered into the free
slot, then the request rides the shared decode program.

Determinism pin (tests/test_serving.py): a request's token stream is
bit-identical whether it decodes alone or joins a running batch — every
slot's row math touches only its own cache/pos/token rows, and inactive
slots' cache writes are gated. So continuous batching is a pure
throughput lever, not an accuracy trade.

Hot swap keeps MULTIPLE param versions live while draining (one per
undrained swap, typically two): slots keep the version they started with
(a compiled program takes params as arguments, so versions share ONE
executable), each iteration dispatches once per live version with the
active mask restricted to that version's slots, and new requests route
to the newest version immediately — zero admission stall, zero dropped
in-flight requests. Drained versions are released on request completion
AND on idle iterations, so repeated swaps never accumulate dead params.

Speculative decoding (`speculate=`, serving/speculate.py): the 1-token
step is replaced by a K-wide verify program (`make_slot_verify_fn`) —
each iteration drafts K-1 tokens per slot (host-side n-gram lookup or a
small draft model) and ONE dispatch accepts 1..K of them per slot.
Slots advance VARIABLE token counts per iteration (the per-slot
positions already support ragged advance), streams stay bit-identical
to plain greedy decode (the accepted tokens are the verify program's
own argmax chain by construction; cross-width argmax parity is pinned
by test — see speculate.py), and speculation composes with the
dual-version swap drain (verify runs under the slot's pinned version;
the draft needs no pinning — it can only cost acceptance).

Deadlines are enforced mid-decode, not just at admission: a slot whose
request outlives its latency budget is evicted between iterations
(future fails with DeadlineExceededError, shed counted, slot refilled
the same iteration).

Paged KV cache (`paged=True`, serving/kvpool.py + the zoo's
`make_paged_decode_fn` / `make_paged_prefill_fn`): the fixed-slot cache
reserves `max_len` rows per slot, so concurrency is bounded by
WORST-CASE length. Paged mode keeps one flat block arena instead; every
request holds a block table, admission is gated by FREE BLOCKS (a
request that cannot get its blocks waits in a memory queue — counted
`blocked_on_memory` — while slots are a pure scheduling width), and
prompt prefixes shared across requests (system prompts, few-shot
templates) map to ONE physical copy with copy-on-write before any
divergent append. Prefill is two programs — a pure prefill returning
k/v panels plus a small DONATED install scatter (mirroring the fixed
path; a fused install would copy the whole undonated arena); decode
stays one dispatch per iteration — paging adds ZERO device dispatches
per token (pinned by counter A/B in tests/test_paged.py), and the
join==solo determinism pin carries over unchanged. `paged=True`
COMPOSES with `speculate=`: the K-wide verify program has a
block-table twin (`make_paged_verify_fn` — writes at table-mapped
frontier rows under the same [wfrom, wto) index gate the paged chunk
program uses, gather attention over the slot's logical window), so
the production configuration keeps the dispatch-amortization win. A
speculative round consumes only blocks its reserve-at-admit table
already holds (no new allocation path), and a CoW-shared partial
block materializes before the FIRST verify dispatch — the K-wide
write starts at the frontier inside that block, so the 1-wide CoW
rule covers it unchanged.

Overload control (PR 9; serving/admission.py + the zoo's
`make_chunked_prefill_fn`) makes saturation a SURVIVABLE regime instead
of the goodput collapse PR 7 measured (past the knee: 2,515 -> 635
tok/s, TTFT p99 x30, queue_wait 72% of request time). Three levers:

* **Chunked prefill** (`chunked_prefill=C`): a joining request's prompt
  no longer runs as one monolithic prefill dispatch that stalls every
  co-resident stream for the whole prompt. The request is admitted into
  its slot in a PREFILL phase and advances C rows per scheduling
  iteration through a verify-shaped chunk program (fixed-slot and paged
  layouts), interleaved with everyone else's decode iterations — the
  head-of-line stall shrinks from O(prompt) to O(chunk), which is what
  the `sched_gap` phase in obs/decompose.py measures. The SIZING RULE
  (see _admit): only prompts longer than one chunk take this path — a
  short prompt already is a chunk-sized stall, and the one-shot bucket
  program runs it at [1, Pb] where the chunk program pays [slots, C].
  The chunked stream is BIT-IDENTICAL to the one-shot stream (the
  join==solo pin extended — tests/test_overload.py), and in paged mode
  chunking starts AFTER any resident shared prefix, so a prefix-cache
  hit now saves the prompt COMPUTE too (the partial-prefill seam PR 8
  left open), not just the memory.
* **Deadline-aware admission** (`admission=` an
  `admission.AdmissionController`, or True for defaults): a
  service-rate estimator over recent scheduling iterations (rolling
  median of iteration time + per-slot token rate — admission.py
  explains why those are the robust, occupancy-independent primitives)
  predicts, at ENQUEUE, when a request would complete behind the
  current backlog of work units; requests that cannot make their
  deadline are shed immediately as `shed_predicted` instead of eating
  queue slots and dying mid-decode. The estimator sheds LATE by
  construction (conservatism knob, cold warm-up guard) and
  SELF-CORRECTS systematic optimism: every prediction's signed error
  — completions exactly, evictions as a certain bound — feeds both
  the `admission_error_ms` histogram (observability) and the
  controller's bias loop.
* **Brownout policy** (`brownout=` an `admission.BrownoutPolicy`):
  accept/defer/shed per request CLASS (`submit(..., klass=)`) driven by
  queue depth and recent SLO attainment — deferred requests park in a
  side line served only when the primary queue is empty, so batch-class
  work yields to interactive work under pressure by POLICY, not queue
  accident. Deferred and memory-parked lines are both failed on
  fail-fast stop and both drain bounded by their remaining work on
  stop(drain=True) — expired deadlines shed at admission, so a
  saturated drain never decodes work nobody can use.
* **Durable KV state** (`serving/kvstate.py` + the zoo's
  `make_block_extract_fn`): a live request's KV block set can leave the
  arena as a host-side `RequestArtifact` (panel rows + token history +
  position + param-version tag) and come back bit-identically — ONE
  serialization primitive closing three production seams. (1)
  PREEMPTION (`preempt=True`, paged + brownout): when a request whose
  class outranks a live slot's (`BrownoutPolicy.may_preempt` — the
  accept/defer/shed verbs extended with preempt) is blocked on KV
  blocks, the victim slot is spilled to host (`preempted`,
  `spill_bytes`), its blocks go to the claimant, and the victim parks
  on a RESUME LINE served ahead of the queue as blocks free
  (`resumed`) — interactive TTFT is bounded at FULL BLOCK OCCUPANCY,
  which queue-depth admission structurally cannot do; the resume
  line's remaining work stays in the admission estimator's backlog
  (plus one re-install unit), so predictions price parked work
  truthfully. (2) PERSISTENT PREFIX CACHE (`prefix_cache_dir=`): on
  stop(), the LRU-cached prefix blocks + index entries are saved under
  the newest param version's content fingerprint; a restarted server
  re-offers the warm blocks (`prefix_restore_hits`), and a restore
  under different params refuses them loudly
  (`KVStateVersionError` — the hot-swap invalidation rule extended
  across restarts). (3) MIGRATION (`migrate_out`/`migrate_in`): a live
  decode-phase request moves between server instances, tag-checked at
  import AND at admission, resumed bit-identical to an uninterrupted
  run — the seam prefill/decode disaggregation and replica fleets
  consume. Extraction is a pure table gather (never a write), so a
  still-pending CoW spare is simply FORGONE — the artifact carries the
  rows, release() returns the spare, and restore re-acquires shared
  leading blocks through the prefix index (refcount++, never
  duplicated) with its own CoW spare if it rides a partial block
  again. All of it composes with chunked prefill and speculation
  (victims/exports are decode-phase slots only; a prefilling slot is
  never spilled — its artifact would be a half-written panel), and the
  non-preempting path stays at ZERO added device dispatches per token
  (counter-pinned: extract/install run only when a spill actually
  happens).
* **Prefix-hit priority admission** (`prefix_priority=`, default on
  where it means something: paged + prefix_cache + chunked_prefill):
  a full-prefix-hit request costs ONE chunk of prefill (chunked paged
  prefill skips resident shared rows — the PR 9 compute reuse), so at
  equal queue position it buys strictly more goodput per slot-second
  than a cold prompt. submit() routes requests whose prompt is fully
  resident in the prefix index (cost == 1 chunk where a cold run would
  pay more) to a priority line served ahead of the primary queue —
  the admission predictor already prices both via `_pf_units`, and an
  admit that actually overtook queued work counts
  `admitted_prefix_priority`. The hit test at submit is advisory (the
  binding match re-runs at admission under the version tag, as
  always): an index entry evicted in between costs the request its
  priority, never its correctness. Priority requests carry the same
  deadline sweep, fail-fast, and drain contracts as the other parked
  lines; the line and the primary queue SHARE the `max_queue` budget
  (neither can stack pending work past the operator's bound); and
  after `_PRIO_BURST` consecutive overtakes the primary head takes
  one turn, so sustained hit traffic degrades cold prompts' position
  but can never starve them outright.
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import itertools
import logging
import os
import queue
import threading
import time

import numpy as np

from .. import obs
from .kvstate import (KVStateError, KVStateVersionError,
                      PrefixCacheArtifact, RequestArtifact,
                      artifact_kind)
from .server import (DeadlineExceededError, ReplicaDeadError,
                     RequestDrainedError, RequestMigratedError,
                     ServerClosedError, ServerOverloadedError,
                     _RequestLoop)

log = logging.getLogger(__name__)


def _param_fingerprint(aux, blocks):
    """Content fingerprint of one param version: sha256 over every
    leaf's shape/dtype/bytes. THE durable version tag
    (serving/kvstate.py): the in-process prefix index is namespaced by
    version INDEX, but an index means nothing across a restart or
    between servers — only the weights themselves do. Computed lazily
    once per version (the host transfer is paid only when durable
    state is actually saved/restored, never on the decode path)."""
    import hashlib

    import jax
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves((aux, blocks)):
        a = np.asarray(leaf)
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# cancel-race-safe future delivery: the ONE implementation now lives
# in server.py (the base loop's raced-stop paths need it too); the
# names stay importable from here (serving/fleet.py does)
from .server import _fail_future, _resolve_future  # noqa: E402


class _Wake:
    """Sentinel pushed through the PRIMARY queue to wake the idle
    blocking get when a priority submit parks in the side line (the
    get watches only the queue). Its future is born resolved, so every
    existing consumer discards it naturally: `_admit_pending` skips
    done-future requests, and the base `_fail_queued` only fails
    futures that are not done — no consumer needs to know sentinels
    exist."""

    __slots__ = ("future", "deadline", "req_id")

    def __init__(self):
        self.future = cf.Future()
        self.future.set_result(None)
        self.deadline = None
        self.req_id = None


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "future", "deadline", "t_submit",
                 "generated", "slot", "version", "req_id", "t_last_tok",
                 "alloc", "mem_blocked", "pf_next", "pf_wfrom",
                 "work_left", "work_counted", "predicted_done", "klass",
                 "prio_overtook", "pf_quoted", "artifact", "migrated",
                 "progress_base")

    def __init__(self, prompt, max_new, deadline, klass="default"):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.future = cf.Future()
        self.deadline = deadline
        self.t_submit = time.monotonic()
        self.generated = []
        self.slot = None
        self.version = None
        self.req_id = None      # assigned at submit (the trace/request id)
        self.t_last_tok = None  # when this request's last token landed
        self.alloc = None       # paged mode: kvpool.PagedAllocation
        self.mem_blocked = False    # counted blocked_on_memory once
        self.pf_next = None     # chunked prefill: next prompt row to run
        self.pf_wfrom = 0       # chunked paged: first row to WRITE
        self.work_left = int(max_new)   # admission backlog accounting
        self.work_counted = False       # work_left added to the backlog?
        self.predicted_done = None      # estimator's completion estimate
        self.klass = klass      # brownout request class
        self.prio_overtook = False  # popped off the priority line ahead
        #                             of queued work; counted at ADMIT
        self.pf_quoted = 1      # prefill units QUOTED at submit (a
        #                         priority hit is quoted 1 chunk; the
        #                         chunked admit retires against this)
        self.artifact = None    # durable KV state parked for resume
        #                         (kvstate.RequestArtifact: preempted
        #                         or migrated-in; None once installed)
        self.migrated = False   # arrived via migrate_in (counted
        #                         `migrated` at restore admission;
        #                         preempted locals count `resumed`)
        self.progress_base = 0  # len(generated) at the last restore:
        #                         a victim must advance
        #                         _PREEMPT_MIN_PROGRESS tokens past
        #                         this before it may be spilled again
        #                         (anti-thrash — see _try_preempt_for)


class ContinuousDecodeServer(_RequestLoop):
    """Token-granularity serving endpoint over a TransformerLM.

    `submit(prompt, max_new_tokens)` returns a Future resolving to the
    full token list (prompt + generated, greedy decode — the
    `generate_batch` contract). `static_batching=True` degrades scheduling
    to gang admission (a new batch only forms when every slot is free) —
    the A/B baseline of continuous batching, through the exact same
    machinery.
    """

    _thread_name = "continuous-decode"
    _default_stop_timeout = 60.0
    # a preemption victim must have decoded this many tokens since its
    # last (re)start before it may be spilled again: each spill's
    # extract+install round-trip is amortized over at least this much
    # progress, so sustained interactive pressure degrades a batch
    # stream's latency but can never pin it in a spill/restore loop
    # with O(1) tokens per full-panel round-trip
    _PREEMPT_MIN_PROGRESS = 4
    # after this many consecutive priority overtakes, the primary
    # queue's head gets one turn: sustained prefix-hit traffic must
    # never starve cold prompts outright (the hit line is a goodput
    # preference, not an SLA inversion)
    _PRIO_BURST = 4
    # fleet prefix tier: max artifact bytes serviced per scheduling
    # iteration by _service_prefix_ops (at least one command always
    # runs) — bounds the extract/install work a burst of peer pulls can
    # steal from one iteration, so the tier can never stall serving
    _PREFIX_IO_BUDGET = 4 << 20

    def __init__(self, lm, slots=4, prompt_buckets=(8, 16, 32),
                 max_queue=64, fault_injector=None, retry_policy=None,
                 metrics=None, stats_reporter=None, report_every=64,
                 static_batching=False, speculate=None, tracer=None,
                 flight_recorder=None, paged=False, block_size=16,
                 n_blocks=None, prefix_cache=True,
                 max_blocks_per_slot=None, chunked_prefill=None,
                 admission=None, brownout=None,
                 default_deadline_ms=None, prefix_priority=True,
                 preempt=False, prefix_cache_dir=None, instance=None,
                 fused_serve=None):
        from ..models.zoo.transformer import (make_block_copy_fn,
                                              make_block_extract_fn,
                                              make_chunked_prefill_fn,
                                              make_fused_decode_fn,
                                              make_paged_decode_fn,
                                              make_paged_fused_decode_fn,
                                              make_paged_install_fn,
                                              make_paged_prefill_fn,
                                              make_paged_verify_fn,
                                              make_prefill_fn,
                                              make_slot_decode_fn)
        from .admission import AdmissionController
        from .speculate import as_speculator
        import jax

        self._tracer = tracer if tracer is not None else obs.TRACER
        self._flight = flight_recorder
        self.lm = lm
        self.slots = int(slots)
        self.max_len = int(lm.aux["pos"].shape[0])
        self.prompt_buckets = tuple(sorted(int(b) for b in prompt_buckets))
        if self.prompt_buckets[-1] > self.max_len:
            raise ValueError(f"largest prompt bucket "
                             f"{self.prompt_buckets[-1]} > model max_len "
                             f"{self.max_len}")
        self._injector = fault_injector
        self._retry = retry_policy
        from .metrics import ServingMetrics
        # instance identity (the fleet plane, obs/fleet.py): names this
        # server in federated metrics (ServingMetrics endpoint name),
        # merged traces (per-instance process groups), and — when set
        # EXPLICITLY — the request/trace ids themselves ("i0-7"), so a
        # request migrated between named instances keeps one globally
        # unique trace id across both servers' traces. Default (None)
        # keeps plain integer ids: single-server behavior unchanged.
        self.metrics = metrics or ServingMetrics(name=instance)
        self.instance = (str(instance) if instance is not None
                         else self.metrics.name)
        self._named_instance = instance is not None
        self._reporter = stats_reporter
        self._report_every = max(1, int(report_every))
        self._static = bool(static_batching)

        n_heads = lm.n_heads
        self._n_heads = n_heads
        self._d_model = int(lm.aux["tok"].shape[1])
        self._cache_dtype = lm.aux["tok"].dtype
        self._n_layers = len(lm.blocks)
        self._versions = [(lm.aux, lm.blocks)]   # index = param version

        # paged KV cache (module docstring): arena + block tables
        # replace the fixed per-slot cache; admission gates on free
        # blocks. Config resolves BEFORE _reset_device_state builds the
        # device state from it.
        self._paged = bool(paged)
        self._block_size = int(block_size)
        if self._paged and self._block_size < 1:
            raise ValueError(f"need block_size >= 1, got {block_size}")
        # default arena == the fixed-slot footprint at the same slot
        # count (equal bytes); callers scale slots/arena independently
        self._n_blocks = (int(n_blocks) if n_blocks is not None else
                          -(-self.slots * self.max_len
                            // self._block_size))
        # per-slot logical capacity: enough table entries for max_len
        # rows (the submit() length guard caps every stream there)
        self._nb_slot = (int(max_blocks_per_slot)
                         if max_blocks_per_slot is not None else
                         -(-self.max_len // self._block_size))
        self._prefix_cache = bool(prefix_cache)
        self._mem_wait = collections.deque()     # blocked on FREE BLOCKS

        # overload control (module docstring; serving/admission.py):
        # chunk size, admission predictor, brownout policy, default
        # per-request deadline (the InferenceServer contract)
        self._chunk = None if chunked_prefill is None \
            else int(chunked_prefill)
        if self._chunk is not None and self._chunk > self.max_len:
            raise ValueError(f"chunked_prefill {self._chunk} > model "
                             f"max_len {self.max_len}")
        self._admission = (AdmissionController() if admission is True
                           else admission)
        if self._admission is not None and \
                self._admission.estimator.slots is None:
            # predictions scale capacity by the scheduling width; a
            # caller-built controller usually leaves it for us to fill
            self._admission.estimator.slots = self.slots
        self._brownout = brownout
        self.default_deadline = (None if default_deadline_ms is None
                                 else float(default_deadline_ms) / 1e3)
        self._defer_q = collections.deque()      # brownout-deferred line
        # prefix-hit priority admission (module docstring): effective
        # only where a full-prefix hit really is cheaper — paged prefix
        # cache + chunked prefill, where a full hit costs ONE chunk
        # while a cold prompt pays ceil(P/C)
        self._prefix_priority = (bool(prefix_priority) and self._paged
                                 and self._prefix_cache
                                 and self._chunk is not None)
        self._prio_q = collections.deque()       # prefix-hit fast line
        self._prio_streak = 0   # consecutive genuine overtakes (anti-
        #                         starvation: see _next_request)
        # durable KV state (module docstring; serving/kvstate.py):
        # preemption policy, the resume line, migration plumbing, and
        # the persistent prefix-cache directory. The preempt verb needs
        # BOTH the paged pool (fixed-slot state has no extractable
        # block set) and a brownout policy (class ranking IS the
        # policy; without one no class may preempt another and the
        # flag would be a silent no-op).
        self._preempt_on = bool(preempt)
        if self._preempt_on and not self._paged:
            raise ValueError("preempt=True requires paged=True (only a "
                             "block-table KV set can be spilled)")
        if self._preempt_on and brownout is None:
            raise ValueError("preempt=True requires a brownout= policy: "
                             "BrownoutPolicy.may_preempt ranks request "
                             "classes, and without a ranking nothing "
                             "may ever be preempted")
        self._prefix_dir = (None if prefix_cache_dir is None
                            else str(prefix_cache_dir))
        if self._prefix_dir is not None and not (
                self._paged and self._prefix_cache):
            raise ValueError("prefix_cache_dir requires paged=True with "
                             "prefix_cache=True (there is no prefix "
                             "cache to persist otherwise)")
        self._resume_q = collections.deque()     # serve-thread ONLY:
        #   spilled requests (artifact set) awaiting blocks + a slot
        self._migrate_in_q = collections.deque()  # client -> serve
        #   staging for migrate_in (drained into _resume_q by the loop
        #   so _resume_q never races a client append)
        self._migrate_cmds = collections.deque()  # (future, reply)
        self._prefix_cmds = collections.deque()  # fleet prefix tier:
        #   ("export", key, max_bytes, reply) | ("adopt", art, reply) —
        #   serviced at the iteration boundary under a per-iteration
        #   bytes budget so the tier can never stall serving
        self._prefix_io_budget = self._PREFIX_IO_BUDGET
        self._drain_cmds = collections.deque()   # (migrate, reply):
        #   the fleet drain verb — serve thread hands back EVERY
        #   admitted request in one pass (see drain())
        self._killed = False    # crash-injection verb fired (kill());
        #   terminal — a killed replica never serves again
        self._tag_cache = {}    # version index -> param fingerprint
        self._prefix_saved = True   # nothing to save before start()
        self._gate_key = None   # preempting-gate rescan guard: the
        #   (pool, progress, depth) signature of the last full scan
        #   that admitted nothing — identical signature => skip
        self._work_lock = threading.Lock()
        self._work_tokens = 0   # work-unit backlog (queued + live)
        # admission hysteresis: any actual eviction/queue expiry
        # CONFIRMS overload and tightens prediction shedding to exactly
        # the deadline budget for this long (admission.py should_shed)
        self._thrash_until = 0.0

        self._reset_device_state()
        # ONE decode program for the life of the server (fixed slot count;
        # params are arguments, so hot swap reuses it). Cache and pos are
        # donated — they are THE device state, rebound every iteration.
        if self._paged:
            # (aux, blocks, cache, btabs, pos, tok, active)
            self._step = jax.jit(
                make_paged_decode_fn(n_heads, self._block_size),
                donate_argnums=(2, 4))
        else:
            self._step = jax.jit(make_slot_decode_fn(n_heads),
                                 donate_argnums=(2, 3))
        # chunked prefill (module docstring): ONE verify-shaped chunk
        # program for the life of the server — every prefilling slot
        # advances C prompt rows per scheduling iteration through it,
        # interleaved with the decode dispatches. Cache and pos are
        # donated exactly like the decode step's: chunk dispatches run
        # inside the scheduler loop, whose terminal-failure path resets
        # the whole device state anyway.
        if self._chunk is None:
            self._chunk_step = None
        elif self._paged:
            self._chunk_step = jax.jit(
                make_chunked_prefill_fn(n_heads, self._chunk,
                                        self._block_size),
                donate_argnums=(2, 4))
        else:
            self._chunk_step = jax.jit(
                make_chunked_prefill_fn(n_heads, self._chunk),
                donate_argnums=(2, 3))
        # rolling window of recent SLO outcomes (1 met / 0 missed): the
        # brownout policy's attainment signal — RECENT, not all-time,
        # so recovery after a burst reopens admission
        self._slo_recent = collections.deque(maxlen=64)
        # speculative decoding (serving/speculate.py): ONE K-wide verify
        # program replaces the 1-token step for every iteration — drafts
        # in, 1..K accepted tokens out per slot per dispatch, token
        # streams pinned bit-identical to the plain step. Fixed layout:
        # the model's OWN cached verify jit (`_spec_verify`), shared
        # with generate(draft=...) so the same (model, K) never
        # compiles twice. Paged layout: the block-table verify twin
        # (`make_paged_verify_fn`), jitted here because block_size is
        # server config; cache and pos donated exactly like the decode
        # step's — they are THE device state, and the loop's
        # terminal-failure path resets all of it anyway.
        self._spec = as_speculator(speculate)
        if self._spec is None:
            self._verify = None
        elif self._paged:
            self._verify = jax.jit(
                make_paged_verify_fn(n_heads, self._spec.k,
                                     self._block_size),
                donate_argnums=(2, 4))
        else:
            self._verify = lm._spec_verify(self._spec.k)
        # fused decode windows (module docstring; ISSUE 18): scan K
        # decode iterations into ONE device dispatch — nn/fused.py's
        # fused_steps applied to serving. K=1 is the plain path exactly
        # (no window program is even built), so the flag defaults to
        # zero behavior change. Slot membership is static inside a
        # window: admissions, evictions, chunked-prefill transitions,
        # and deadline sweeps all land at window boundaries
        # (_loop_once runs them once per pass, and one fused pass IS
        # one window). Cache and pos are donated exactly like the
        # 1-wide step's — same device state, same terminal-failure
        # reset contract.
        self._fused = 1 if fused_serve is None else int(fused_serve)
        if self._fused < 1:
            raise ValueError(f"fused_serve must be >= 1, got "
                             f"{fused_serve}")
        if self._fused > 1 and self._spec is not None:
            # the PR 8 composition precedent: refuse LOUDLY at the
            # constructor instead of silently picking one mode — a
            # fused window advances every slot one token per scanned
            # step, while speculation needs fresh host-side drafts
            # every iteration; the two cannot share a dispatch yet
            raise ValueError(
                "fused_serve > 1 does not compose with speculate= "
                "(a fused window cannot take fresh drafts mid-scan); "
                "configure one or the other")
        if self._fused > 1:
            if self._paged:
                # (aux, blocks, cache, btabs, pos, tok, active, steps,
                #  wto)
                self._window_step = jax.jit(
                    make_paged_fused_decode_fn(
                        n_heads, self._block_size, self._fused),
                    donate_argnums=(2, 4))
            else:
                # (aux, blocks, cache, pos, tok, active, steps)
                self._window_step = jax.jit(
                    make_fused_decode_fn(n_heads, self._fused),
                    donate_argnums=(2, 3))
        else:
            self._window_step = None
        # per-iteration wall-time EWMA: the fused deadline clamp's rate
        # estimate (None until the first token-bearing iteration)
        self._iter_ewma = None
        self._prefills = {}                      # bucket -> jitted program
        # Paged prefill mirrors the fixed path's two-program shape:
        # a pure-compute prefill returning panels (no arena argument —
        # an admission-time failure must fail ONLY that request, and a
        # program that neither takes nor returns the arena trivially
        # leaves it valid) plus a small DONATED install scatter that
        # aliases the arena in place. Fusing install into the prefill
        # would force the arena through an UNDONATED output and copy
        # every untouched row — the whole pool's bytes — per admission.
        # The CoW copy is donated for the same reason; it runs inside
        # _decode_iteration, whose failure path — like the donated
        # decode step's — resets the entire device state anyway.
        if self._paged:
            self._make_prefill = lambda: jax.jit(make_paged_prefill_fn(
                n_heads))
            self._paged_install = jax.jit(
                make_paged_install_fn(self._block_size),
                donate_argnums=(0,))
            self._cow_copy = jax.jit(
                make_block_copy_fn(self._block_size),
                donate_argnums=(0,))
            # durable-KV extract: a pure [NB]-table gather (arena read,
            # never donated) — one compiled program per server, shared
            # by preemption, migration export, and the prefix-cache
            # save (which batches cached blocks through the same table
            # width)
            self._extract = jax.jit(
                make_block_extract_fn(self._block_size))
        else:
            self._make_prefill = lambda: jax.jit(make_prefill_fn(
                n_heads, self.max_len))

            def install(cache, rows, s):
                return [{"k": c["k"].at[s].set(r["k"][0]),
                         "v": c["v"].at[s].set(r["v"][0])}
                        for c, r in zip(cache, rows)]
            # only the cache is donated: its buffers alias the output
            # exactly, while the [1, L, H, hd] prefill rows never could
            self._install = jax.jit(install, donate_argnums=(0,))

        self._swap_lock = threading.Lock()
        self._init_loop(max_queue)
        if self._named_instance:
            # namespaced request/trace ids: every span lane and trace
            # context this server emits is unique across the fleet
            self._req_ids = (f"{self.instance}-{n}"
                             for n in itertools.count())
        if self._prefix_dir is not None and \
                artifact_kind(self._prefix_dir) == "prefix_cache":
            # warm start: a committed snapshot exists — restore it into
            # the fresh pool BEFORE serving begins. A version mismatch
            # raises KVStateVersionError out of the constructor (LOUD:
            # the operator pointed a new model at an old cache; zero
            # silent reuse). An absent/partial snapshot is a cold
            # start, not an error.
            self.restore_prefix_cache(self._prefix_dir)

    # -- client API ----------------------------------------------------
    def submit(self, prompt, max_new_tokens, deadline_ms=None,
               klass="default"):
        """Enqueue one decode request; Future resolves to the full token
        list (prompt + `max_new_tokens` greedy continuations).
        `deadline_ms` falls back to the server's `default_deadline_ms`;
        `klass` is the brownout request class (ignored without a
        `brownout=` policy)."""
        if not self._running:
            raise ServerClosedError("server is not running")
        prompt = [int(t) for t in np.asarray(prompt).ravel()]
        if not prompt:
            raise ValueError("prompt must contain at least one token")
        if int(max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) > self.prompt_buckets[-1]:
            raise ValueError(f"prompt length {len(prompt)} exceeds the "
                             f"largest bucket {self.prompt_buckets[-1]}")
        if len(prompt) + int(max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt+new tokens ({len(prompt)}+{max_new_tokens}) "
                f"exceed max_len {self.max_len}")
        if self._paged:
            # never-fits check: a request whose worst-case block table
            # exceeds the WHOLE pool would wait forever in the memory
            # queue — shed it loudly at submit instead
            need = self._pool.blocks_needed(
                len(prompt) + int(max_new_tokens) - 1)
            if need > self._n_blocks:
                self.metrics.count("shed_blocks")
                raise ServerOverloadedError(
                    f"request needs {need} KV blocks but the pool holds "
                    f"{self._n_blocks} (block_size="
                    f"{self._block_size})")
            if need > self._nb_slot:
                # the per-slot block TABLE is the other hard ceiling: a
                # caller-tuned max_blocks_per_slot below ceil(max_len/bs)
                # must shed here, not crash the admission thread on the
                # table write
                self.metrics.count("shed_blocks")
                raise ServerOverloadedError(
                    f"request needs {need} KV blocks but a slot's table "
                    f"holds {self._nb_slot} (max_blocks_per_slot)")
        if self._injector is not None:
            self._injector.fire("serve.request")
        self.metrics.count("received")
        now = time.monotonic()
        if deadline_ms is not None:
            dl = now + deadline_ms / 1e3
        else:
            dl = (now + self.default_deadline
                  if self.default_deadline is not None else None)
        deferred = False
        if self._brownout is not None:
            from .admission import DEFER, SHED
            # maxsize <= 0 is queue.Queue's unbounded convention: depth
            # pressure is undefined there, so the depth thresholds never
            # engage (attainment brownout still can). The priority line
            # counts toward depth: its requests bypass the queue.Queue
            # but are pending work all the same.
            frac = ((self._q.qsize() + len(self._prio_q))
                    / self._q.maxsize if self._q.maxsize > 0 else 0.0)
            decision = self._brownout.decide(
                klass, frac, self._recent_attainment())
            if decision == SHED:
                self.metrics.count("shed_brownout")
                self.metrics.record_queue_depth(self._pending_depth())
                raise ServerOverloadedError(
                    f"brownout: class {klass!r} shed at queue depth "
                    f"{frac:.0%}")
            deferred = decision == DEFER
        prio = False
        if self._prefix_priority and not deferred \
                and len(prompt) > self._chunk:
            # prefix-hit priority (module docstring): a FULL-prefix hit
            # leaves at most one chunk of prefill where a cold prompt
            # pays ceil(P/C) — route it to the fast line. Advisory test
            # under the newest version tag; the binding match re-runs
            # at admission, so an index entry evicted in between costs
            # priority, never correctness. Prompts that fit one chunk
            # anyway gain nothing and stay FIFO. The lookup runs on the
            # CLIENT thread against pool dicts the serve thread
            # mutates: a raced resize mid-walk degrades to FIFO (the
            # same cost as a missed match), never to a failed submit.
            with self._swap_lock:
                vidx = len(self._versions) - 1
            try:
                rows = self._pool.match_prefix(prompt, tag=vidx)[1]
            except RuntimeError:    # dict resized during the walk
                rows = 0
            start = min(rows, len(prompt) - 1)
            prio = len(prompt) - start <= self._chunk
        if self._admission is not None and dl is not None \
                and not deferred:
            # predicted completion at ENQUEUE: work ahead (queued + live
            # generated-token backlog) plus this request's own budget,
            # over the measured aggregate rate. Shedding here — before
            # the request costs a queue slot, blocks, or decode work —
            # is the whole point; the estimator's conservatism contract
            # (sheds late, never a request solo execution could finish
            # in time) lives in serving/admission.py and is pinned by
            # property test. Submit-time sheds stay out of slo_total,
            # matching the queue-full precedent: attainment is over
            # ADMITTED requests.
            backlog = self._work_tokens
            # the predictor prices BOTH prefill costs: a priority-line
            # prefix hit re-runs one chunk, a cold prompt its full
            # chunk count — so a hit request's shed decision reflects
            # the cheaper admission it will actually get
            own = int(max_new_tokens) + (1 if prio else
                                         self._pf_units(len(prompt)))
            if self._admission.should_shed(
                    backlog, own, dl - now,
                    strict=now < self._thrash_until):
                self.metrics.count("shed_predicted")
                pred = self._admission.predict_seconds(backlog, own)
                raise ServerOverloadedError(
                    f"predicted completion in {pred * 1e3:.0f}ms behind "
                    f"{backlog} backlog work units cannot make the "
                    f"{(dl - now) * 1e3:.0f}ms deadline budget")
        req = _DecodeRequest(prompt, max_new_tokens, dl, klass=klass)
        # work is counted in ITERATION-EQUIVALENT units: generated
        # tokens plus the prefill dispatches (chunks) the prompt will
        # consume — a slot spends one scheduling iteration per unit, so
        # backlog predictions see prefill-heavy queues at true size.
        # A priority-line hit is QUOTED its real 1-chunk cost (matching
        # the shed decision above), so the prediction stamped below and
        # the bias loop's (predicted - actual) error measure the same
        # request the admission decision admitted — full-cost phantom
        # units here would read systematically pessimistic for every
        # hit and mask genuine optimism from cold requests.
        req.pf_quoted = 1 if prio else self._pf_units(len(prompt))
        req.work_left += req.pf_quoted
        if self._admission is not None and not deferred:
            # DEFERRED requests carry no prediction: their service time
            # is brownout policy (they yield until the primary queue
            # empties), and stamping a primary-queue prediction on them
            # would feed huge phantom "optimism" errors into the bias
            # loop and thrash window when they complete late BY DESIGN
            pred = self._admission.predict_seconds(
                self._work_tokens, req.work_left)
            if pred is not None:
                # stamped for the (predicted - actual) error histogram —
                # recorded for every admitted PRIMARY-line prediction,
                # deadline-tight or not, so the estimator's drift is
                # visible even while nothing is being shed
                req.predicted_done = now + pred
        # backlog accounting: the request's whole unit budget joins the
        # backlog now and retires unit-by-unit as it prefills/decodes;
        # ANY resolution of the future (result, failure, caller cancel)
        # retires the remainder exactly once, so the counter cannot
        # drift under sheds, evictions, or stop(). DEFERRED requests
        # join only when they leave the deferred line (_next_request):
        # they run BEHIND the primary queue, so counting them ahead of
        # primary submissions would invert the priority inside
        # predictions and shed feasible primary requests
        if not deferred:
            with self._work_lock:
                self._work_tokens += req.work_left
                req.work_counted = True
        req.future.add_done_callback(
            lambda _f, r=req: self._retire_work(r))
        try:
            if deferred:
                return self._enqueue_deferred(req)
            if prio:
                return self._enqueue_priority(req)
            return self._enqueue(req)
        except BaseException:
            self._retire_work(req)
            raise

    def _deadline_miss(self, req, now, thrash=True):
        """The ONE deadline-expiry bookkeeping path for all four shed
        sites (submit queue, memory gate, deferred line, mid-decode):
        counters, SLO miss, the rolling attainment window, admission
        feedback, and — unless the expiry is brownout deferral starving
        a class by POLICY rather than overload — the admission thrash
        window."""
        self.metrics.count("shed_deadline")
        self.metrics.record_slo_miss()
        self._slo_recent.append(0)
        self._admission_outcome(req, now, completed=False)
        if thrash:
            self._thrash_until = now + 0.5

    def _admission_outcome(self, req, now, completed):
        """Close one prediction's feedback loop: the signed
        (predicted - actual) error at completion; at an eviction/expiry
        the actual end is unknown but >= now, so a NEGATIVE
        (predicted - now) is a CERTAIN lower bound on the optimism —
        recorded too (an uninformative positive bound is dropped, and
        skipping evictions entirely would survivor-bias the histogram
        toward pessimism). Both the histogram (observability) and the
        controller's bias loop (self-correction) are fed here."""
        if req.predicted_done is None:
            return
        err = req.predicted_done - now
        req.predicted_done = None
        if not completed and err >= 0:
            return
        self.metrics.record_admission_error(err * 1e3)
        if self._admission is not None:
            self._admission.observe_error(err)

    def _pf_units(self, plen):
        """Prefill cost of a prompt in iteration-equivalent work units:
        its chunk count when it will take the chunked path (longer than
        one chunk — the sizing rule in _admit), one one-shot dispatch
        otherwise."""
        if self._chunk is not None and int(plen) > self._chunk:
            return -(-int(plen) // self._chunk)
        return 1

    def _retire_work(self, req):
        """Remove a request's unproduced work units from the admission
        backlog (idempotent — work_left zeroes on first retirement; a
        still-deferred request was never counted in)."""
        with self._work_lock:
            if req.work_counted:
                self._work_tokens -= req.work_left
            req.work_left = 0

    def _spend_work(self, req, units=1):
        """Retire `units` of a request's backlog as they are served."""
        with self._work_lock:
            n = min(units, req.work_left)
            req.work_left -= n
            self._work_tokens -= n

    def _recent_attainment(self):
        """Mean of the rolling SLO-outcome window (None while empty):
        the brownout policy's attainment input."""
        win = list(self._slo_recent)
        return (sum(win) / len(win)) if win else None

    def _enqueue_deferred(self, req):
        """Park a brownout-DEFERRED request in the side line the
        scheduler serves only when the primary queue is empty. Same
        contracts as `_enqueue`: bounded (sheds loudly when the line is
        as deep as the queue), traced, and a raced stop() fails the
        future rather than stranding the caller."""
        if req.req_id is None:
            req.req_id = next(self._req_ids)
        if 0 < self._q.maxsize <= len(self._defer_q):
            self.metrics.count("shed_queue_full")
            self.metrics.record_queue_depth(self._q.maxsize)
            raise ServerOverloadedError(
                f"deferred line full ({self._q.maxsize} parked)")
        self.metrics.count("deferred")
        self._defer_q.append(req)
        tr = self._tracer
        if tr.enabled:
            tr.instant("serve.enqueue", cat="serve",
                       track=f"req-{req.req_id}", trace_id=req.req_id)
        if not self._running:
            # _fail_future: cancel-race-safe (the base _enqueue rule)
            _fail_future(req.future, ServerClosedError(
                "server stopped during submit"))
            raise ServerClosedError("server stopped during submit")
        return req.future

    def _pending_depth(self):
        """Enqueue-time depth includes every parked line — the priority
        line and the resume/migrate-in lines are pending work the gauge
        must not hide — and the one base-class sample per enqueue stays
        the ONLY sample."""
        return (self._q.qsize() + len(self._prio_q)
                + len(self._resume_q) + len(self._migrate_in_q))

    def _shed_if_lines_full(self):
        """The ONE shared-budget check every admission path runs (plain
        submit, priority line, migrate_in): the primary queue and ALL
        parked lines — priority, resume, migrate-in staging — together
        may never stack pending work past `max_queue`, otherwise parked
        hits/artifacts plus queued colds would multiply the operator's
        backpressure bound (and the resume/staging lines hold full KV
        panels in host memory). (Two racing submits can each pass the
        sum check — the same benign width every parked-line bound has;
        the Queue's own put_nowait still hard-caps the primary line.)"""
        if 0 < self._q.maxsize <= self._pending_depth():
            self.metrics.count("shed_queue_full")
            self.metrics.record_queue_depth(self._pending_depth())
            raise ServerOverloadedError(
                f"queue full ({self._q.maxsize} pending incl. parked "
                f"lines)")

    def _enqueue(self, req):
        """The primary enqueue with the budget shared BOTH ways (see
        `_shed_if_lines_full`)."""
        self._shed_if_lines_full()
        return super()._enqueue(req)

    def _enqueue_priority(self, req):
        """Park a prefix-hit request in the PRIORITY line served ahead
        of the primary queue (module docstring). Same contracts as
        `_enqueue`: bounded (the line and the primary queue share the
        queue budget — a full house sheds loudly), depth-sampled,
        traced, and a raced stop() fails the future rather than
        stranding the caller."""
        if req.req_id is None:
            req.req_id = next(self._req_ids)
        self._shed_if_lines_full()
        self._prio_q.append(req)
        if not any(r is not None for r in self._slot_req):
            # wake a possibly idle-BLOCKED serve loop: the idle wait
            # blocks on the primary queue only, and without a nudge a
            # hit landing on an idle server would eat the whole idle
            # timeout — latency the cold path never pays. Only the
            # idle loop needs it (a busy loop checks the priority line
            # every iteration without blocking), and only then is the
            # sentinel consumed promptly — pushed while busy it would
            # sit in the queue eating backpressure budget. The
            # idle-check race (loop going idle right after we look)
            # costs at most one 50 ms idle timeout, the pre-fix cost.
            try:
                self._q.put_nowait(_Wake())
            except queue.Full:
                pass
        self.metrics.record_queue_depth(self._pending_depth())
        tr = self._tracer
        if tr.enabled:
            tr.instant("serve.enqueue", cat="serve",
                       track=f"req-{req.req_id}", trace_id=req.req_id)
        if not self._running:
            # _fail_future: cancel-race-safe (the base _enqueue rule)
            _fail_future(req.future, ServerClosedError(
                "server stopped during submit"))
            raise ServerClosedError("server stopped during submit")
        return req.future

    def generate(self, prompt, max_new_tokens, deadline_ms=None,
                 timeout=None):
        """Blocking convenience wrapper over submit()."""
        return self.submit(prompt, max_new_tokens,
                           deadline_ms=deadline_ms).result(timeout)

    # -- hot swap ------------------------------------------------------
    def swap(self, new_lm):
        """Route NEW requests to `new_lm`'s params while slots already
        decoding drain on the version they started with (dual-version
        dispatch — module docstring). Structure/shape mismatch raises."""
        import jax
        with self._swap_lock:
            if self._injector is not None:
                self._injector.fire("serve.swap")
            new = (new_lm.aux, new_lm.blocks)
            old_l, old_t = jax.tree_util.tree_flatten(self._versions[-1])
            new_l, new_t = jax.tree_util.tree_flatten(new)
            if old_t != new_t:
                raise ValueError("swap rejected: param tree structure "
                                 "differs from the serving model")
            for o, n in zip(old_l, new_l):
                if o.shape != n.shape or o.dtype != n.dtype:
                    raise ValueError(f"swap rejected: leaf mismatch "
                                     f"{n.shape}/{n.dtype} vs serving "
                                     f"{o.shape}/{o.dtype}")
            self._versions.append(new)
            self.metrics.count("swaps")

    def current_params(self):
        """(aux, blocks) of the NEWEST param version — the canary
        rollout's rollback snapshot (`serving/fleet.py` swaps it back
        through a duck-typed params view when the gate trips)."""
        with self._swap_lock:
            return self._versions[-1]

    # -- fleet verbs (serving/fleet.py) --------------------------------
    @property
    def paged(self):
        """Whether this server runs the block-table KV cache — the
        capability gate for migrate_in/migrate_out/drain(migrate=True)
        (the fleet router and the wire HELLO both read it; reaching
        for `_paged` from outside was the old way)."""
        return self._paged

    @property
    def alive(self):
        """True while the serve loop is running on a live thread — the
        fleet router's liveness probe. A killed or crashed loop reads
        False even before anyone calls stop()."""
        t = self._thread
        return bool(self._running and not self._killed
                    and t is not None and t.is_alive())

    def kill(self):
        """Abrupt replica death — the crash-injection verb the fleet's
        `fleet.replica` FaultInjector sever action lands on. The serve
        loop exits at the next iteration boundary and EVERY in-flight,
        parked, and queued future fails loudly with `ReplicaDeadError`;
        nothing drains and nothing persists (a real crash would not).
        Terminal and idempotent: a killed server refuses start().
        Thread-safe; callable from any thread including callbacks on
        this server's own futures."""
        self._killed = True
        self._running = False
        self._drain_on_stop = False
        try:                        # wake an idle-blocked loop
            self._q.put_nowait(_Wake())
        except queue.Full:
            pass
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(10.0)
        if t is None or not t.is_alive() \
                or t is threading.current_thread():
            # the loop is gone (or IS this thread): nobody else will
            # fail the stragglers — do it here (idempotent: resolved
            # futures are skipped)
            self._die_now()

    def _die_now(self):
        """Fail every request this server still holds with the crash
        error (kill()'s delivery half — runs on the serve thread when
        the loop notices `_killed`, or on the killer's thread once the
        loop is gone)."""
        exc = ReplicaDeadError(f"replica {self.instance!r} crashed")
        n_failed = 0
        for s, r in enumerate(self._slot_req):
            if r is not None and _fail_future(r.future, exc):
                n_failed += 1
            self._slot_req[s] = None
        if n_failed:
            self.metrics.count("failed", n_failed)
        self._fail_parked(exc)
        super()._fail_queued(exc)

    def drain(self, migrate=None, timeout=60.0):
        """Hand off EVERY admitted request in ONE verb, then stop.

        Returns ``(migrated, replayed)``:

          * ``migrated`` — list of ``(local_future, RequestArtifact)``
            for DECODE-PHASE requests (live slots plus the parked
            resume line): each local future fails with
            `RequestMigratedError`; `migrate_in(artifact)` on another
            server resumes the stream bit-identically (the durable-KV
            pin, now exercised across the router).
          * ``replayed`` — list of ``(local_future, spec)`` for queued,
            deferred, priority-parked, memory-blocked, and PREFILLING
            requests. A half-written prefill panel is NEVER an
            artifact (the preemption victim rule, enforced at this
            seam too), so these replay from their prompt instead:
            each local future fails with `RequestDrainedError` and
            ``spec`` carries ``{"prompt", "max_new", "deadline"
            (absolute monotonic or None), "klass"}`` ready to resubmit
            on a survivor — deterministic greedy decode makes the
            replayed stream equal the uninterrupted one.

        `migrate` defaults to the cache layout's capability (paged
        servers migrate, fixed-slot servers replay everything);
        migrate=True on a fixed-slot server raises. The extraction
        runs on the serve thread between iterations (the migrate_out
        machinery); on return the loop is STOPPED and the server holds
        zero requests."""
        migrate = self._paged if migrate is None else bool(migrate)
        if migrate and not self._paged:
            raise ValueError("drain(migrate=True) requires paged=True "
                             "(only a block-table KV set can leave the "
                             "arena); fixed-slot servers drain with "
                             "migrate=False — everything replays")
        if not self._running:
            raise ServerClosedError("server is not running")
        reply = cf.Future()
        self._drain_cmds.append((migrate, reply))
        try:                        # wake an idle-blocked loop
            self._q.put_nowait(_Wake())
        except queue.Full:
            pass
        migrated, replayed = reply.result(timeout)
        self.stop(drain=False, timeout=timeout)
        return migrated, replayed

    def _service_drain(self):
        """Serve-thread half of `drain()`."""
        while self._drain_cmds:
            migrate, reply = self._drain_cmds.popleft()
            try:
                out = self._drain_now(migrate)
            except BaseException as e:  # noqa: BLE001 — reply carries it
                if not reply.done():
                    reply.set_exception(e)
            else:
                if not reply.done():
                    reply.set_result(out)

    def _drain_now(self, migrate):
        migrated, replayed = [], []

        def spec_of(r):
            return {"prompt": list(r.prompt), "max_new": r.max_new,
                    "deadline": r.deadline, "klass": r.klass}

        def hand_off(r, art):
            """One request out the door: decode-phase state with rows
            in hand migrates (when asked), everything else replays."""
            if migrate and art is not None:
                if _fail_future(r.future, RequestMigratedError(
                        "request drained to another replica")):
                    migrated.append((r.future, art))
                    self.metrics.count("migrated_out")
                    self._mark_migrate_out(r)
            elif _fail_future(r.future, RequestDrainedError(
                    "request replayed on another replica (queued/"
                    "prefill-phase state is never migrated)")):
                replayed.append((r.future, spec_of(r)))

        # live slots: decode-phase slots carry extractable rows; a
        # PREFILLING slot's panel is half-written — never an artifact
        for s, r in enumerate(self._slot_req):
            if r is None:
                continue
            if r.future.done():
                self._free_slot(s)
                continue
            art = None
            if migrate and r.pf_next is None and r.generated:
                art = self._extract_artifact(s)
            hand_off(r, art)
            self._free_slot(s)
        # parked artifacts (resume line + migrate-in staging) already
        # ARE their own baton
        while self._migrate_in_q:
            self._resume_q.append(self._migrate_in_q.popleft())
        while self._resume_q:
            r = self._resume_q.popleft()
            if r.future.done():
                continue
            art, r.artifact = r.artifact, None
            hand_off(r, art)
        # queued lines: no KV state anywhere — replay specs
        for dq in (self._mem_wait, self._prio_q, self._defer_q):
            while dq:
                try:
                    r = dq.popleft()
                except IndexError:
                    break
                if not r.future.done():
                    hand_off(r, None)
        while True:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                break
            if not r.future.done():     # skips _Wake sentinels too
                hand_off(r, None)
        self._gc_versions()
        return migrated, replayed

    # -- durable KV state (serving/kvstate.py) -------------------------
    def start(self):
        if self._killed:
            raise ServerClosedError(
                "replica was killed; build a new server instead of "
                "restarting a crashed one")
        # a (re)started server has live state the next clean stop must
        # persist again
        self._prefix_saved = self._prefix_dir is None
        return super().start()

    def stop(self, drain=True, timeout=None):
        """Stop the loop (base semantics), then — when constructed with
        `prefix_cache_dir=` and the loop really exited — persist the
        prefix cache so the next server instance warm-starts. The save
        runs on the CALLER's thread against a dead loop (the serve
        thread owned the arena until it exited); a join timeout skips
        it (the loop still owns the arena) but a RETRIED stop() after
        the drain finally finishes performs it — the `_prefix_saved`
        flag, not a was-running snapshot, decides, so a slow drain
        cannot silently cost the warm start. A failed save is logged,
        not raised: stop() must tear the server down regardless."""
        super().stop(drain=drain, timeout=timeout)
        t = self._thread
        if (self._prefix_dir is not None and not self._prefix_saved
                and not self._running
                and (t is None or not t.is_alive())):
            self._prefix_saved = True
            try:
                self.save_prefix_cache(self._prefix_dir)
            except Exception:   # noqa: BLE001 — teardown must finish
                log.exception("prefix-cache save failed at stop()")

    def _version_tag(self, vidx):
        """Content fingerprint of param version `vidx` — the durable
        tag artifacts carry (computed once per version, cached)."""
        tag = self._tag_cache.get(vidx)
        if tag is None:
            with self._swap_lock:
                ver = self._versions[vidx]
            if ver is None:
                raise KVStateError(f"param version {vidx} already "
                                   f"drained; nothing to fingerprint")
            tag = self._tag_cache[vidx] = _param_fingerprint(*ver)
        return tag

    def _extract_artifact(self, slot):
        """Pull `slot`'s complete KV state to host as a
        `RequestArtifact` (serve thread only; decode phase only). One
        extract dispatch — a pure table gather, so a still-pending CoW
        spare needs no materialization (the shared partial block is
        READ; restore re-acquires shared rows through the prefix index
        or re-installs them privately) and the arena is never at risk
        from a failed call."""
        import jax.numpy as jnp
        r = self._slot_req[slot]
        pos = len(r.prompt) + len(r.generated) - 1
        tab = np.zeros((self._nb_slot,), np.int32)
        tab[:len(r.alloc.ids)] = r.alloc.ids
        with self._tracer.span("decode.extract", cat="serve",
                               track="server", trace_id=r.req_id,
                               slot=slot, rows=pos):
            panels = self._extract(self._cache, jnp.asarray(tab))
        # slice to the frontier on host: rows >= pos are dead rows
        # (rejected speculative suffixes, chunk padding) or zero-table
        # resolutions — garbage by contract, never serialized
        panels = [(np.asarray(k)[:pos].copy(), np.asarray(v)[:pos].copy())
                  for k, v in panels]
        # the Dapper baton: the artifact carries the request's trace id
        # + origin lane, so the importing server continues the SAME
        # `req-<id>` lane under the same trace id and the two saved
        # traces stitch into one timeline (obs.fleet.merge_traces).
        # Host-side metadata only — zero device work, and a consumer
        # that never traces simply ignores it.
        art = RequestArtifact(r.prompt, r.generated, r.max_new,
                              self._version_tag(r.version),
                              self._block_size, panels, klass=r.klass,
                              trace={"trace_id": r.req_id,
                                     "parent_span": f"req-{r.req_id}",
                                     "origin": self.instance})
        self.metrics.count("spill_bytes", art.nbytes)
        return art

    def _preempt_slot(self, slot):
        """PAUSE `slot`'s request: spill its KV state to host, release
        its blocks to the pool, park it on the resume line. The future
        stays pending (the caller notices nothing but latency), the
        request's remaining tokens stay in the admission backlog, and
        one re-install unit joins them — the resume line is real work
        the estimator must price."""
        r = self._slot_req[slot]
        r.artifact = self._extract_artifact(slot)
        self._free_slot(slot)           # blocks back to the pool
        r.slot = None                   # r.version KEPT: the resume
        #                                 must run under the params the
        #                                 rows were computed with
        #                                 (_gc_versions guards it)
        with self._work_lock:
            if r.work_counted:
                r.work_left += 1        # the resume-install unit
                self._work_tokens += 1
        self._resume_q.append(r)
        self.metrics.count("preempted")
        tr = self._tracer
        if tr.enabled:
            tr.instant("decode.preempt", cat="serve",
                       track=f"req-{r.req_id}", trace_id=r.req_id)

    def _gate_signature(self):
        """Everything the preempting memory gate's outcome depends on:
        pool occupancy (admit feasibility), total decode progress (the
        anti-thrash eligibility clock — a victim becomes preemptible by
        decoding), and pending depth (new work to scan). Identical
        signature => an identical rescan outcome, so the gate skips it
        (see _admit_pending). Deadline expiries and line sweeps shrink
        the depth; completions/evictions/preemptions move the pool."""
        return (self._pool.blocks_free, self._pool.blocks_in_use,
                self.metrics.count_value("tokens_out"),
                self._pending_depth())

    def _try_preempt_for(self, req):
        """Free blocks for a memory-blocked `req` by preempting ONE
        victim slot, or return False when policy/occupancy offer none.
        Victims are DECODE-PHASE slots whose class the brownout policy
        ranks strictly below the claimant's (`may_preempt`) AND that
        have decoded at least `_PREEMPT_MIN_PROGRESS` tokens since
        their last (re)start — the anti-thrash floor: without it a
        just-resumed victim is immediately eligible again, and a
        sustained interactive stream pins it in a spill/restore loop
        paying a full-panel round-trip per ~token. Among candidates
        the most-yielding class goes first and, within it, the slot
        holding the most blocks (fewest preemptions to free the
        claimant's demand). A prefilling slot is never a victim — its
        panel is half-written."""
        if not self._preempt_on or self._brownout is None:
            return False
        cands = []
        for s, r in enumerate(self._slot_req):
            if r is None or r.pf_next is not None or r.alloc is None:
                continue
            if len(r.generated) - r.progress_base \
                    < self._PREEMPT_MIN_PROGRESS:
                continue
            if not self._brownout.may_preempt(r.klass, req.klass):
                continue
            rank = self._brownout.classes.get(
                str(r.klass), self._brownout.default)[0]
            cands.append((rank, -len(r.alloc.ids), s))
        if not cands:
            return False
        self._preempt_slot(min(cands)[2])
        return True

    def _check_artifact(self, art):
        """Structural fit of an artifact against THIS server (the
        version tag is checked separately — structure says the bytes
        can land, the tag says they may)."""
        k0 = art.panels[0][0]
        hd = self._d_model // self._n_heads
        if art.block_size != self._block_size:
            raise KVStateError(
                f"artifact block_size {art.block_size} != server "
                f"block_size {self._block_size}")
        if (len(art.panels) != self._n_layers
                or k0.shape[1:] != (self._n_heads, hd)
                or k0.dtype != np.dtype(self._cache_dtype)):
            raise KVStateError(
                f"artifact panel [{k0.shape[0]}, {k0.shape[1]}, "
                f"{k0.shape[2]}] x {len(art.panels)} layers "
                f"({k0.dtype}) does not fit this server's cache "
                f"([rows, {self._n_heads}, {hd}] x {self._n_layers}, "
                f"{np.dtype(self._cache_dtype)})")
        if len(art.prompt) + art.max_new > self.max_len:
            raise KVStateError(
                f"artifact needs {len(art.prompt)} + {art.max_new} "
                f"rows; server max_len is {self.max_len}")

    def migrate_out(self, future, timeout=30.0):
        """Export a live request's KV state as a `RequestArtifact` and
        DROP it locally: the request identified by its submit()
        `future` is extracted between scheduling iterations (the serve
        thread performs the gather; this call blocks until it has), its
        blocks are released, and the local future fails with
        `RequestMigratedError` — the importing server's
        `migrate_in(artifact)` future carries the resumed stream,
        bit-identical to an uninterrupted run. Only decode-phase
        requests are migratable (a prefilling panel is half-written; a
        queued request has no KV state to move — just resubmit it)."""
        if not self._paged:
            raise ValueError("migrate_out requires paged=True")
        if not self._running:
            raise ServerClosedError("server is not running")
        reply = cf.Future()
        self._migrate_cmds.append((future, reply))
        try:        # nudge an idle-blocked loop (the priority-line
            self._q.put_nowait(_Wake())     # wake pattern)
        except queue.Full:
            pass
        return reply.result(timeout)

    def _service_migrations(self):
        """Serve-thread half of `migrate_out`: resolve each pending
        export command against the live slots (and the resume line — a
        PREEMPTED request already is its artifact)."""
        while self._migrate_cmds:
            fut, reply = self._migrate_cmds.popleft()
            try:
                art = self._migrate_out_now(fut)
            except BaseException as e:  # noqa: BLE001 — reply carries it
                reply.set_exception(e)
            else:
                reply.set_result(art)

    def _service_prefix_ops(self):
        """Serve-thread half of `prefix_export`/`prefix_adopt`: answer
        queued fleet-prefix-tier commands at the iteration boundary,
        bounded by a per-iteration BYTES budget — at least one command
        always runs (progress), but a burst of peer pulls spreads over
        iterations instead of stalling one (the tier is a goodput
        optimization; it must never cost the current batch a beat)."""
        spent = 0
        while self._prefix_cmds and (
                spent == 0 or spent < self._prefix_io_budget):
            verb, arg, max_bytes, reply = self._prefix_cmds.popleft()
            try:
                if verb == "export":
                    art = self._prefix_export_now(arg, max_bytes)
                    spent += art.nbytes if art is not None else 0
                    out = art
                else:
                    spent += arg.nbytes
                    out = self._prefix_adopt_now(arg)
            except BaseException as e:  # noqa: BLE001 — reply carries it
                if not reply.done():
                    reply.set_exception(e)
            else:
                if not reply.done():
                    reply.set_result(out)

    def _mark_migrate_out(self, r):
        """Instant marker closing the request's lane on THIS instance:
        in the merged fleet trace it reads as the spill point between
        'decode on A' and 'resume on B'."""
        tr = self._tracer
        if tr.enabled:
            tr.instant("serve.migrate_out", cat="serve",
                       track=f"req-{r.req_id}", trace_id=r.req_id,
                       origin=self.instance)

    def _migrate_out_now(self, fut):
        for s, r in enumerate(self._slot_req):
            if r is None or r.future is not fut:
                continue
            if r.pf_next is not None:
                raise KVStateError(
                    "request is still in chunked prefill; only "
                    "decode-phase requests are migratable")
            art = self._extract_artifact(s)
            _fail_future(r.future, RequestMigratedError(
                "request exported to another server"))
            self._free_slot(s)
            self._gc_versions()
            self.metrics.count("migrated_out")
            self._mark_migrate_out(r)
            return art
        for r in list(self._resume_q):
            if r.future is fut and r.artifact is not None:
                self._resume_q.remove(r)
                art = r.artifact
                r.artifact = None
                _fail_future(r.future, RequestMigratedError(
                    "request exported to another server"))
                self.metrics.count("migrated_out")
                self._mark_migrate_out(r)
                return art
        raise KVStateError(
            "request not found in a decode slot (completed, failed, "
            "still queued, or never admitted here)")

    def migrate_in(self, artifact, deadline_ms=None):
        """Adopt another server's exported `RequestArtifact`: returns a
        Future resolving to the FULL token list (prompt + every
        generated token, pre- and post-migration), exactly what the
        source's future would have resolved to uninterrupted. The
        artifact's param tag must match this server's newest version
        (`KVStateVersionError` otherwise — checked here AND re-checked
        at admission, so a hot swap racing the import still refuses
        stale rows); the request then parks on the resume line and is
        installed when blocks and a slot free up."""
        if not self._paged:
            raise ValueError("migrate_in requires paged=True")
        if not self._running:
            raise ServerClosedError("server is not running")
        art = artifact
        with self._swap_lock:
            vidx = len(self._versions) - 1
        art.require_tag(self._version_tag(vidx), what="migrated request")
        self._check_artifact(art)
        need = self._pool.blocks_needed(len(art.prompt) + art.max_new - 1)
        if need > self._n_blocks or need > self._nb_slot:
            self.metrics.count("shed_blocks")
            raise ServerOverloadedError(
                f"migrated request needs {need} KV blocks but the "
                f"server holds {min(self._n_blocks, self._nb_slot)} "
                f"(pool / per-slot table)")
        # the max_queue budget caps MIGRATED pending work too (the ONE
        # shared check — a rebalancer draining a failing replica into
        # this one hits the same backpressure bound ordinary submits do)
        self._shed_if_lines_full()
        self.metrics.count("received")
        now = time.monotonic()
        if deadline_ms is not None:
            dl = now + deadline_ms / 1e3
        else:
            dl = (now + self.default_deadline
                  if self.default_deadline is not None else None)
        req = _DecodeRequest(list(art.prompt), art.max_new, dl,
                             klass=art.klass)
        req.generated = list(art.generated)
        ctx = art.trace or {}
        if isinstance(ctx.get("trace_id"), str):
            # cross-process trace continuity: continue the ORIGIN's
            # `req-<id>` lane under the same trace id, so the merged
            # trace reads enqueue -> decode on A -> spill -> resume
            # here as ONE request timeline. Only NAMED instances mint
            # string ids ("i0-7") — those are fleet-unique by
            # construction. An UNNAMED origin's plain integer id could
            # collide with this server's own counter (both count from
            # 0), silently fusing two requests' lanes in this trace —
            # so it gets a fresh local id instead (continuity is a
            # fleet feature; name the instances to get it).
            req.req_id = ctx["trace_id"]
        else:
            req.req_id = next(self._req_ids)
        req.migrated = True
        if art.remaining <= 0:
            # fully-decoded artifact: nothing left to serve — resolve
            # immediately rather than park a no-op on the resume line
            req.future.set_result(list(art.prompt) + req.generated)
            return req.future
        req.artifact = art
        # resume-line work units: the remaining token budget plus one
        # re-install unit join the backlog NOW — the estimator prices
        # parked migrated work like any queued work
        req.work_left = art.remaining + 1
        with self._work_lock:
            self._work_tokens += req.work_left
            req.work_counted = True
        req.future.add_done_callback(
            lambda _f, r=req: self._retire_work(r))
        self._migrate_in_q.append(req)
        try:        # nudge an idle-blocked loop
            self._q.put_nowait(_Wake())
        except queue.Full:
            pass
        tr = self._tracer
        if tr.enabled:
            kw = {"trace_id": req.req_id}
            if ctx.get("origin") is not None:
                kw["migrated_from"] = ctx["origin"]
            tr.instant("serve.migrate_in", cat="serve",
                       track=f"req-{req.req_id}", **kw)
            tr.instant("serve.enqueue", cat="serve",
                       track=f"req-{req.req_id}", trace_id=req.req_id)
        if not self._running:
            _fail_future(req.future, ServerClosedError(
                "server stopped during migrate_in"))
            raise ServerClosedError("server stopped during migrate_in")
        return req.future

    def _install_panel(self, ids, panels, length, shared_len):
        """Install host panel rows through a block table: rows
        [shared_len, length) land at their table-mapped arena rows via
        the SAME donated install scatter prefill uses, at full table
        width — one compiled restore shape per server, shared by
        resume, migrate-in, and the prefix-cache restore."""
        import jax.numpy as jnp
        R = self._nb_slot * self._block_size
        tab = np.zeros((self._nb_slot,), np.int32)
        tab[:len(ids)] = ids
        dev = []
        for k, v in panels:
            kp = np.zeros((1, R) + k.shape[1:], k.dtype)
            vp = np.zeros((1, R) + v.shape[1:], v.dtype)
            kp[0, :k.shape[0]] = k
            vp[0, :v.shape[0]] = v
            dev.append((jnp.asarray(kp), jnp.asarray(vp)))
        self._cache = self._paged_install(
            self._cache, dev, jnp.asarray(tab),
            jnp.asarray(int(length), jnp.int32),
            jnp.asarray(int(shared_len), jnp.int32))

    def _count_restore_hits(self, alloc):
        """Prefix blocks this admission shares that came from a
        restored snapshot — the restart-warm-start proof counter."""
        if not self._pool.restored:
            return
        hits = sum(1 for b in alloc.ids[:alloc.n_shared]
                   if b in self._pool.restored)
        if hits:
            self.metrics.count("prefix_restore_hits", hits)

    def _admit_restored(self, req, slot, alloc, vidx):
        """Install a spilled/migrated request into `slot` from its
        artifact: block table + position + one install dispatch for
        the rows the prefix match did not already make resident.
        Shared FULL leading blocks were re-acquired by the pool
        (refcount++, never duplicated) and are skipped by the install's
        index gate; a partial-block ride materializes its CoW spare
        BEFORE the install (body comment — installing through a
        still-shared partial block would overwrite the cached owner's
        tail). The resumed stream is bit-identical: panel rows ARE the
        bits the uninterrupted run computed, and decode continues from
        the same (pos, last token) state."""
        art = req.artifact
        pos = art.pos
        if alloc.cow is not None:
            # a PARTIAL-tail ride must not be installed into: the
            # install below writes rows [resident, pos), and with the
            # shared partial block still in the table those rows would
            # land INSIDE it — overwriting the cached owner's tail that
            # other prompts still match. Swap the reserved CoW spare in
            # NOW; no device row-copy is needed (unlike the decode-path
            # CoW) because the artifact carries every row of that block
            # and the install writes them all — so the resident set
            # shrinks to the FULL shared blocks only.
            self._pool.cow(alloc)
        resident = alloc.n_shared * self._block_size
        self._btabs[slot, :] = 0
        self._btabs[slot, :len(alloc.ids)] = alloc.ids
        req.alloc = alloc
        with self._tracer.span("decode.restore", cat="serve",
                               track="server", trace_id=req.req_id,
                               slot=slot, rows=pos, shared=resident):
            self._install_panel(alloc.ids, art.panels, pos, resident)
        # only now are the request's own prompt blocks really filled —
        # commit them to the prefix index (same ordering rule as
        # prefill: a failed install must never leave garbage matchable)
        self._pool.commit(alloc)
        self._count_restore_hits(alloc)
        self._spend_work(req)           # the install unit
        self._pos = self._pos.at[slot].set(pos)
        self._tok[slot] = req.generated[-1]
        req.pf_next = None
        req.slot = slot
        req.version = vidx
        req.artifact = None             # host copy released
        req.progress_base = len(req.generated)  # anti-thrash floor
        req.t_last_tok = time.monotonic()
        self._slot_req[slot] = req
        if self._spec is not None:
            self._spec.draft.start(slot, list(req.prompt) + req.generated)
        self.metrics.count("migrated" if req.migrated else "resumed")

    def _admit_resume(self, slot):
        """Serve the RESUME LINE into `slot` (ahead of every queue —
        parked spilled work is the oldest admitted work in the house).
        Non-blocking: a resume head that cannot get its blocks leaves
        admission open for queue work (which may fit in less, or
        preempt its own victim) instead of head-of-line-blocking the
        door; it retries every iteration and has first claim on freed
        blocks. Returns True when the slot was filled."""
        while self._resume_q:
            req = self._resume_q[0]
            if req.future.done():       # cancelled / failed while parked
                self._resume_q.popleft()
                continue
            now = time.monotonic()
            if req.deadline is not None and now > req.deadline:
                self._resume_q.popleft()
                if _fail_future(req.future, DeadlineExceededError(
                        "deadline expired on the resume line")):
                    self._deadline_miss(req, now)
                continue
            art = req.artifact
            if req.version is not None:
                vidx = req.version      # in-process preemption: the
                #                         pinned version (GC-guarded)
            else:
                with self._swap_lock:   # migrated in: newest version,
                    vidx = len(self._versions) - 1      # tag re-checked
                try:
                    art.require_tag(self._version_tag(vidx),
                                    what="migrated request")
                except KVStateVersionError as e:
                    self._resume_q.popleft()
                    if _fail_future(req.future, e):
                        self.metrics.count("failed")
                    continue
            alloc = self._pool.admit(
                req.prompt, len(req.prompt) + req.max_new - 1,
                will_append=True, tag=vidx)
            if alloc is None:
                if not req.mem_blocked:
                    req.mem_blocked = True
                    self.metrics.count("blocked_on_memory")
                return False
            self._resume_q.popleft()
            try:
                self._admit_restored(req, slot, alloc, vidx)
            except BaseException as e:  # noqa: BLE001 — fail THIS req
                self._pool.release(alloc)
                _fail_future(req.future, e)
                self.metrics.count("failed")
                continue
            return True
        return False

    def save_prefix_cache(self, path=None):
        """Persist the prefix cache's resident blocks (the pool's
        LRU-cached tier) as a `PrefixCacheArtifact` under the NEWEST
        param version's tag. Only entries indexed under that version
        are saved — older versions' rows would be unreachable after a
        restart anyway (the in-process tag rule). Call on a STOPPED
        server (stop() does, when `prefix_cache_dir` is set); returns
        the artifact path, or None when there is nothing to save."""
        if not (self._paged and self._prefix_cache):
            raise ValueError("no paged prefix cache to save")
        if self._running or (self._thread is not None
                             and self._thread.is_alive()):
            raise KVStateError("save_prefix_cache needs a stopped "
                               "server (the serve thread owns the "
                               "arena while running)")
        path = path if path is not None else self._prefix_dir
        if path is None:
            raise ValueError("no path: pass one or construct with "
                             "prefix_cache_dir=")
        with self._swap_lock:
            vidx = len(self._versions) - 1
        entries = self._pool.cached_entries(tag=vidx)
        if not entries:
            # nothing saveable under the NEWEST version. A snapshot
            # already at the server's OWN prefix_cache_dir is then
            # STALE (earlier params or an earlier run) and must not
            # survive: left in place it would strand the next
            # constructor on a loud version refusal the server's own
            # lifecycle caused (e.g. hot-swapped then stopped before
            # any new-version prefix landed). Remove it so the next
            # start is a clean cold start. An EXPLICITLY passed foreign
            # path is never deleted — it may be another server's valid
            # snapshot; the loud refusal stays reserved for those.
            own = (self._prefix_dir is not None
                   and os.path.abspath(path)
                   == os.path.abspath(self._prefix_dir))
            if own and artifact_kind(path) == "prefix_cache":
                import shutil
                shutil.rmtree(path, ignore_errors=True)
            return None
        tag = self._version_tag(vidx)
        bs = self._block_size
        panels_by_bid = {}
        # batch extraction through the one compiled [NB]-table gather:
        # nb_slot blocks per dispatch, rows sliced apart on host
        import jax.numpy as jnp
        ids = [bid for bid, _ in entries]
        for at in range(0, len(ids), self._nb_slot):
            group = ids[at:at + self._nb_slot]
            tab = np.zeros((self._nb_slot,), np.int32)
            tab[:len(group)] = group
            panels = self._extract(self._cache, jnp.asarray(tab))
            panels = [(np.asarray(k), np.asarray(v)) for k, v in panels]
            for i, bid in enumerate(group):
                panels_by_bid[bid] = [
                    (k[i * bs:(i + 1) * bs].copy(),
                     v[i * bs:(i + 1) * bs].copy()) for k, v in panels]
        art = PrefixCacheArtifact(
            tag, bs, [(prefix, panels_by_bid[bid])
                      for bid, prefix in entries])
        self.metrics.count("spill_bytes", art.nbytes)
        out = art.save(path)
        log.info("saved %d prefix-cache blocks (%d bytes) under tag %s "
                 "at %s", len(entries), art.nbytes, tag, out)
        return out

    def restore_prefix_cache(self, path=None):
        """Adopt a saved `PrefixCacheArtifact` into the (fresh) pool:
        tag-checked against the newest param version FIRST —
        `KVStateVersionError` on mismatch, zero blocks adopted (the
        loud-refusal rule) — then every entry gets a block
        (parent-first), its rows installed before serving can match
        it. A pool too small for the whole snapshot adopts a prefix of
        it. Returns the number of blocks restored. Like the save twin,
        this needs a NOT-running server (the constructor calls it
        before start()): the serve thread owns the arena and the pool
        while serving, and an install racing a decode dispatch on the
        donated buffers corrupts both."""
        if not (self._paged and self._prefix_cache):
            raise ValueError("no paged prefix cache to restore into")
        if self._running or (self._thread is not None
                             and self._thread.is_alive()):
            raise KVStateError("restore_prefix_cache needs a stopped "
                               "server (the serve thread owns the "
                               "arena while running)")
        path = path if path is not None else self._prefix_dir
        if path is None:
            raise ValueError("no path: pass one or construct with "
                             "prefix_cache_dir=")
        art = PrefixCacheArtifact.load(path)
        with self._swap_lock:
            vidx = len(self._versions) - 1
        art.require_tag(self._version_tag(vidx),
                        what="prefix-cache snapshot")
        if art.entries:
            self._check_artifact_panels(art)
        adopted = []                    # (bid, panels) in adopt order
        for prefix, panels in art.entries:
            bid = self._pool.adopt((vidx, prefix))
            if bid is None:
                continue
            adopted.append((bid, panels))
        bs = self._block_size
        for at in range(0, len(adopted), self._nb_slot):
            group = adopted[at:at + self._nb_slot]
            ids = [bid for bid, _ in group]
            rows = [(np.concatenate([p[li][0] for _, p in group]),
                     np.concatenate([p[li][1] for _, p in group]))
                    for li in range(self._n_layers)]
            self._install_panel(ids, rows, len(ids) * bs, 0)
        if adopted:
            log.info("restored %d prefix-cache blocks under tag %s",
                     len(adopted), art.tag)
        return len(adopted)

    def prefix_export(self, key, max_bytes=None, timeout=30.0):
        """Export the resident prefix-cache chain covering `key` (the
        leading block-aligned prompt tokens) as a `PrefixCacheArtifact`
        under the NEWEST param version's tag — the fleet prefix tier's
        SOURCE seam (serving/wire.py OP_PREFIX_PULL): a peer missing a
        hot prefix adopts this instead of recomputing it. Valid on a
        RUNNING server: the serve thread performs the gather between
        scheduling iterations (this call blocks until it has), and
        indexed rows are immutable once committed, so live sharers are
        unaffected. Non-destructive — the blocks stay resident here.
        `max_bytes` truncates the chain parent-first (a partial chain
        is still matchable from the front). Returns None when nothing
        indexed under the newest version covers `key`."""
        if not (self._paged and self._prefix_cache):
            raise ValueError("prefix_export requires paged=True with "
                             "prefix_cache=True")
        if not self._running:
            raise ServerClosedError("server is not running")
        reply = cf.Future()
        self._prefix_cmds.append(("export", tuple(key), max_bytes,
                                  reply))
        try:        # nudge an idle-blocked loop
            self._q.put_nowait(_Wake())
        except queue.Full:
            pass
        return reply.result(timeout)

    def prefix_adopt(self, artifact, timeout=30.0):
        """Adopt a peer's exported prefix chain into the running pool —
        the fleet prefix tier's SINK seam. Tag-checked FIRST against
        the newest param version (`KVStateVersionError` on mismatch,
        zero blocks adopted, `prefix_pull_refused` counted — the caller
        degrades to cold compute); adoption never evicts resident state
        (a full pool adopts a prefix of the chain). Returns the number
        of blocks adopted; counts `prefix_pull_hits` (blocks) and
        `prefix_pull_bytes` for the fleet books."""
        if not (self._paged and self._prefix_cache):
            raise ValueError("prefix_adopt requires paged=True with "
                             "prefix_cache=True")
        if not self._running:
            raise ServerClosedError("server is not running")
        reply = cf.Future()
        self._prefix_cmds.append(("adopt", artifact, None, reply))
        try:        # nudge an idle-blocked loop
            self._q.put_nowait(_Wake())
        except queue.Full:
            pass
        return reply.result(timeout)

    def _prefix_export_now(self, key, max_bytes):
        """Serve-thread half of `prefix_export`: walk the pool's index
        chain under the newest version and pull the rows to host
        through the SAME batched [NB]-table gather the persistent
        prefix cache uses."""
        with self._swap_lock:
            vidx = len(self._versions) - 1
        chain = self._pool.indexed_chain(key, tag=vidx)
        bs = self._block_size
        if max_bytes is not None and chain:
            # fixed per-block payload: truncate parent-first BEFORE
            # extracting (no device work for bytes that won't ship)
            per_block = (2 * self._n_layers * bs * self._n_heads
                         * (self._d_model // self._n_heads)
                         * np.dtype(self._cache_dtype).itemsize)
            chain = chain[:int(max_bytes) // per_block]
        if not chain:
            return None
        import jax.numpy as jnp
        ids = [bid for bid, _ in chain]
        panels_by_bid = {}
        for at in range(0, len(ids), self._nb_slot):
            group = ids[at:at + self._nb_slot]
            tab = np.zeros((self._nb_slot,), np.int32)
            tab[:len(group)] = group
            panels = self._extract(self._cache, jnp.asarray(tab))
            panels = [(np.asarray(k), np.asarray(v))
                      for k, v in panels]
            for i, bid in enumerate(group):
                panels_by_bid[bid] = [
                    (k[i * bs:(i + 1) * bs].copy(),
                     v[i * bs:(i + 1) * bs].copy()) for k, v in panels]
        return PrefixCacheArtifact(
            self._version_tag(vidx), bs,
            [(prefix, panels_by_bid[bid]) for bid, prefix in chain])

    def _prefix_adopt_now(self, art):
        """Serve-thread half of `prefix_adopt`: `restore_prefix_cache`
        at the iteration boundary — tag check FIRST (the loud-refusal
        rule, counted), then adopt + grouped install, parent-first."""
        with self._swap_lock:
            vidx = len(self._versions) - 1
        try:
            art.require_tag(self._version_tag(vidx),
                            what="pulled prefix blocks")
        except KVStateVersionError:
            self.metrics.count("prefix_pull_refused")
            raise
        if art.entries:
            self._check_artifact_panels(art)
        adopted = []
        nbytes = 0
        for prefix, panels in art.entries:
            bid = self._pool.adopt((vidx, prefix))
            if bid is None:
                continue
            adopted.append((bid, panels))
            nbytes += sum(k.nbytes + v.nbytes for k, v in panels)
        bs = self._block_size
        for at in range(0, len(adopted), self._nb_slot):
            group = adopted[at:at + self._nb_slot]
            ids = [bid for bid, _ in group]
            rows = [(np.concatenate([p[li][0] for _, p in group]),
                     np.concatenate([p[li][1] for _, p in group]))
                    for li in range(self._n_layers)]
            self._install_panel(ids, rows, len(ids) * bs, 0)
        if adopted:
            self.metrics.count("prefix_pull_hits", len(adopted))
            self.metrics.count("prefix_pull_bytes", nbytes)
        return len(adopted)

    def _check_artifact_panels(self, art):
        """Prefix-cache twin of `_check_artifact` (no request fields)."""
        k0 = art.entries[0][1][0][0]
        hd = self._d_model // self._n_heads
        if (art.block_size != self._block_size
                or len(art.entries[0][1]) != self._n_layers
                or k0.shape[1:] != (self._n_heads, hd)
                or k0.dtype != np.dtype(self._cache_dtype)):
            raise KVStateError(
                f"prefix-cache snapshot (block_size {art.block_size}, "
                f"{len(art.entries[0][1])} layers, rows x "
                f"{k0.shape[1:]} {k0.dtype}) does not fit this server "
                f"(block_size {self._block_size}, {self._n_layers} "
                f"layers, rows x ({self._n_heads}, {hd}) "
                f"{np.dtype(self._cache_dtype)})")

    # -- scheduler internals -------------------------------------------
    def _complete(self, req, t_now):
        """Resolve one finished request: future, latency + SLO metrics,
        the request-timeline span, and the flight-recorder feed. ONE
        implementation for the three completion sites (prefill-only,
        plain iteration, speculative iteration) so SLO accounting cannot
        drift between them."""
        if not _resolve_future(req.future,
                               list(req.prompt) + req.generated):
            return
        total_ms = (t_now - req.t_submit) * 1e3
        self.metrics.record_request(
            total_ms, tokens=len(req.generated),
            deadline_met=(None if req.deadline is None
                          else t_now <= req.deadline))
        if req.deadline is not None:
            self._slo_recent.append(1 if t_now <= req.deadline else 0)
        self._admission_outcome(req, t_now, completed=True)
        tr = self._tracer
        if tr.enabled:
            t0 = int(req.t_submit * 1e9)
            tr.emit("serve.request", t0, int(total_ms * 1e6), cat="serve",
                    track=f"req-{req.req_id}", trace_id=req.req_id,
                    args={"tokens": len(req.generated)})
        if self._flight is not None:
            self._flight.observe(total_ms)

    def _reset_device_state(self):
        """Fresh slot state: the KV cache, per-slot positions/tokens, and
        host-side occupancy. Called at construction and after a decode
        dispatch fails terminally (the donated cache/pos buffers may have
        been consumed by the failed call — they cannot be trusted)."""
        import jax.numpy as jnp

        from ..models.zoo.transformer import (init_kv_cache,
                                              init_paged_kv_cache)
        if self._paged:
            from .kvpool import BlockPool
            self._cache = init_paged_kv_cache(
                self._n_layers, self._n_blocks, self._block_size,
                self._d_model, self._n_heads, self._cache_dtype)
            # the pool dies with the arena: every allocation referenced
            # rows in buffers that no longer exist
            self._pool = BlockPool(self._n_blocks, self._block_size,
                                   prefix_cache=self._prefix_cache)
            self._btabs = np.zeros((self.slots, self._nb_slot), np.int32)
        else:
            self._cache = init_kv_cache(self._n_layers, self.slots,
                                        self.max_len, self._d_model,
                                        self._n_heads, self._cache_dtype)
        self._pos = jnp.zeros((self.slots,), jnp.int32)
        # tok is HOST state uploaded per dispatch (like active/btabs):
        # chunk-prefill transitions and decode iterations both write
        # per-slot entries, and a device-side array rebuilt from one
        # iteration's live set would silently zero the slots the other
        # path just set
        self._tok = np.zeros((self.slots,), np.int32)
        self._slot_req = [None] * self.slots     # host-side occupancy
        spec = getattr(self, "_spec", None)      # unset on first call
        if spec is not None:
            for s in range(self.slots):          # idempotent stops
                spec.draft.stop(s)

    @property
    def prefill_programs(self):
        """bucket -> compiled prefill program (compile-cache pin)."""
        return dict(self._prefills)

    def _prompt_bucket(self, n):
        for b in self.prompt_buckets:
            if b >= n:
                return b
        return self.prompt_buckets[-1]

    def _admit(self, req, slot, alloc=None, version=None):
        """Prefill `req`'s prompt and install it into `slot` (paged
        mode: through `alloc`'s block table — a pure prefill dispatch
        plus the donated install scatter on success). `version` is the
        (vidx, aux, blocks) the PAGED caller
        already bound when it tagged the pool admission — prefill must
        run under exactly the params the prefix match was tagged with,
        or a swap racing the admission could share old-version rows
        into a new-version stream."""
        import jax.numpy as jnp
        tr = self._tracer
        if tr.enabled:
            # queue wait ends at ADMISSION here (a decode request's
            # "batch formation" is winning a slot)
            t0 = int(req.t_submit * 1e9)
            tr.emit("serve.queue_wait", t0, time.monotonic_ns() - t0,
                    cat="serve", track=f"req-{req.req_id}",
                    trace_id=req.req_id)
        if version is not None:
            vidx, aux, blocks = version
        else:
            with self._swap_lock:   # version index + params read atomically
                vidx = len(self._versions) - 1
                aux, blocks = self._versions[vidx]
        if self._chunk is not None and len(req.prompt) > self._chunk:
            # chunked prefill: NO monolithic prompt dispatch here — the
            # request enters its slot in the PREFILL phase and the
            # scheduler advances it C rows per iteration
            # (_chunk_iteration), interleaved with everyone's decode.
            # The CHUNK SIZING RULE: only prompts LONGER than one chunk
            # take this path — a prompt that fits in one chunk already
            # IS a chunk-sized stall, and the one-shot bucket program
            # below runs it at [1, Pb] instead of the chunk program's
            # [slots, C] (the S-wide chunk dispatch computes every slot
            # unconditionally, so routing short prompts through it
            # would multiply the fleet-dominant traffic's prefill
            # compute by the slot count for zero head-of-line benefit).
            self._admit_chunked(req, slot, alloc, vidx)
            return
        bucket = self._prompt_bucket(len(req.prompt))
        prog = self._prefills.get(bucket)
        if prog is None:
            prog = self._prefills[bucket] = self._make_prefill()
            log.info("compiled prefill program for prompt bucket %d",
                     bucket)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(req.prompt)] = req.prompt

        def dispatch():
            if self._injector is not None:
                self._injector.fire("serve.batch")
            return prog(aux, blocks, jnp.asarray(padded),
                        jnp.asarray(len(req.prompt), jnp.int32))

        with self._tracer.span("decode.prefill", cat="serve",
                               track="server", trace_id=req.req_id,
                               bucket=bucket, slot=slot):
            if self._retry is not None:
                logits, rows = self._retry.call(
                    dispatch,
                    on_retry=lambda a, e, d: self.metrics.count("retries"))
            else:
                logits, rows = dispatch()
        if self._paged:
            # `rows` are the prompt's k/v panels: scatter them to their
            # block-table rows in the DONATED install (arena aliased in
            # place — a prefill failure above leaves it untouched). Only
            # now are the prompt blocks really filled, so only now may
            # they enter the prefix index — commit() BEFORE this point
            # would let a failed prefill leave garbage blocks matchable
            tab = np.zeros((self._nb_slot,), np.int32)
            tab[:len(alloc.ids)] = alloc.ids
            self._cache = self._paged_install(
                self._cache, rows, jnp.asarray(tab),
                jnp.asarray(len(req.prompt), jnp.int32),
                jnp.asarray(alloc.shared_rows, jnp.int32))
            self._pool.commit(alloc)
            self.metrics.count("prefix_rows_total", len(req.prompt))
            if alloc.shared_rows:
                self.metrics.count("prefix_rows_hit", alloc.shared_rows)
        first = int(np.argmax(np.asarray(logits)[0]))
        req.generated.append(first)
        # TTFT closes HERE: prefill's argmax IS the first generated
        # token, whether or not the request goes on to occupy a slot
        req.t_last_tok = time.monotonic()
        self.metrics.record_ttft((req.t_last_tok - req.t_submit) * 1e3)
        self._spend_work(req, 2)    # the prefill unit + the first token
        if len(req.generated) >= req.max_new:
            # one-token request: done at prefill, never occupies a slot
            # (paged: its blocks release immediately — and a shared
            # partial block it rode needed no CoW, the zero-copy case)
            self._complete(req, time.monotonic())
            if self._paged:
                self._pool.release(alloc)
            return
        if self._paged:
            req.alloc = alloc
            self._btabs[slot, :] = 0
            self._btabs[slot, :len(alloc.ids)] = alloc.ids
        else:
            self._cache = self._install(self._cache, rows, slot)
        self._pos = self._pos.at[slot].set(len(req.prompt))
        self._tok[slot] = first
        req.slot = slot
        req.version = vidx
        self._slot_req[slot] = req
        if self._spec is not None:
            # draft stream keyed by slot: full context so far (slot reuse
            # is safe — start() resets the key, _free_slot stops it)
            self._spec.draft.start(slot, list(req.prompt) + req.generated)

    def _admit_chunked(self, req, slot, alloc, vidx):
        """Install `req` into `slot` in the PREFILL phase: block table /
        position state only, zero dispatches. Paged mode starts the
        chunk cursor past any resident shared prefix — a prefix-cache
        hit now saves the prompt COMPUTE, not just the install — but
        always re-runs at least the final prompt row, whose argmax IS
        the first generated token (write-gated below `pf_wfrom`, so
        recomputed shared rows are never re-installed and a shared
        partial block is never touched)."""
        plen = len(req.prompt)
        if self._paged:
            self._btabs[slot, :] = 0
            self._btabs[slot, :len(alloc.ids)] = alloc.ids
            req.alloc = alloc
            start = min(alloc.shared_rows, plen - 1)
            req.pf_wfrom = alloc.shared_rows
        else:
            start = 0
            req.pf_wfrom = 0
        req.pf_next = start
        # prefix hits skip leading chunks: retire their work units NOW,
        # or they would sit in the admission backlog as phantoms until
        # the future resolves, over-predicting every later request.
        # Retirement is against the units QUOTED at submit (a priority
        # hit was quoted 1 chunk already, so a surviving hit retires
        # nothing here; an evaporated hit's extra chunks clamp against
        # the request's remaining budget in _spend_work)
        chunks_left = -(-(plen - start) // self._chunk)
        self._spend_work(req, max(0, req.pf_quoted - chunks_left))
        self._pos = self._pos.at[slot].set(start)
        self._tok[slot] = 0
        req.slot = slot
        req.version = vidx
        self._slot_req[slot] = req

    def _next_request(self, wait):
        """Head of the admission line: memory-blocked requests first
        (FIFO — a small late request must not starve a big early one),
        then the prefix-hit PRIORITY line (a full-prefix hit costs one
        chunk of prefill, so it overtakes cold prompts by policy —
        counted `admitted_prefix_priority` when it actually overtakes
        queued work), then the submit queue, then the brownout-DEFERRED
        line — served only when the primary queue is empty, which is
        the policy: deferred classes yield until pressure drops. The
        blocking `wait` engages only when every line is empty (the
        idle sleep)."""
        if self._mem_wait:
            return self._mem_wait.popleft()
        # discard wake sentinels at the queue head FIRST (safe: this
        # loop is the queue's only consumer; producers only append):
        # a sentinel is a nudge, not work — left in place it would
        # read as queued work to the overtake flag below and spend the
        # anti-starvation fairness turn on a no-op
        while True:
            try:
                if not isinstance(self._q.queue[0], _Wake):
                    break
                self._q.get_nowait()
            except (IndexError, queue.Empty):
                break
        # anti-starvation bound: after _PRIO_BURST consecutive genuine
        # overtakes, the primary head takes one turn — sustained hit
        # traffic degrades cold prompts' position, never parks them
        # forever (the deferred line's reciprocal guarantee)
        if not (self._prio_streak >= self._PRIO_BURST
                and not self._q.empty()):
            r = self._pop_prio()
            if r is not None:
                if r.prio_overtook:
                    self._prio_streak += 1
                return r
        try:
            r = self._q.get_nowait()
        except queue.Empty:
            pass
        else:
            self._prio_streak = 0
            return r
        if self._defer_q:
            try:
                r = self._defer_q.popleft()
            except IndexError:          # raced a concurrent drain
                return None
            # leaving the deferred line: its work joins the backlog now
            with self._work_lock:
                if not r.work_counted:
                    self._work_tokens += r.work_left
                    r.work_counted = True
            return r
        if wait:
            # the idle sleep. Priority submits that land while the get
            # blocks push a `_Wake` sentinel through the queue (see
            # `_enqueue_priority`): the get returns it, the caller
            # discards its done future, and the next `_next_request`
            # pops the priority line first — no polling, no timeout
            # eaten by the parked request.
            try:
                return self._q.get(timeout=wait)
            except queue.Empty:
                return None
        return None

    def _pop_prio(self):
        """Pop the priority line's head (None when empty or raced),
        flagging whether it genuinely overtook queued work — the flag
        is counted only when the request actually ADMITS, so a
        deadline-expired or caller-cancelled pop never reports an
        overtake that did not happen."""
        if not self._prio_q:
            return None
        try:
            r = self._prio_q.popleft()
        except IndexError:              # raced a concurrent drain
            return None
        r.prio_overtook = not self._q.empty()
        return r

    def _admit_pending(self, timeout=0.0):
        """Fill free slots from the queue. `timeout` blocks on the FIRST
        get only — the idle loop's way of waiting for work on the queue
        itself instead of busy-polling at the 1 ms decode tick. Paged
        mode adds the MEMORY gate: a request that cannot get its blocks
        parks at the head of the line (`blocked_on_memory` counted once)
        and admission stops until completions free blocks — EXCEPT with
        `preempt=True`, where a blocked request must not wall off the
        line behind it: a claimant stuck behind a blocked lower-class
        head would never reach its preemption chance (head-of-line
        priority inversion), so the preempting gate keeps scanning —
        blocked requests collect in arrival order and re-park at the
        FRONT of the memory line (keeping first claim on freed blocks)
        while later requests get their own admit-or-preempt attempt."""
        if not self._running and not self._drain_on_stop:
            # fail-fast stop: queued requests must NOT be admitted into
            # freed slots — the loop's final drain fails them once the
            # busy slots finish. The memory-wait AND deferred lines are
            # failed HERE, not at loop exit: parked requests count as
            # _busy(), so leaving either parked would keep the loop
            # alive (and their futures unresolved) forever once the
            # slots drain.
            self._fail_parked(ServerClosedError("server stopped"))
            return
        free = [s for s in range(self.slots) if self._slot_req[s] is None]
        if self._static and len(free) < self.slots:
            return      # gang scheduling: wait for the whole batch
        if self._preempt_on and self._gate_key is not None \
                and self._gate_key == self._gate_signature():
            # the last full preempting-gate scan admitted nothing, and
            # NOTHING it depends on has changed since (pool occupancy,
            # decode progress — the anti-thrash eligibility input —
            # or pending depth): re-running the O(pending x slots)
            # scan every ~1 ms tick would tax the serve thread exactly
            # when the machine is most loaded, for an identical outcome
            return
        wait = float(timeout)
        blocked = []    # memory-blocked pops, in arrival order
        admitted = False    # anything placed into a slot this call?
        try:
            for s in free:
                if self._paged and self._admit_resume(s):
                    # spilled/migrated work re-enters ahead of every
                    # queue
                    admitted = True
                    continue
                req, alloc = None, None
                while req is None:
                    req = self._next_request(wait)
                    wait = 0.0
                    if req is None:
                        return
                    if req.future.done():   # failed by raced submit/stop
                        req = None
                    elif req.deadline is not None and \
                            time.monotonic() > req.deadline:
                        if _fail_future(req.future, DeadlineExceededError(
                                "deadline expired before prefill")):
                            self._deadline_miss(req, time.monotonic())
                        req = None
                    elif self._paged:
                        # admission gated by FREE BLOCKS, not free
                        # slots: reserve everything the request will
                        # ever write (prompt + decode rows, minus any
                        # shared prefix). The param version is bound
                        # HERE, before the prefix match: the match is
                        # tagged with it and the prefill below runs
                        # under the same params, so a swap racing this
                        # admission cannot share old-version rows into
                        # a new-version stream.
                        with self._swap_lock:
                            vidx = len(self._versions) - 1
                            aux, blocks = self._versions[vidx]
                        version = (vidx, aux, blocks)
                        alloc = self._pool.admit(
                            req.prompt, len(req.prompt) + req.max_new - 1,
                            will_append=req.max_new > 1, tag=vidx)
                        # PREEMPTION (module docstring): a claimant
                        # whose class outranks a live slot's takes that
                        # slot's blocks — victims spill to host one at
                        # a time until the claimant fits or policy runs
                        # out of victims
                        while alloc is None and \
                                self._try_preempt_for(req):
                            alloc = self._pool.admit(
                                req.prompt,
                                len(req.prompt) + req.max_new - 1,
                                will_append=req.max_new > 1, tag=vidx)
                        if alloc is None:
                            if not req.mem_blocked:
                                req.mem_blocked = True
                                self.metrics.count("blocked_on_memory")
                            blocked.append(req)
                            if not self._preempt_on:
                                return      # FIFO gate: stop admission
                            req = None      # preempting gate: scan on
                try:
                    self._admit(req, s, alloc,
                                version=version if self._paged else None)
                except BaseException as e:  # noqa: BLE001 — fail THIS
                    if alloc is not None:   # request
                        self._pool.release(alloc)
                    _fail_future(req.future, e)
                    self.metrics.count("failed")
                else:
                    admitted = True
                    if alloc is not None:
                        self._count_restore_hits(alloc)
                    if req.prio_overtook:
                        # a REAL reordered admission: the request left
                        # the priority line past queued work and
                        # prefilled
                        req.prio_overtook = False
                        self.metrics.count("admitted_prefix_priority")
        finally:
            if blocked:
                # re-park at the FRONT in arrival order: first claim on
                # freed blocks stays with the oldest blocked request
                self._mem_wait.extendleft(reversed(blocked))
            if self._preempt_on:
                # arm the rescan guard only after a FULLY blocked scan;
                # any admission/preemption changed the inputs anyway
                self._gate_key = (self._gate_signature()
                                  if blocked and not admitted else None)

    def _free_slot(self, slot):
        """Release `slot`'s host-side occupancy (and its draft stream,
        and — paged — its block-table allocation back to the pool).
        Device rows/pos are left stale on purpose: the next admission
        resets pos and decode overwrites rows before attending (the
        dead-row contract); a freed slot's stale block table is inert
        because inactive slots' writes are index-dropped."""
        req = self._slot_req[slot]
        self._slot_req[slot] = None
        if self._paged and req is not None and req.alloc is not None:
            self._pool.release(req.alloc)
            req.alloc = None
            self._btabs[slot, :] = 0
        if self._spec is not None:
            self._spec.draft.stop(slot)

    def _sweep_line(self, dq, msg, now, thrash=True):
        """THE deadline sweep for every parked FIFO line (memory gate,
        priority line, deferred line — waiting anywhere is queue wait):
        one rotation skipping already-resolved futures and failing
        expired ones through the shared `_deadline_miss` bookkeeping.
        Keepers return to the FRONT in order (deque ops are each
        atomic, so a submit appending concurrently is safe and lands
        BEHIND them — the sweep preserves line-FIFO fairness instead
        of leapfrogging old requests). `thrash=False` is the deferred
        line's flag: a class starved by brownout POLICY expiring is
        not evidence of overload, so it must not tighten admission."""
        keep = []
        for _ in range(len(dq)):
            try:
                r = dq.popleft()
            except IndexError:
                break
            if r.future.done():
                continue
            if r.deadline is not None and now > r.deadline:
                if _fail_future(r.future, DeadlineExceededError(msg)):
                    self._deadline_miss(r, now, thrash=thrash)
            else:
                keep.append(r)
        dq.extendleft(reversed(keep))

    def _evict_expired(self):
        """Mid-decode deadline enforcement: a slot whose request deadline
        has passed is evicted BETWEEN iterations — future fails with
        DeadlineExceededError, the shed is counted, and the slot frees
        THIS iteration (the following `_admit_pending` can refill it).
        Admission-time shedding (`_admit_pending`) only protects requests
        that expire in the queue; this protects the slots themselves from
        requests whose token budget outlives their latency budget."""
        now = time.monotonic()
        self._sweep_line(self._mem_wait,
                         "deadline expired while blocked on KV blocks",
                         now)
        self._sweep_line(self._prio_q,
                         "deadline expired in the priority line", now)
        self._sweep_line(self._defer_q,
                         "deadline expired while brownout-deferred",
                         now, thrash=False)
        self._sweep_line(self._resume_q,
                         "deadline expired on the resume line", now)
        evicted = False
        for s, r in enumerate(self._slot_req):
            if r is None or r.deadline is None or now <= r.deadline:
                continue
            mid_decode = r.pf_next is None
            phase = (f"mid-decode after {len(r.generated)} tokens"
                     if mid_decode else "during chunked prefill")
            if _fail_future(r.future, DeadlineExceededError(
                    f"deadline expired {phase}")):
                if mid_decode:
                    # prefill-phase evictions stay OUT of this counter:
                    # it is the decode-work-thrown-away signal the
                    # overload A/B judges the admission predictor on
                    self.metrics.count("evicted_mid_decode")
                self._deadline_miss(r, now)
            self._free_slot(s)
            evicted = True
        if evicted:
            self._gc_versions()

    def _materialize_cow(self, live):
        """Lazy copy-on-write, at exactly the FIRST divergent append: a
        live slot whose next write lands in a block it still SHARES gets
        its private copy now — the spare was reserved at admission, so
        this can never fail for lack of blocks. One small device copy
        per CoW event (per REQUEST, not per token — the per-token
        dispatch count is pinned unchanged by tests/test_paged.py)."""
        import jax.numpy as jnp
        for s, r in live:
            if r.alloc is None or r.alloc.cow is None:
                continue
            src, dst = self._pool.cow(r.alloc)
            self._btabs[s, :len(r.alloc.ids)] = r.alloc.ids
            with self._tracer.span("decode.cow", cat="serve",
                                   track="server", src=src, dst=dst):
                self._cache = self._cow_copy(
                    self._cache, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32))
            self.metrics.count("cow_copies")

    def _fail_parked(self, exc):
        """Fail everything parked OUTSIDE the submit queue: the paged
        memory-wait line, the prefix-hit priority line, and the
        brownout-deferred line (all count as _busy(), so all must
        resolve before a stop may exit — the PR 8 memory-waiter
        livelock pin, extended to every parked line)."""
        for dq in (self._mem_wait, self._prio_q, self._defer_q,
                   self._resume_q, self._migrate_in_q):
            while dq:
                try:
                    r = dq.popleft()
                except IndexError:      # raced a concurrent drain
                    break
                if _fail_future(r.future, exc):
                    self.metrics.count("failed")
        while self._migrate_cmds:
            try:
                _, reply = self._migrate_cmds.popleft()
            except IndexError:
                break
            if not reply.done():
                reply.set_exception(exc)
        while self._prefix_cmds:
            try:
                *_ignored, reply = self._prefix_cmds.popleft()
            except IndexError:
                break
            if not reply.done():
                reply.set_exception(exc)
        while self._drain_cmds:
            try:
                _, reply = self._drain_cmds.popleft()
            except IndexError:
                break
            if not reply.done():
                reply.set_exception(exc)

    def _fail_queued(self, exc):
        """Queued = the submit queue, the paged memory-wait line, AND
        the brownout-deferred line. On a KILLED server the named crash
        error wins whatever exception the exiting loop passed (the
        loop may notice `_running` dropped before it notices
        `_killed` — a queued caller must still see the crash, not a
        clean shutdown)."""
        if self._killed:
            exc = ReplicaDeadError(f"replica {self.instance!r} crashed")
        self._fail_parked(exc)
        super()._fail_queued(exc)

    def _observe_rate(self, tokens, dt, active=0):
        """Feed one scheduling iteration into the admission estimator
        and publish the live capacity estimate (no-op without admission
        control)."""
        if self._admission is None:
            return
        est = self._admission.estimator
        est.observe(tokens, dt, active)
        tps = est.tokens_per_second
        if tps is not None:
            self.metrics.record_service_rate(tps)

    def _note_iter_time(self, dt):
        """Fold one decode iteration's wall time into the EWMA the
        fused deadline clamp divides by (`_fused_window_ok`). Fed by
        the plain path per iteration and by the fused path per window
        (window wall / K) — so the estimate tracks the PER-ITERATION
        cost in both modes and the clamp's horizon arithmetic stays in
        one unit."""
        a = 0.2
        self._iter_ewma = (dt if self._iter_ewma is None
                           else a * dt + (1 - a) * self._iter_ewma)

    def _chunk_iteration(self, pf):
        """Advance every PREFILLING slot one chunk (C prompt rows): one
        chunk dispatch per live param version, active mask restricted to
        that version's prefilling slots. A slot whose FINAL chunk lands
        transitions to the decode phase: the last real row's argmax is
        the first generated token (TTFT closes here, exactly as the
        one-shot prefill's argmax closes it), the paged prompt blocks
        commit to the prefix index only now (a failed chunk must never
        leave garbage blocks matchable), and a one-token request
        completes without ever decoding. Chunk dispatches count
        `chunk_dispatches`, not `dispatches` — prefill work has never
        been in the per-token dispatch counters."""
        import jax.numpy as jnp
        C = self._chunk
        tr = self._tracer
        done_any = False
        for v in sorted({r.version for _, r in pf}):
            pf_v = [(s, r) for s, r in pf if r.version == v]
            active = np.zeros((self.slots,), bool)
            toks = np.zeros((self.slots, C), np.int32)
            nrows = np.zeros((self.slots,), np.int32)
            wfrom = np.zeros((self.slots,), np.int32)
            wto = np.zeros((self.slots,), np.int32)
            for s, r in pf_v:
                active[s] = True
                n = min(C, len(r.prompt) - r.pf_next)
                nrows[s] = n
                toks[s, :n] = r.prompt[r.pf_next:r.pf_next + n]
                wfrom[s] = r.pf_wfrom
                wto[s] = len(r.prompt)
            aux, blocks = self._versions[v]

            def dispatch():
                if self._injector is not None:
                    self._injector.fire("serve.batch")
                if self._paged:
                    return self._chunk_step(
                        aux, blocks, self._cache,
                        jnp.asarray(self._btabs), self._pos,
                        jnp.asarray(toks), jnp.asarray(nrows),
                        jnp.asarray(active), jnp.asarray(wfrom),
                        jnp.asarray(wto))
                return self._chunk_step(
                    aux, blocks, self._cache, self._pos,
                    jnp.asarray(toks), jnp.asarray(nrows),
                    jnp.asarray(active))

            # same donated-buffer retry contract as the decode step: the
            # injector site sits BEFORE the compiled call; a failure
            # inside it is terminal here (loop resets device state)
            t0 = time.monotonic_ns() if tr.enabled else None
            if self._retry is not None:
                nxt, self._cache, self._pos = self._retry.call(
                    dispatch,
                    on_retry=lambda a, e, d: self.metrics.count(
                        "retries"))
            else:
                nxt, self._cache, self._pos = dispatch()
            self.metrics.count("chunk_dispatches")
            for s, r in pf_v:
                self._spend_work(r)     # one chunk = one work unit
            nxt = np.asarray(nxt)
            if t0 is not None:
                # one prefill span per PREFILLING REQUEST over the
                # shared chunk window, on its own request lane:
                # decompose attributes the window to each prefilled
                # request's prefill_ms, while co-resident decoders still
                # see it as sched_gap — the before/after head-of-line
                # metric chunking exists to shrink
                dur = time.monotonic_ns() - t0
                for s, r in pf_v:
                    tr.emit("decode.prefill", t0, dur, cat="serve",
                            track=f"req-{r.req_id}", trace_id=r.req_id,
                            args={"chunk": int(nrows[s]), "slot": s})
            t_now = time.monotonic()
            for s, r in pf_v:
                r.pf_next += int(nrows[s])
                if r.pf_next < len(r.prompt):
                    continue
                r.pf_next = None        # final chunk: decode phase now
                if self._paged:
                    self._pool.commit(r.alloc)
                    self.metrics.count("prefix_rows_total",
                                       len(r.prompt))
                    if r.alloc.shared_rows:
                        self.metrics.count("prefix_rows_hit",
                                           r.alloc.shared_rows)
                first = int(nxt[s, int(nrows[s]) - 1])
                r.generated.append(first)
                r.t_last_tok = t_now
                self.metrics.record_ttft(
                    (r.t_last_tok - r.t_submit) * 1e3)
                self._spend_work(r)     # the first token
                if len(r.generated) >= r.max_new:
                    # one-token request: done at prefill, never decodes
                    # (_free_slot releases its blocks)
                    self._complete(r, t_now)
                    self._free_slot(s)
                    done_any = True
                    continue
                self._tok[s] = first
                if self._spec is not None:
                    self._spec.draft.start(
                        s, list(r.prompt) + r.generated)
        if done_any:
            self._gc_versions()

    def _fused_window_ok(self, dec):
        """The mid-window deadline clamp: deadline sweeps run only at
        window boundaries, so a window may start ONLY when the tightest
        live deadline has at least K iterations of headroom — otherwise
        this round falls back to the plain per-iteration path, which
        sweeps (and evicts) at exactly the K=1 cadence. Clamping the
        per-slot `steps` budget instead would NOT help: a scanned step
        still pays its compute when gated off, so a steps-clamped
        window's wall time is still ~K iterations — the boundary has to
        move, and the only shorter window program is the 1-wide step
        (the same ragged-tail argument behind nn/fused.py's single-step
        fallback). No rate estimate yet (cold EWMA) is treated as no
        headroom: conservative, and the plain rounds it forces are
        exactly what warms the estimate. Net pin: a tight-deadline
        request under fused_serve=K is evicted no later than at K=1
        plus one iteration of slack (the round in flight when its
        headroom first dropped below the horizon)."""
        tightest = None
        now = time.monotonic()
        for _, r in dec:
            if r.deadline is not None:
                rem = r.deadline - now
                tightest = rem if tightest is None else min(tightest,
                                                            rem)
        if tightest is None:
            return True
        if self._iter_ewma is None:
            return False
        return tightest >= self._fused * self._iter_ewma

    def _fused_iteration(self, dec, t_iter_start, n_occ):
        """One fused WINDOW: K decode iterations scanned into one
        device dispatch per live param version (`make_fused_decode_fn`
        / its paged twin), K tokens-per-slot read back in ONE transfer,
        then the host replays the window — budgets, completions,
        metrics — exactly as K plain iterations would have.

        Per-slot `steps` clamps the window to each request's remaining
        token budget (a finished slot freezes on device exactly like an
        inactive one, so neighbours' bits never see the difference);
        the paged path additionally clamps to the reservation's
        writable rows (`BlockPool.writable_rows`) and passes the bound
        as the in-program write gate `wto` — no window crosses an
        unreserved block. CoW materializes BEFORE the dispatch (the
        first scanned write lands at the frontier, inside a
        still-shared partial block — the 1-wide rule, once per window).
        Tokens past a slot's steps budget are garbage by contract and
        never consumed (`toks[:steps[s], s]` only), so nothing needs
        replaying: unconsumed scan work is discarded with the buffer.

        Observability stays PER-ITERATION: the admission estimator is
        fed K samples of (tokens at step i, window wall / K) — one
        K-sized sample would inflate its rolling median ~K-fold and
        shed feasible work — and `decode_iterations` advances by the
        window's realized iteration count while `dispatches` advances
        once per version, which is what makes `iterations_per_dispatch`
        the scraped amortization number."""
        import jax.numpy as jnp
        K = self._fused
        tr = self._tracer
        t_iter0 = time.monotonic_ns() if tr.enabled else None
        if self._paged:
            self._materialize_cow(dec)
            self.metrics.record_pool(self._pool.blocks_in_use,
                                     self._pool.capacity)
        steps = np.zeros((self.slots,), np.int32)
        wto = np.zeros((self.slots,), np.int32)
        for s, r in dec:
            n = min(K, r.max_new - len(r.generated))
            if self._paged:
                # frontier row is len(prompt) + len(generated) - 1 (the
                # final emitted token is never written back — the
                # blocks_needed sizing rule); never scan past the
                # reservation
                wto[s] = self._pool.writable_rows(r.alloc)
                n = min(n, int(wto[s]) - (len(r.prompt)
                                          + len(r.generated) - 1))
            steps[s] = max(n, 0)
        versions = sorted({r.version for _, r in dec})
        win_tok = {}
        for v in versions:
            active = np.zeros((self.slots,), bool)
            for s, r in dec:
                if r.version == v:
                    active[s] = True
            aux, blocks = self._versions[v]

            def dispatch():
                if self._injector is not None:
                    self._injector.fire("serve.batch")
                if self._paged:
                    return self._window_step(
                        aux, blocks, self._cache,
                        jnp.asarray(self._btabs), self._pos,
                        jnp.asarray(self._tok), jnp.asarray(active),
                        jnp.asarray(steps), jnp.asarray(wto))
                return self._window_step(
                    aux, blocks, self._cache, self._pos,
                    jnp.asarray(self._tok), jnp.asarray(active),
                    jnp.asarray(steps))

            # same donated-buffer retry contract as the plain step: the
            # injector site sits BEFORE the compiled call; a failure
            # inside it is terminal here (loop resets device state)
            with tr.span("decode.window", cat="serve", track="server",
                         version=v, k=K):
                if self._retry is not None:
                    toks, self._cache, self._pos = self._retry.call(
                        dispatch,
                        on_retry=lambda a, e, d: self.metrics.count(
                            "retries"))
                else:
                    toks, self._cache, self._pos = dispatch()
            self.metrics.count("dispatches")
            self.metrics.count("fused_windows")
            toks = np.asarray(toks)             # [K, S]
            for s, r in dec:
                if r.version == v:
                    win_tok[s] = toks[:, s]
        n_iters = int(steps.max())
        total = 0
        done_any = False
        t_now = time.monotonic()
        for s, r in dec:
            n = int(steps[s])
            if n <= 0:
                continue
            got = [int(t) for t in win_tok[s][:n]]
            r.generated.extend(got)
            self._tok[s] = got[-1]
            total += n
            self._spend_work(r, n)
            # the window lands n tokens at once: record the PER-TOKEN
            # stream rate, one sample per window per slot (the
            # speculative path's convention)
            if r.t_last_tok is not None:
                self.metrics.record_inter_token(
                    (t_now - r.t_last_tok) * 1e3 / n)
            r.t_last_tok = t_now
            if len(r.generated) >= r.max_new:
                r.generated = r.generated[:r.max_new]
                self._complete(r, t_now)
                self._free_slot(s)
                done_any = True
        self.metrics.count("tokens_out", total)
        self.metrics.count("decode_iterations", n_iters)
        if t_iter0 is not None:
            tr.emit("decode.iteration", t_iter0,
                    time.monotonic_ns() - t_iter0, cat="serve",
                    track="server",
                    args={"slot_occupancy": n_occ / self.slots,
                          "accepted": total, "fused_k": K,
                          "iterations": n_iters})
        # per-window metrics fan-out: K per-iteration samples, NOT one
        # K-sized sample — see the estimator note in the docstring
        window_dt = time.monotonic() - t_iter_start
        self._note_iter_time(window_dt / K)
        for i in range(K):
            t_i = int(np.sum(steps > i))
            self._observe_rate(t_i, window_dt / K, t_i)
        if done_any:
            self._gc_versions()
        self._after_iteration()
        return True

    def _decode_iteration(self):
        """One scheduling iteration: advance PREFILLING slots one chunk
        each (chunked mode, `_chunk_iteration`), then one decode
        dispatch per live param version over the DECODING slots, active
        mask restricted to that version's slots. Plain mode advances
        every decoding slot exactly one token; speculative mode
        (`speculate=`) advances each slot 1..K tokens per dispatch
        (per-slot positions already support ragged advance)."""
        import jax.numpy as jnp
        t_iter_start = time.monotonic()
        live = [(s, r) for s, r in enumerate(self._slot_req)
                if r is not None]
        if not live:
            return False
        pf = [(s, r) for s, r in live if r.pf_next is not None]
        if pf:
            self._chunk_iteration(pf)
        # transitions/completions in the chunk pass may have changed the
        # slot map: recompute the DECODING set
        dec = [(s, r) for s, r in enumerate(self._slot_req)
               if r is not None and r.pf_next is None]
        # occupancy/live_streams recorded ONCE per scheduling iteration,
        # from the post-chunk-pass occupied count (prefilling slots
        # included, freed one-token slots excluded) — identical
        # semantics in plain and speculative modes
        n_occ = sum(1 for r in self._slot_req if r is not None)
        if n_occ:
            self.metrics.record_occupancy(n_occ, self.slots)
            self.metrics.record_live_streams(n_occ)
        if not dec:
            # pure prefill pass: zero tokens — the estimator accumulates
            # this pass's wall time into the next token-bearing sample
            # (prefill cost must dilute the measured rate, not vanish)
            self._observe_rate(0, time.monotonic() - t_iter_start, 0)
            self._after_iteration()
            return True
        if self._spec is not None:
            return self._spec_iteration(dec, t_iter_start)
        if self._fused > 1 and self._fused_window_ok(dec):
            return self._fused_iteration(dec, t_iter_start, n_occ)
        tr = self._tracer
        t_iter0 = time.monotonic_ns() if tr.enabled else None
        if self._paged:
            self._materialize_cow(dec)
            self.metrics.record_pool(self._pool.blocks_in_use,
                                     self._pool.capacity)
        versions = sorted({r.version for _, r in dec})
        new_tok = {}
        for v in versions:
            active = np.zeros((self.slots,), bool)
            for s, r in dec:
                if r.version == v:
                    active[s] = True
            aux, blocks = self._versions[v]

            def dispatch():
                if self._injector is not None:
                    self._injector.fire("serve.batch")
                if self._paged:
                    return self._step(aux, blocks, self._cache,
                                      jnp.asarray(self._btabs),
                                      self._pos,
                                      jnp.asarray(self._tok),
                                      jnp.asarray(active))
                return self._step(aux, blocks, self._cache, self._pos,
                                  jnp.asarray(self._tok),
                                  jnp.asarray(active))

            # NOTE on retry composition: cache/pos are donated, so a
            # failure INSIDE the compiled call is not retryable at this
            # level (the buffers are gone) — the injector site sits before
            # the call, which is exactly the transient class (a fault
            # before dispatch) retries exist for.
            with tr.span("decode.dispatch", cat="serve", track="server",
                         version=v):
                if self._retry is not None:
                    nxt, _, self._cache, self._pos = self._retry.call(
                        dispatch,
                        on_retry=lambda a, e, d: self.metrics.count(
                            "retries"))
                else:
                    nxt, _, self._cache, self._pos = dispatch()
            self.metrics.count("dispatches")
            nxt = np.asarray(nxt)
            for s, r in dec:
                if r.version == v:
                    new_tok[s] = int(nxt[s])
        self.metrics.count("tokens_out", len(dec))
        self.metrics.count("decode_iterations")
        for s, r in dec:
            self._spend_work(r)
        done_any = False
        t_now = time.monotonic()
        for s, r in dec:
            self._tok[s] = new_tok[s]
            r.generated.append(new_tok[s])
            # one inter-token sample per decode iteration per slot
            if r.t_last_tok is not None:
                self.metrics.record_inter_token(
                    (t_now - r.t_last_tok) * 1e3)
            r.t_last_tok = t_now
            if len(r.generated) >= r.max_new:
                # the final token needs no decode step (generate() makes
                # the same point): resolve and free the slot
                r.generated = r.generated[:r.max_new]
                self._complete(r, t_now)
                self._free_slot(s)
                done_any = True
        if t_iter0 is not None:
            # one span per scheduling iteration, tagged with the two
            # numbers head-of-line surgery needs: how full the machine
            # was and how many tokens the iteration produced
            tr.emit("decode.iteration", t_iter0,
                    time.monotonic_ns() - t_iter0, cat="serve",
                    track="server",
                    args={"slot_occupancy": n_occ / self.slots,
                          "accepted": len(dec)})
        dt_iter = time.monotonic() - t_iter_start
        self._note_iter_time(dt_iter)
        self._observe_rate(len(dec), dt_iter, len(dec))
        if done_any:
            self._gc_versions()
        self._after_iteration()
        return True

    def _spec_iteration(self, live, t_iter_start=None):
        """One SPECULATIVE iteration: per live version, gather each
        slot's draft (K-1 tokens, zero-padded — padding costs acceptance,
        never correctness), run ONE K-wide verify dispatch, and advance
        each slot by its accepted count (matched prefix + bonus). The
        emitted stream is the verify program's own greedy argmax chain —
        acceptance only decides the dispatch count; bit-identity with
        the plain step's stream is pinned by test (cross-width argmax
        parity, speculate.py). Draft and verify are both evaluated
        under the slot's pinned param version (`r.version`); the draft
        source itself needs no pinning because a mismatched draft cannot
        alter accepted tokens. `live` is the DECODING slot set (chunked
        mode runs prefilling slots through `_chunk_iteration` first).

        Paged mode swaps the program for the block-table verify twin
        (`make_paged_verify_fn`): the block table and a per-slot write
        bound `wto` (the reservation's row capacity —
        `BlockPool.writable_rows`) ride in as host arguments like
        tok/active, a round that crosses a block boundary writes into
        blocks the reserve-at-admit table already holds (no allocation
        here), and any pending CoW materializes FIRST — the K-wide
        write starts at the frontier, inside a still-shared partial
        block (the 1-wide CoW rule's K-wide twin)."""
        import jax.numpy as jnp
        if t_iter_start is None:
            t_iter_start = time.monotonic()
        tr = self._tracer
        t_iter0 = time.monotonic_ns() if tr.enabled else None
        n_accepted = 0
        # occupancy/live_streams were recorded by _decode_iteration
        # (one record per scheduling iteration, both modes)
        K = self._spec.k
        draft = self._spec.draft
        d0 = getattr(draft, "dispatch_count", 0)   # ModelDraft device cost
        if self._paged:
            self._materialize_cow(live)
            self.metrics.record_pool(self._pool.blocks_in_use,
                                     self._pool.capacity)
        versions = sorted({r.version for _, r in live})
        done_any = False
        for v in versions:
            live_v = [(s, r) for s, r in live if r.version == v]
            active = np.zeros((self.slots,), bool)
            toks = np.zeros((self.slots, K), np.int32)
            wto = np.zeros((self.slots,), np.int32)
            n_dr = {}
            for s, r in live_v:
                active[s] = True
                if self._paged:
                    wto[s] = self._pool.writable_rows(r.alloc)
                # never request drafts past the request's remaining token
                # budget: a ModelDraft would pay real dispatches for
                # tokens that can never be accepted, and the acceptance
                # reservoir would log them as misses
                n_want = r.max_new - len(r.generated)
                dr = list(draft.propose(
                    s, min(K - 1, n_want - 1)))[:K - 1]
                n_dr[s] = len(dr)
                toks[s, :1 + len(dr)] = [r.generated[-1]] + dr
            aux, blocks = self._versions[v]

            def dispatch():
                if self._injector is not None:
                    self._injector.fire("serve.batch")
                if self._paged:
                    return self._verify(
                        aux, blocks, self._cache,
                        jnp.asarray(self._btabs), self._pos,
                        jnp.asarray(toks), jnp.asarray(active),
                        jnp.asarray(wto))
                return self._verify(aux, blocks, self._cache, self._pos,
                                    jnp.asarray(toks), jnp.asarray(active))

            # same donated-buffer retry contract as the plain step: the
            # injector site sits BEFORE the compiled call (the transient
            # class); a failure inside it is terminal here
            with tr.span("decode.verify", cat="serve", track="server",
                         version=v, k=K):
                if self._retry is not None:
                    nxt, n_acc, _, self._cache, self._pos = \
                        self._retry.call(
                            dispatch,
                            on_retry=lambda a, e, d: self.metrics.count(
                                "retries"))
                else:
                    nxt, n_acc, _, self._cache, self._pos = dispatch()
            self.metrics.count("dispatches")
            nxt = np.asarray(nxt)
            n_acc = np.asarray(n_acc)
            t_now = time.monotonic()
            for s, r in live_v:
                want = r.max_new - len(r.generated)
                take = min(int(n_acc[s]) + 1, want)
                acc = [int(t) for t in nxt[s, :take]]
                r.generated.extend(acc)
                # a speculative iteration lands `take` tokens at once:
                # record the PER-TOKEN stream rate (delta / take), one
                # sample per iteration per slot like the plain step
                if take and r.t_last_tok is not None:
                    self.metrics.record_inter_token(
                        (t_now - r.t_last_tok) * 1e3 / take)
                r.t_last_tok = t_now
                n_accepted += take
                self.metrics.count("tokens_out", take)
                self._spend_work(r, take)
                # drafted = REAL draft tokens (zero-padding is not a
                # draft); matched likewise capped — a pad that happens to
                # equal the argmax is accepted (it IS the argmax) but
                # credits luck, not the draft
                self.metrics.record_speculation(
                    take, n_dr[s], min(int(n_acc[s]), take, n_dr[s]))
                if len(r.generated) >= r.max_new:
                    self._complete(r, t_now)
                    self._free_slot(s)
                    done_any = True
                else:
                    draft.observe(s, acc)
        dd = getattr(draft, "dispatch_count", 0) - d0
        if dd:
            # a ModelDraft pays real device dispatches for its proposals;
            # count them so dispatch amortization stays honest (NGramDraft
            # never moves this — host-only)
            self.metrics.count("draft_dispatches", dd)
        if t_iter0 is not None:
            tr.emit("decode.iteration", t_iter0,
                    time.monotonic_ns() - t_iter0, cat="serve",
                    track="server",
                    args={"slot_occupancy": len(live) / self.slots,
                          "accepted": n_accepted,
                          "draft_dispatches": dd})
        self.metrics.count("decode_iterations")
        self._observe_rate(n_accepted, time.monotonic() - t_iter_start,
                           len(live))
        if done_any:
            self._gc_versions()
        self._after_iteration()
        return True

    def _after_iteration(self):
        self.metrics.count("batches")       # decode iterations
        if self._reporter is not None and \
                self.metrics.count_value("batches") % self._report_every \
                == 0:
            self._reporter.report(self.metrics.snapshot())

    def _gc_versions(self):
        """Drop drained old param versions (keep indices stable: only a
        fully-drained PREFIX below the newest can be released)."""
        with self._swap_lock:
            in_use = {r.version for r in self._slot_req if r is not None}
            # a PREEMPTED request's version is pinned while it parks:
            # its artifact's rows are only resumable under exactly
            # those params (migrated-in entries carry version None and
            # bind the newest at admission)
            for r in self._resume_q:
                if r.version is not None:
                    in_use.add(r.version)
            newest = len(self._versions) - 1
            for v in range(newest):
                if v not in in_use and self._versions[v] is not None:
                    self._versions[v] = None

    def _busy(self):
        return any(r is not None for r in self._slot_req) \
            or bool(self._mem_wait) or bool(self._prio_q) \
            or bool(self._defer_q) or bool(self._resume_q) \
            or bool(self._migrate_in_q) or bool(self._migrate_cmds) \
            or bool(self._prefix_cmds) or bool(self._drain_cmds)

    def _loop_once(self):
        if self._killed:
            # crash-injection verb (kill()): fail everything loudly and
            # let the loop exit — no drain, no persistence
            self._die_now()
            return
        self._service_drain()
        if self._paged:
            # drain the client-side migrate-in staging into the serve-
            # thread-only resume line, then answer export commands —
            # both BEFORE the deadline sweep so a just-arrived artifact
            # is swept/served this iteration
            while self._migrate_in_q:
                self._resume_q.append(self._migrate_in_q.popleft())
            self._service_migrations()
            self._service_prefix_ops()
        # evict deadline-expired slots FIRST so the admit below can refill
        # them in the same iteration
        self._evict_expired()
        # idle (no slot occupied): block on the queue up to 50 ms instead
        # of spinning at the decode tick; busy: drain the queue non-blocking
        self._admit_pending(timeout=0.0 if self._busy() else 0.05)
        try:
            busy = self._decode_iteration()
        except BaseException as e:  # noqa: BLE001 — fail slots, survive
            # a decode dispatch failed terminally (non-retryable, or
            # retries exhausted). The donated cache/pos buffers cannot be
            # trusted after a failed call, so every occupied request
            # fails LOUDLY and the slot state resets — the server keeps
            # serving instead of stranding all future requests on a dead
            # thread.
            n_failed = 0
            for r in self._slot_req:
                if r is not None and _fail_future(r.future, e):
                    n_failed += 1
            if n_failed:
                self.metrics.count("failed", n_failed)
            self._reset_device_state()
            self._gc_versions()
            return
        if not busy:
            # idle: still GC param versions (repeated swaps on an idle
            # server must not accumulate dead params); the next loop's
            # blocking admit is the idle wait, no sleep needed
            self._gc_versions()
