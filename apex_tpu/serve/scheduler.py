"""Continuous-batching scheduler: admit / evict / preempt at step
granularity, with capacity accounted in pool pages.

Pure host-side state machine — no jax imports, no device work — so the
policy is unit-testable without a model and the engine's jitted steps
stay pure. The policy is the vLLM recompute-preemption shape:

- **FCFS admission**: waiting sequences admit in arrival order, when a
  batch slot is free AND the allocator can cover the sequence's current
  tokens plus the next decode write. Head-of-line blocking is
  deliberate (no starvation).
- **Chunked prefill** (``prefill_chunk``): a prompt goes in
  ``prefill_chunk`` tokens a round, at most ONE chunk a round, beside that
  round's decode batch. The sequence at the head of the queue is admitted
  (slot and pages for its whole prompt) with its first chunk and stays at
  the head, ``PREFILLING``, until the round of its last chunk, when it
  joins the running set; the next one is admitted the round after. Without
  ``prefill_chunk`` a prompt is one prefill and every waiting sequence that
  fits is admitted in the same round, as ever.
- **On-demand growth**: a running sequence takes one page exactly when
  its next decode position crosses a page boundary.
- **Evict-on-exhaustion**: when growth cannot be served, the LATEST-
  arrived running sequence is preempted — its pages are freed and the
  sequence returns to the head of the waiting queue *keeping its
  generated tokens*. Re-admission recomputes the cache (prefill of the
  prompt + decode-replay of the generated tokens through the SAME
  compiled programs), which is why preempt/resume is bit-exact — see
  ``docs/serve.md``.

Page 0 of the pool is the null page and is never allocated (the
``cache`` module's masked-write convention).

Telemetry: every scheduling transition is traced through
:mod:`apex_tpu.monitor.spans` and the host hooks — a ``serve/queue_wait``
span opens when a sequence enters (or re-enters, after preemption) the
waiting queue and closes at admission, preemptions emit a
``serve/preempt`` annotation + counter, and the measured queue wait
feeds the ``serve/queue_wait_ms`` streaming histogram. All of it is
host-clock-only and detached-free (``apex_tpu.monitor`` is zero-dep —
this module still imports no jax, and with no recorder attached every
hook is one global read).
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

from apex_tpu.monitor import hooks as _mhooks
from apex_tpu.monitor import spans as _mspans

WAITING = "waiting"
PREFILLING = "prefilling"      # admitted; its prompt goes in a chunk a round
RUNNING = "running"
FINISHED = "finished"


@dataclasses.dataclass
class Sequence:
    """One request's full lifecycle state."""

    seq_id: int
    prompt: List[int]
    max_new_tokens: int
    arrival: int = 0
    state: str = WAITING
    tokens: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None         # engine batch slot while RUNNING
    num_cached: int = 0                # positions the cache holds (a
    #                                    prompt's chunks advance it)
    draft_cached: int = 0              # positions in the DRAFT pool
    n_preemptions: int = 0
    # -- telemetry (host-only; None/0 when monitoring is detached) ----
    span: Optional[int] = None         # serve/request span id
    queue_span: Optional[int] = None   # open serve/queue_wait span id
    arrival_t: float = 0.0             # perf_counter at first add()
    queued_t: float = 0.0              # perf_counter at last (re)queue
    queue_wait_s: float = 0.0          # total time spent WAITING
    ttft_ms: Optional[float] = None    # arrival -> first generated token
    # tokens the engine has dispatched whose VALUES are not on the host
    # yet (it runs one round ahead of the tokens it has read): scheduling
    # goes by ``num_dispatched``, everything a caller counts by ``tokens``
    in_flight: int = 0

    def __post_init__(self):
        if not self.prompt:
            raise ValueError("empty prompt")
        if not self.tokens:
            self.tokens = list(self.prompt)

    @property
    def num_tokens(self) -> int:
        return len(self.tokens)

    @property
    def num_generated(self) -> int:
        return len(self.tokens) - len(self.prompt)

    @property
    def done(self) -> bool:
        return self.num_generated >= self.max_new_tokens

    @property
    def num_dispatched(self) -> int:
        """Positions that exist on the device: read tokens + in flight."""
        return len(self.tokens) + self.in_flight

    @property
    def sent(self) -> bool:
        """The request's last round has gone out: it takes no further
        row, and ends when the tokens in flight are read."""
        return self.num_generated + self.in_flight >= self.max_new_tokens


class PageAllocator:
    """Free-list over pages ``1..num_pages-1`` (0 is the null page)."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))  # pop() -> low ids

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if not (0 < p < self.num_pages):
                raise ValueError(f"freeing invalid page {p}")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
        self._free.extend(pages)


@dataclasses.dataclass
class StepPlan:
    """What the engine should run this step: prefills first (each is a
    full-prompt pass, or the next chunk of one from ``seq.num_cached``, +
    any decode-replay of generated tokens), then one batched decode over
    every running sequence."""

    prefill: List[Sequence] = dataclasses.field(default_factory=list)
    decode: List[Sequence] = dataclasses.field(default_factory=list)
    preempted: List[Sequence] = dataclasses.field(default_factory=list)


class Scheduler:
    def __init__(self, *, num_pages: int, page_size: int, max_batch: int,
                 lookahead: int = 0, prefill_chunk: Optional[int] = None):
        self.allocator = PageAllocator(num_pages)
        self.page_size = page_size
        self.max_batch = max_batch
        self.prefill_chunk = prefill_chunk
        # speculative decoding writes up to ``lookahead`` positions past
        # the next decode position in one round (the verify window), so
        # growth/admission must cover them up front — a preemption
        # mid-window would otherwise strand a half-written round
        self.lookahead = int(lookahead)
        self.waiting: List[Sequence] = []
        self.running: List[Sequence] = []
        self._arrival = 0

    # -- bookkeeping -------------------------------------------------

    def add(self, seq: Sequence) -> None:
        seq.arrival = self._arrival
        self._arrival += 1
        seq.state = WAITING
        now = time.perf_counter()
        seq.arrival_t = seq.arrival_t or now
        seq.queued_t = now
        seq.queue_span = _mspans.start(
            "serve/queue_wait", parent=seq.span, seq_id=seq.seq_id)
        _mhooks.counter("serve/requests_queued")
        self.waiting.append(seq)

    def finish(self, seq: Sequence) -> None:
        seq.state = FINISHED
        self.running.remove(seq)
        self.allocator.free(seq.pages)
        seq.pages = []
        seq.slot = None
        seq.num_cached = 0
        seq.draft_cached = 0
        _mhooks.counter("serve/requests_finished")

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def _pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def _preempt(self, seq: Sequence) -> None:
        # a sequence caught between two chunks is still in the queue
        (self.waiting if seq.state == PREFILLING
         else self.running).remove(seq)
        seq.state = WAITING
        seq.n_preemptions += 1
        freed = len(seq.pages)
        self.allocator.free(seq.pages)
        seq.pages = []
        seq.slot = None
        seq.num_cached = 0
        # the draft pool reuses the target's page ids, so eviction
        # invalidates the draft cache too — re-admission re-ingests
        seq.draft_cached = 0
        # evict/re-queue transition on the request trace: annotation on
        # the request span + a fresh queue-wait span (re-admission will
        # close it and add the second wait to the request's total)
        _mhooks.counter("serve/preemptions")
        _mspans.annotate("serve/preempt", span=seq.span,
                         seq_id=seq.seq_id,
                         n_preemptions=seq.n_preemptions,
                         freed_pages=freed,
                         tokens_kept=seq.num_dispatched)
        seq.queued_t = time.perf_counter()
        seq.queue_span = _mspans.start(
            "serve/queue_wait", parent=seq.span, seq_id=seq.seq_id,
            resumed=True)
        # back of the ARRIVAL order, front of readmission among later
        # arrivals: waiting stays sorted by arrival
        self.waiting.append(seq)
        self.waiting.sort(key=lambda s: s.arrival)

    # -- the per-step policy -----------------------------------------

    def schedule(self) -> StepPlan:
        plan = StepPlan()

        # 1. growth: every running sequence must hold pages for its
        # next decode write (position num_dispatched-1) plus the
        # speculative lookahead window. Earliest arrivals
        # are served first; exhaustion preempts the LATEST-arrived
        # running sequence — possibly the grower itself, when it is the
        # latest. A ``sent`` sequence only waits for its tokens to be
        # read: it neither grows nor decodes, and is no victim (it
        # frees its pages at that read).
        for seq in sorted(self.running, key=lambda s: s.arrival):
            if seq.state != RUNNING or seq.sent:
                continue                    # preempted earlier this pass
            grown = True
            want = self._pages_needed(seq.num_dispatched + self.lookahead)
            while want > len(seq.pages):
                need = want - len(seq.pages)
                got = self.allocator.alloc(need)
                if got is not None:
                    seq.pages.extend(got)
                    break
                victim = max((s for s in self.running + self.waiting[:1]
                              if s.state != WAITING and not s.sent),
                             key=lambda s: s.arrival)
                self._preempt(victim)
                plan.preempted.append(victim)
                if victim is seq:
                    grown = False
                    break
            if grown and seq.state == RUNNING:
                plan.decode.append(seq)

        # 2. FCFS admission into free slots/pages. A resumed sequence
        # needs pages for ALL its tokens (prompt + generated: the
        # recompute) plus the next write.
        while self.waiting and len(self.running) < self.max_batch:
            seq = self.waiting[0]
            if seq.state != PREFILLING and not self._admit(seq):
                break                       # head-of-line: no skip-ahead
            plan.prefill.append(seq)
            chunk = self.prefill_chunk
            if chunk and seq.num_cached + chunk < len(seq.prompt):
                # not its last chunk: it keeps the head of the queue, and
                # nobody else is prefilled this round
                seq.state = PREFILLING
                break
            self.waiting.pop(0)
            seq.state = RUNNING
            self.running.append(seq)
            if chunk:
                break                       # one chunk a round
        return plan

    def _admit(self, seq: Sequence) -> bool:
        """Pages for all of a waiting sequence's tokens, or nothing."""
        need = self._pages_needed(seq.num_dispatched + 1 + self.lookahead)
        if need > self.allocator.num_pages - 1:
            raise RuntimeError(
                f"sequence {seq.seq_id} needs {need} pages; the pool "
                f"has {self.allocator.num_pages - 1} usable — it can "
                f"never be admitted (grow num_pages or page_size)")
        got = self.allocator.alloc(need)
        if got is None:
            return False
        seq.pages = got
        # admission closes the open queue-wait span; the measured
        # wait (wall clock, span or not) feeds the streaming
        # histogram and the request's running total
        wait_s = time.perf_counter() - seq.queued_t \
            if seq.queued_t else 0.0
        seq.queue_wait_s += wait_s
        _mspans.end(seq.queue_span, seq_id=seq.seq_id)
        seq.queue_span = None
        _mhooks.observe("serve/queue_wait_ms", 1e3 * wait_s)
        _mhooks.counter("serve/admissions")
        return True
