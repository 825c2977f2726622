"""Continuous batching for :class:`~repro.runtime.server.LMServer`.

Wave mode pre-partitions requests into fixed batches and fork-joins them —
fine for offline bulk, wrong for traffic: a request arriving just after a
wave sealed waits a full wave, and every member of a wave decodes as far
as its longest neighbour.  The :class:`ContinuousBatcher` replaces the
fixed partition with slot-based admission, at one of two granularities:

* **batch-level** (the PR 3/4 path, any backend): up to ``slots`` decode
  batches in flight; a batch seals on ``max_batch``/``max_wait_ms``,
  grouped by decode-length bucket, and dispatches through the same
  ``submit_wave`` / ``unpack_wave`` core as wave mode.  Admission happens
  *between* batches — each batch re-runs prefill and rebuilds its KV
  cache from scratch.
* **iteration-level** (ISSUE 5, backends with ``resident_state``): one
  :class:`~repro.runtime.engine.EngineClient` per slot owns a worker-
  resident cache arena of ``max_batch`` rows.  Arriving prompts are
  prefilled into free rows (or served from the worker's prompt-prefix
  cache and skipped entirely), decode advances every live row in
  ``quantum``-step chunks, rows evict the moment they hit their
  ``max_new`` (no batch-tail wait), and freed rows are refilled at the
  next chunk boundary.  The KV cache never crosses the wire; each chunk
  ships a handle and returns token ids.  TTFT is the prefill round-trip,
  not the batch tail.

Which one runs is automatic (``iteration_level=None``): iteration-level
when the backend keeps worker-resident state (``inline``/``threads``
process-local; ``processes``/``http``/``http-aio`` via affinity-pinned
workers and CONTROL state leases) *and* the model family supports slot
arenas; the batch-level path otherwise (e.g. ``sim-aws``, encdec).
Requests that cannot fit an arena (prompt above ``prompt_cap``) fall back
to a solo wave per request.  Both granularities are pad-masked end to
end, so a request decodes to the same greedy tokens whichever scheduler
ran it and whatever ragged company it kept.
"""
from __future__ import annotations

import asyncio
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Sequence

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..runtime.sandbox import WorkerCrash
from ..runtime.server import Completion, LMServer, Request, decode_bucket
from .aio import await_invocation

# Failover bound: how many times one row may be replayed before its error
# surfaces.  Replay re-prefills prompt + generated-so-far after worker or
# lease loss (ISSUE 10); a row that keeps landing on dying workers must
# eventually fail rather than orbit the fleet forever.
MAX_ROW_REPLAYS = 3

# serving latency histograms (process-default registry), queryable through
# Session.stats()["metrics"] and merged fleet-wide with the worker-side
# registries.  TTFT/TPOT are stamped once here (see
# _LiveRow.token_times_ms) — serve_bench consumes these stamps instead of
# re-deriving.  TTFT and the queue wait start at ContinuousBatcher.submit.
_M_TTFT = obs_metrics.REGISTRY.histogram(
    "serve_ttft_ms", "time to first token, client-observed (ms)")
_M_TPOT = obs_metrics.REGISTRY.histogram(
    "serve_tpot_ms", "mean inter-token time per request (ms)")
_M_WAIT = obs_metrics.REGISTRY.histogram(
    "serve_queue_wait_ms", "submit to admission into an arena row (ms)")


@dataclass
class BatcherStats:
    """Scheduler-side accounting (client latency is measured by callers)."""
    mode: str = "batch"              # "batch" | "iteration"
    requests: int = 0
    batches: int = 0                 # batch-level: dispatched batches
    occupancy_sum: int = 0           # sum of batch sizes / chunk occupancy
    decode_steps: int = 0            # batch: bucket lengths; iter: real steps
    sealed_full: int = 0             # batches sealed by max_batch
    sealed_wait: int = 0             # batches sealed by max_wait
    bucket_histogram: dict = field(default_factory=dict)
    # iteration-level accounting
    admission_groups: int = 0        # prefill round-trips
    decode_chunks: int = 0           # decode round-trips
    prefix_hits: int = 0             # rows whose prefill was skipped
    prefix_misses: int = 0
    prefill_tokens: int = 0          # prompt tokens run through prefill
    prefix_tokens: int = 0           # prompt tokens served from the index
    wave_fallbacks: int = 0          # requests too big for the arena
    state_resets: int = 0            # arenas rebuilt after state loss
    recovered_rows: int = 0          # live rows replayed instead of failed
    migrated_rows: int = 0           # prefill→decode row hand-offs (fleet)
    # paged-arena occupancy peaks (ISSUE 7), folded from worker replies
    live_tokens_peak: int = 0
    allocated_blocks_peak: int = 0
    shared_blocks_peak: int = 0

    @property
    def mean_batch(self) -> float:
        n = self.batches or self.decode_chunks
        return self.occupancy_sum / n if n else 0.0

    def summary(self) -> dict:
        out = {"mode": self.mode, "requests": self.requests,
               "batches": self.batches,
               "mean_batch": round(self.mean_batch, 2),
               "decode_steps": self.decode_steps,
               "sealed_full": self.sealed_full,
               "sealed_wait": self.sealed_wait,
               "buckets": dict(sorted(self.bucket_histogram.items()))}
        if self.mode == "iteration":
            out.update({"admission_groups": self.admission_groups,
                        "decode_chunks": self.decode_chunks,
                        "prefix_hits": self.prefix_hits,
                        "prefix_misses": self.prefix_misses,
                        "prefill_tokens": self.prefill_tokens,
                        "prefix_tokens": self.prefix_tokens,
                        "wave_fallbacks": self.wave_fallbacks,
                        "state_resets": self.state_resets,
                        "recovered_rows": self.recovered_rows,
                        "migrated_rows": self.migrated_rows,
                        "live_tokens_peak": self.live_tokens_peak,
                        "allocated_blocks_peak": self.allocated_blocks_peak,
                        "shared_blocks_peak": self.shared_blocks_peak})
        return out


@dataclass
class _LiveRow:
    """One occupied arena slot (iteration-level scheduler bookkeeping)."""
    request: Request
    fut: asyncio.Future
    t_arrival: float                 # admission (the loop's clock)
    tokens: list = field(default_factory=list)
    ttft_ms: float = 0.0
    cost_gb_s: float = 0.0
    # one stamp per token, ms since t_arrival, appended at the chunk reply
    # that delivered it (chunk-mates share a stamp); [0] == ttft_ms
    token_times_ms: list = field(default_factory=list)
    # failover bookkeeping (ISSUE 10): how many times this row has been
    # replayed onto a fresh arena, and whether it survived at least one
    recovered: bool = False
    replays: int = 0
    t_submit: float | None = None    # ContinuousBatcher.submit, if known

    @property
    def remaining(self) -> int:
        return self.request.max_new - len(self.tokens)


class EngineLoop:
    """One worker-resident arena driven step-chunk by step-chunk — the
    iteration-level inner loop, factored out of :class:`ContinuousBatcher`
    so the fleet layer (:mod:`repro.fleet`) can run one per fleet member
    with its own queue, role, and hand-off callbacks (ISSUE 6).

    Roles (disaggregated prefill/decode):

    * ``"unified"`` — prefill and decode in one arena (the PR 5 path; what
      every :class:`ContinuousBatcher` slot runs);
    * ``"prefill"`` — admit/prefill only: each admitted row is extracted
      (its arena slot freed immediately) and passed to ``await
      handoff(items)``, which migrates it into some decode member's
      ``intake``;
    * ``"decode"`` — no prompt admission: pre-filled rows arrive through
      ``intake`` (dicts ``{"entry": migration payload, "row": _LiveRow}``),
      are inserted into this arena, and decode here to completion.

    ``queue`` holds ``(Request, future)`` pairs; ``arrived`` is the shared
    wake-up event (broadcast — every idle loop re-checks its own work
    source after a wake); ``is_closed()`` polls the owner's shutdown flag;
    ``fallback(item)`` takes requests the arena can never hold.  Setting
    ``draining`` makes the loop exit once its queue/intake and live rows
    are served out — the zero-loss scale-down path: the owner must simply
    stop feeding the queue first.
    """

    def __init__(self, server: LMServer, *, index: int, queue, arrived,
                 stats: BatcherStats, cpu, is_closed, fallback=None,
                 max_batch: int = 8, quantum: int = 8, prompt_cap: int = 64,
                 prefix_tokens: int = 1 << 16, arena_cap: int | None = None,
                 lease_ttl_s: float = 60.0, role: str = "unified",
                 handoff=None, intake=None, paged: bool = False,
                 block_size: int = 16, prefill_budget: int | None = None,
                 pool_blocks: int | None = None, recover=None,
                 heartbeat: bool = True):
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"unknown engine-loop role {role!r}")
        if role == "prefill" and handoff is None:
            raise ValueError("a prefill-role loop needs a handoff callback")
        if paged and role != "unified":
            # row migration moves contiguous cache rows; a paged row is a
            # table of shared refcounted blocks with no standalone payload
            raise ValueError("paged arenas serve role='unified' only "
                             "(block tables cannot migrate between pools)")
        self.server = server
        self.index = index
        self.queue = queue
        self.intake = intake if intake is not None else deque()
        self.arrived = arrived
        self.stats = stats
        self.cpu = cpu
        self.is_closed = is_closed
        self.fallback = fallback
        self.role = role
        self.handoff = handoff
        # ``recover(item)`` re-queues a row lost to worker/state failure
        # for replay somewhere else (the fleet router re-routes around the
        # dead member); default = this loop's own queue.
        self.recover = recover
        self.heartbeat = bool(heartbeat)
        self.draining = False
        self.engine = None                     # set once run() starts
        self.live: dict[int, _LiveRow] = {}
        self.pending: dict[int, _LiveRow] = {}  # paged: prefill in flight
        self._free: deque[int] = deque()
        # paged: slots evicted locally but not yet released worker-side —
        # shipped as ``free_slots`` on the next engine call so blocks are
        # always given back BEFORE a slot id can be re-admitted
        self._to_free: set[int] = set()
        # per-member accounting the fleet router/bench report
        self.served = 0
        self.chunks = 0
        self.chunk_occupancy = 0
        self.migrated_in = 0
        self.migrated_out = 0
        self._root_span = obs_trace.NOOP       # set for real in run()
        self._sched = obs_trace.NOOP           # the open engine.schedule
        self._kwargs = dict(rows=max(1, max_batch),
                            prompt_cap=prompt_cap, quantum=quantum,
                            prefix_tokens=prefix_tokens, ttl_s=lease_ttl_s,
                            cap=arena_cap, paged=paged, block_size=block_size,
                            prefill_budget=prefill_budget,
                            pool_blocks=pool_blocks)

    # -------------------------------------------------------- router view --
    @property
    def rows(self) -> int:
        return self._kwargs["rows"]

    @property
    def free_rows(self) -> int:
        return self.rows - len(self.live) - len(self.pending)

    @property
    def load(self) -> int:
        """Row-units of work this member owns (queued + live + pending +
        in-flight hand-offs) — what the router's least-loaded policies
        compare."""
        pend = sum(1 for _, f in self.queue if not f.done())
        return pend + len(self.live) + len(self.pending) + len(self.intake)

    @property
    def closing(self) -> bool:
        return self.draining or self.is_closed()

    # ---------------------------------------------------------- internals --
    def _prune(self) -> None:
        while self.queue and self.queue[0][1].done():
            self.queue.popleft()               # cancelled while queued
        while self.intake and self.intake[0]["row"].fut.done():
            self.intake.popleft()

    def _fail(self, fut: asyncio.Future, e: BaseException,
              what: str) -> None:
        if not fut.done():
            fut.set_exception(e if isinstance(e, Exception)
                              else RuntimeError(f"{what}: {e!r}"))
        self.stats.requests += 1

    def _complete_row(self, row: _LiveRow, now: float) -> None:
        times = row.token_times_ms[:row.request.max_new]
        if not row.fut.done():
            row.fut.set_result(Completion(
                tokens=[int(t) for t in row.tokens[:row.request.max_new]],
                latency_ms=(now - row.t_arrival) * 1000.0,
                ttft_ms=row.ttft_ms, cost_gb_s=row.cost_gb_s,
                token_times_ms=times or None,
                recovered=row.recovered))
        self.stats.requests += 1
        self.served += 1
        wait_ms = 0.0 if row.t_submit is None \
            else (row.t_arrival - row.t_submit) * 1000.0
        _M_TTFT.observe(row.ttft_ms + wait_ms)
        if len(times) > 1:
            _M_TPOT.observe((times[-1] - times[0]) / (len(times) - 1))

    # ------------------------------------------------------- failover ----
    @staticmethod
    def _replayable(err: BaseException) -> bool:
        """Infrastructure loss — worker death, dropped connection, expired
        lease — is replayable; user-code/model errors are not (replaying a
        deterministic failure would just fail again elsewhere)."""
        from ..runtime.engine import is_state_lost
        return (is_state_lost(err) or isinstance(err, WorkerCrash)
                or isinstance(err, ConnectionError))

    def _recover_item(self, item) -> None:
        if self.recover is not None:
            self.recover(item)
        else:
            self.queue.append(item)
            self.arrived.set()

    def _readmit_ok(self, fut) -> bool:
        """Bounded requeue for a request whose ADMISSION died (no tokens
        lost — it never entered the arena)."""
        n = getattr(fut, "_readmits", 0) + 1
        fut._readmits = n
        return n <= MAX_ROW_REPLAYS

    def _try_replay(self, row: _LiveRow, err: BaseException) -> bool:
        """Requeue a lost live/pending row as ``prompt + generated_so_far``
        for chunked re-prefill on a healthy arena.  Greedy decode is a
        pure function of the token prefix, so the recovered completion is
        bit-identical to the unfailed one — worker death becomes added
        latency, not a client-visible error.  Returns False when the row
        must fail instead (non-replayable error, replay cap reached)."""
        fut = row.fut
        if fut.done() or not self._replayable(err) \
                or row.replays >= MAX_ROW_REPLAYS:
            return False
        orig = row.request
        fut._replay = {"request": orig,
                       "tokens": [int(t) for t in row.tokens],
                       "t_arrival": row.t_arrival, "ttft_ms": row.ttft_ms,
                       "t_submit": row.t_submit,
                       "token_times_ms": list(row.token_times_ms),
                       "cost": row.cost_gb_s, "attempts": row.replays + 1}
        replay = Request(
            prompt=list(orig.prompt) + [int(t) for t in row.tokens],
            max_new=row.remaining)
        self._recover_item((replay, fut))
        return True

    def _resume_row(self, meta: dict, fut, t0: int, now: float,
                    share: float = 0.0) -> _LiveRow:
        """Rebuild a replayed row at re-admission: original request, prior
        tokens + the re-prefill's first continuation token, timing merged
        so ``token_times_ms[0] == ttft_ms`` still holds."""
        t_ms = (now - meta["t_arrival"]) * 1000.0
        row = _LiveRow(request=meta["request"], fut=fut,
                       t_arrival=meta["t_arrival"],
                       tokens=list(meta["tokens"]) + [int(t0)],
                       ttft_ms=meta["ttft_ms"],
                       cost_gb_s=meta["cost"] + share,
                       token_times_ms=list(meta["token_times_ms"]) + [t_ms],
                       recovered=True, replays=meta["attempts"],
                       t_submit=meta["t_submit"])
        return row

    def _lose_state(self, err: BaseException) -> None:
        recovered = 0
        now = asyncio.get_running_loop().time()
        for rows in (self.live, self.pending):
            for slot, row in rows.items():
                self._free.append(slot)
                if row.fut.done():
                    continue
                if row.remaining <= 0:
                    # every requested token already arrived client-side:
                    # the crash cost nothing — deliver
                    self._complete_row(row, now)
                elif self._try_replay(row, err):
                    recovered += 1
                else:
                    self._fail(row.fut, err, "engine failed")
            rows.clear()
        self._to_free.clear()      # the new handle starts with a fresh pool
        self.engine.reset()
        self.stats.state_resets += 1
        self.stats.recovered_rows += recovered

    def _span(self, name: str, **attrs):
        """A span of this loop: a child of its root trace in the ring when
        that root was sampled, and an annotation in a capturing JAX
        profiler session either way."""
        return obs_trace.TRACER.span(name, self._root_span.ctx,
                                     profile=True, **attrs)

    @contextmanager
    def _phase(self, name: str, **attrs):
        """Leave the loop's own work (the open ``engine.schedule`` span)
        for an engine call or the idle wait, and resume it afterwards: the
        loop thread is in exactly one of these spans at a time, and none
        of them encloses a whole pass."""
        self._sched.finish()
        try:
            with self._span(name, **attrs) as sp:
                yield sp
        finally:
            self._sched = self._span("engine.schedule")

    def _admitted(self, fut, now: float) -> float | None:
        """Submit stamp of a newly admitted request; its queue wait goes to
        ``serve_queue_wait_ms``."""
        t = getattr(fut, "_t_submit", None)
        if t is not None:
            _M_WAIT.observe((now - t) * 1000.0)
        return t

    def _bound(self, span, fn):
        """Bind ``span`` as the dispatch parent for ``fn`` when it runs on
        the pack executor thread: the client.submit span the engine call
        mints over there nests under this chunk's span."""
        return obs_trace.bound(span.ctx, fn) if span else fn

    # --------------------------------------------------------------- run --
    async def run(self) -> None:
        from ..runtime.engine import EngineClient, is_state_lost
        loop = asyncio.get_running_loop()
        self._root_span = (obs_trace.TRACER.start_trace(
            "engine.loop", member=self.index, role=self.role)
            if obs_trace.TRACER.enabled else obs_trace.NOOP)
        try:
            # affinity = member/loop index, deterministically: a warmup
            # pass and the run it warms land on the SAME workers (a global
            # counter would re-home every fresh loop onto cold slots)
            self.engine = engine = EngineClient(self.server,
                                               affinity=self.index,
                                               **self._kwargs)
        except BaseException as e:
            # a loop that dies before serving must not leave submitters
            # parked forever: fail whatever is queued and surface the error
            while self.queue:
                _, fut = self.queue.popleft()
                self._fail(fut, e, "engine init failed")
            while self.intake:
                self._fail(self.intake.popleft()["row"].fut, e,
                           "engine init failed")
            raise
        live = self.live
        free = self._free
        free.extend(range(engine.rows))
        hits_seen = misses_seen = 0
        if self.heartbeat:
            # lease renewal decoupled from engine calls: a stalled loop
            # (chaos straggle, long pack) cannot expire live rows' state
            engine.start_heartbeat()

        self._sched = self._span("engine.schedule")
        try:
            while True:
                self._prune()
                # ---------------------------------- admission (every chunk)
                if self.role == "decode":
                    await self._admit_migrated(loop, is_state_lost)
                else:
                    await self._admit_prompts(loop, is_state_lost)
                # paged: advance in-flight chunked prefills by one budget's
                # worth of tokens, so long prompts interleave with the
                # decode chunk below instead of stalling it
                if self.pending:
                    await self._advance_prefill(loop, is_state_lost)
                # fold this engine's prefix-mirror counters into the shared
                # stats as deltas (several engine loops share one stats)
                self.stats.prefix_hits += engine.prefix_hits - hits_seen
                self.stats.prefix_misses += engine.prefix_misses - misses_seen
                hits_seen = engine.prefix_hits
                misses_seen = engine.prefix_misses

                # -------------------------------------- completion sweep
                now = loop.time()
                for slot in list(live):
                    row = live[slot]
                    if row.fut.done() or row.remaining <= 0:
                        self._complete_row(row, now)
                        del live[slot]
                        free.append(slot)
                        if engine.paged:
                            self._to_free.add(slot)
                for slot in list(self.pending):   # cancelled mid-prefill
                    if self.pending[slot].fut.done():
                        self._complete_row(self.pending.pop(slot), now)
                        free.append(slot)
                        self._to_free.add(slot)

                # ------------------------------------------ idle / close
                if not live:
                    waiting = (self.intake if self.role == "decode"
                               else self.queue)
                    if waiting or self.pending:
                        continue        # free slots / prefill work remain
                    if self.closing:
                        return
                    self.arrived.clear()
                    if waiting or self.closing:
                        continue
                    with self._phase("engine.idle"):
                        await self.arrived.wait()
                    continue

                # -------------------------------------------- decode chunk
                k = engine.choose_k(max(row.remaining
                                        for row in live.values()))
                if engine.paged:
                    # paged slots release by refcount drop, exactly once
                    # per eviction (a pending slot's blocks must survive)
                    idle = tuple(self._to_free)
                else:
                    # free every non-live slot, not just freshly-evicted
                    # ones: an idle freed slot whose start stayed at its
                    # freeze-time value would pin arena compaction forever
                    idle = tuple(s for s in range(engine.rows)
                                 if s not in live)
                try:
                    with self._phase("engine.decode_quantum", k=k,
                                     rows=len(live)) as cspan:
                        inv_fut = await loop.run_in_executor(
                            self.cpu, self._bound(cspan, engine.submit_step),
                            k, idle)
                        reply = engine.observe(
                            await await_invocation(inv_fut))
                except BaseException as e:
                    self._lose_state(e)
                    if isinstance(e, asyncio.CancelledError):
                        raise
                    continue
                self._to_free.difference_update(idle)
                self._note_occupancy()
                toks = reply["tokens"]
                rec = inv_fut.record
                share = (rec.billed_gb_s / len(live)) if rec else 0.0
                # ONE stamping point for per-token times: every token this
                # chunk delivered arrived, client-side, at this reply
                # (serve_bench and the TPOT metrics consume these stamps
                # instead of re-deriving from latency - ttft)
                t_chunk = loop.time()
                for slot, row in live.items():
                    need = row.remaining
                    if need > 0:
                        new = [int(t) for t in toks[slot][:need]]
                        row.tokens.extend(new)
                        t_ms = (t_chunk - row.t_arrival) * 1000.0
                        row.token_times_ms.extend([t_ms] * len(new))
                    row.cost_gb_s += share
                self.stats.decode_chunks += 1
                self.stats.decode_steps += k
                self.stats.occupancy_sum += len(live)
                self.chunks += 1
                self.chunk_occupancy += len(live)
        finally:
            self._sched.finish()
            self._root_span.set("served", self.served)
            self._root_span.set("chunks", self.chunks)
            self._root_span.finish()
            await loop.run_in_executor(self.cpu, engine.close)

    # ---------------------------------------------------------- admission --
    async def _admit_prompts(self, loop, is_state_lost) -> None:
        """Unified/prefill admission: pop queued prompts into free slots,
        one prefill round-trip; prefill-role loops then extract and hand
        the finished rows off instead of keeping them live."""
        engine, live, free = self.engine, self.live, self._free
        take: list[tuple[int, Request, asyncio.Future]] = []
        while free and self.queue:
            r, fut = self.queue.popleft()
            if fut.done():
                continue
            if not engine.fits(len(r.prompt), r.max_new):
                if self.fallback is not None:
                    self.fallback((r, fut))
                else:
                    self._fail(fut, ValueError(
                        f"prompt of {len(r.prompt)} tokens cannot fit this "
                        "arena and no fallback is configured"), "admission")
                continue
            take.append((free.popleft(), r, fut))
        if engine.paged:
            await self._admit_paged(loop, is_state_lost, take)
            return
        if not take:
            return
        t_sent = loop.time()
        hits0, miss0 = engine.prefix_hits, engine.prefix_misses
        try:
            with self._phase("engine.prefill", rows=len(take)) as pspan:
                inv_fut, order = await loop.run_in_executor(
                    self.cpu, self._bound(pspan, engine.submit_admit),
                    [(slot, r.prompt) for slot, r, _ in take],
                    # an arena holding live rows must already exist: never
                    # silently recreate an expired lease under them
                    not live)
                reply = engine.observe(await await_invocation(inv_fut))
                pspan.set("prefix_hits", engine.prefix_hits - hits0)
                pspan.set("prefix_misses", engine.prefix_misses - miss0)
                self._note_chunk(pspan, reply)
        except BaseException as e:
            for slot, r, fut in take:
                free.append(slot)
                # infrastructure loss during admission: nothing was decoded
                # yet, so the request (or in-flight replay) simply requeues
                if not fut.done() and self._replayable(e) \
                        and not isinstance(e, asyncio.CancelledError) \
                        and self._readmit_ok(fut):
                    self._recover_item((r, fut))
                else:
                    self._fail(fut, e, "admission failed")
            if is_state_lost(e):
                self._lose_state(e)
            if isinstance(e, asyncio.CancelledError):
                raise
            return
        now = loop.time()
        rec = inv_fut.record
        share = (rec.billed_gb_s / len(take)) if rec else 0.0
        ttft = (now - t_sent) * 1000.0
        by_slot = {slot: (r, fut) for slot, r, fut in take}
        for slot, t0 in zip(order, reply["first"]):
            r, fut = by_slot[slot]
            meta = getattr(fut, "_replay", None)
            if meta is not None:
                # this admission was a failover re-prefill: resume the
                # original row where its dead arena left off
                del fut._replay
                live[slot] = self._resume_row(meta, fut, t0, now, share)
                continue
            live[slot] = _LiveRow(request=r, fut=fut, t_arrival=t_sent,
                                  tokens=[int(t0)], ttft_ms=ttft,
                                  cost_gb_s=share,
                                  token_times_ms=[ttft],
                                  t_submit=self._admitted(fut, t_sent))
        self.stats.admission_groups += 1
        if self.role == "prefill":
            await self._handoff_rows(loop, list(live), is_state_lost)

    # ------------------------------------------------- paged admission --
    def _promote(self, reply: dict, now: float, share: float = 0.0) -> None:
        """Move pending rows whose chunked prefill just completed into the
        live set, stamping TTFT at the reply that produced their first
        token (not at admission — a long prompt's TTFT includes every
        chunk it waited through)."""
        for slot, info in reply.get("slots", {}).items():
            row = self.pending.get(int(slot))
            if row is None:
                continue
            row.cost_gb_s += share
            if info.get("live"):
                del self.pending[int(slot)]
                row.tokens.append(int(info["first"]))
                t_ms = (now - row.t_arrival) * 1000.0
                if row.recovered and row.token_times_ms:
                    # failover re-prefill: TTFT was stamped by the original
                    # admission — this is just the next token arriving late
                    row.token_times_ms.append(t_ms)
                else:
                    row.ttft_ms = t_ms
                    row.token_times_ms.append(t_ms)
                self.live[int(slot)] = row

    def _note_occupancy(self) -> None:
        occ = self.engine.occupancy
        if not occ:
            return
        st = self.stats
        st.live_tokens_peak = max(st.live_tokens_peak,
                                  int(occ.get("live_tokens", 0)))
        st.allocated_blocks_peak = max(st.allocated_blocks_peak,
                                       int(occ.get("allocated_blocks", 0)))
        st.shared_blocks_peak = max(st.shared_blocks_peak,
                                    int(occ.get("shared_blocks", 0)))

    def _note_chunk(self, span, reply: dict) -> None:
        """A prefill call's prompt tokens: run through the model (real, and
        padded to the programs' widths) and served from the arena's prefix
        store or radix index."""
        real = int(reply.get("prefill_tokens", 0))
        matched = int(reply.get("prefix_tokens", 0))
        self.stats.prefill_tokens += real
        self.stats.prefix_tokens += matched
        span.set("prompt_tokens", real)
        span.set("padded_tokens", int(reply.get("padded_tokens", 0)))
        span.set("prefix_tokens", matched)

    async def _admit_paged(self, loop, is_state_lost, take) -> None:
        """Paged admission: one prefill round-trip admits the new rows and
        advances them up to the chunk budget.  Rows that finish inside the
        call go live with their first token; the rest stay pending and
        advance via :meth:`_advance_prefill` on later iterations."""
        engine, live, free = self.engine, self.live, self._free
        if not take:
            return
        t_sent = loop.time()
        hits0, miss0 = engine.prefix_hits, engine.prefix_misses
        try:
            with self._phase("engine.prefill_chunk", rows=len(take),
                             pending=len(self.pending)) as pspan:
                inv_fut, _ = await loop.run_in_executor(
                    self.cpu, self._bound(pspan, engine.submit_admit),
                    [(slot, r.prompt) for slot, r, _ in take],
                    not (live or self.pending), tuple(self._to_free))
                reply = engine.observe_paged_prefill(
                    await await_invocation(inv_fut))
                pspan.set("radix_hits", engine.prefix_hits - hits0)
                pspan.set("radix_misses", engine.prefix_misses - miss0)
                self._note_chunk(pspan, reply)
        except BaseException as e:
            for slot, r, fut in take:
                free.append(slot)
                if not fut.done() and self._replayable(e) \
                        and not isinstance(e, asyncio.CancelledError) \
                        and self._readmit_ok(fut):
                    self._recover_item((r, fut))
                else:
                    self._fail(fut, e, "admission failed")
            if is_state_lost(e):
                self._lose_state(e)
            if isinstance(e, asyncio.CancelledError):
                raise
            return
        self._to_free.clear()
        now = loop.time()
        rec = inv_fut.record
        share = (rec.billed_gb_s / len(take)) if rec else 0.0
        for slot, r, fut in take:
            meta = getattr(fut, "_replay", None)
            if meta is not None:
                # failover re-prefill joins the pending set carrying its
                # prior tokens; _promote appends the continuation token
                # without restamping TTFT
                del fut._replay
                self.pending[slot] = _LiveRow(
                    request=meta["request"], fut=fut,
                    t_arrival=meta["t_arrival"],
                    tokens=list(meta["tokens"]), ttft_ms=meta["ttft_ms"],
                    cost_gb_s=meta["cost"],
                    token_times_ms=list(meta["token_times_ms"]),
                    recovered=True, replays=meta["attempts"],
                    t_submit=meta["t_submit"])
                continue
            self.pending[slot] = _LiveRow(
                request=r, fut=fut, t_arrival=t_sent,
                t_submit=self._admitted(fut, t_sent))
        self._promote(reply, now, share)
        self.stats.admission_groups += 1
        self._note_occupancy()

    async def _advance_prefill(self, loop, is_state_lost) -> None:
        """One budget's worth of chunked-prefill progress for the pending
        rows (no new admissions).  Any failure here is arena-fatal — the
        pool's block accounting is mid-flight — so it resets like a failed
        decode chunk."""
        engine = self.engine
        try:
            with self._phase("engine.prefill_chunk", rows=0,
                             pending=len(self.pending)) as pspan:
                inv_fut = await loop.run_in_executor(
                    self.cpu, self._bound(pspan, engine.submit_prefill_step),
                    tuple(self._to_free))
                reply = engine.observe_paged_prefill(
                    await await_invocation(inv_fut))
                self._note_chunk(pspan, reply)
        except BaseException as e:
            self._lose_state(e)
            if isinstance(e, asyncio.CancelledError):
                raise
            return
        self._to_free.clear()
        rec = inv_fut.record
        n = max(1, len(self.pending))
        share = (rec.billed_gb_s / n) if rec else 0.0
        self._promote(reply, loop.time(), share)
        self.stats.admission_groups += 1
        self._note_occupancy()

    async def _handoff_rows(self, loop, slots, is_state_lost) -> None:
        """Prefill role: pull the freshly-prefilled rows out of the arena
        (freeing its slots for the next admission group) and hand them to
        the router, which places them in a decode member's intake.  TTFT
        was already stamped at the prefill reply — migration latency shows
        up in per-token time, not time-to-first-token."""
        engine, live, free = self.engine, self.live, self._free
        try:
            with self._phase("engine.migrate_out", rows=len(slots)) as mspan:
                payloads = await loop.run_in_executor(
                    self.cpu, self._bound(mspan, engine.extract_rows), slots)
        except BaseException as e:
            for slot in slots:
                row = live.pop(slot, None)
                if row is not None:
                    self._fail(row.fut, e, "row hand-off failed")
                free.append(slot)
            if is_state_lost(e):
                engine.reset()
                self.stats.state_resets += 1
            if isinstance(e, asyncio.CancelledError):
                raise
            return
        items = []
        for slot, payload in zip(slots, payloads):
            row = live.pop(slot)
            free.append(slot)
            items.append({"entry": payload, "row": row})
        self.migrated_out += len(items)
        self.stats.migrated_rows += len(items)
        await self.handoff(items)

    async def _admit_migrated(self, loop, is_state_lost) -> None:
        """Decode-role admission: insert migrated rows from the intake into
        free slots.  An idle decode arena may have expired between bursts —
        when no rows are live it is (re)built empty first, so an insert can
        never silently target a blank lease."""
        engine, live, free = self.engine, self.live, self._free
        take: list[tuple[int, dict]] = []
        while free and self.intake:
            ent = self.intake.popleft()
            if ent["row"].fut.done():
                continue
            take.append((free.popleft(), ent))
        if not take:
            return
        slots = [slot for slot, _ in take]
        try:
            with self._phase("engine.migrate_in", rows=len(take)) as mspan:
                if not live:
                    inv_fut, _ = await loop.run_in_executor(
                        self.cpu, self._bound(mspan, engine.submit_admit),
                        [], True)
                    engine.observe(await await_invocation(inv_fut))
                await loop.run_in_executor(
                    self.cpu, self._bound(mspan, engine.insert_rows), slots,
                    [ent["entry"] for _, ent in take])
        except BaseException as e:
            for slot, ent in take:
                free.append(slot)
                self._fail(ent["row"].fut, e, "row insert failed")
            if is_state_lost(e):
                self._lose_state(e)
            if isinstance(e, asyncio.CancelledError):
                raise
            return
        for slot, ent in take:
            live[slot] = ent["row"]
        self.migrated_in += len(take)
        self.stats.admission_groups += 1


def _merge_replay(fut, comp: Completion, now: float) -> Completion:
    """Fold a failover re-prefill served by the WAVE path back into its
    original request's completion: a replay whose grown prompt exceeded
    ``prompt_cap`` falls back to a solo wave, which decodes only the
    continuation — prepend the tokens decoded before the crash and keep
    the original TTFT/arrival timing (ISSUE 10)."""
    meta = getattr(fut, "_replay", None)
    if meta is None:
        return comp
    del fut._replay
    orig = meta["request"]
    tokens = list(meta["tokens"]) + [int(t) for t in comp.tokens]
    t_ms = (now - meta["t_arrival"]) * 1000.0
    times = list(meta["token_times_ms"]) + \
        [t_ms] * max(0, len(tokens) - len(meta["token_times_ms"]))
    n = orig.max_new
    return Completion(tokens=tokens[:n], latency_ms=t_ms,
                      cost_gb_s=meta["cost"] + comp.cost_gb_s,
                      ttft_ms=meta["ttft_ms"],
                      token_times_ms=times[:n] or None, recovered=True)


class ContinuousBatcher:
    """Admit arriving requests into in-flight decode capacity.

    ::

        async with ContinuousBatcher(server, max_batch=8, slots=4,
                                     max_wait_ms=10) as batcher:
            completion = await batcher.submit(Request(prompt, max_new=16))

    ``submit`` may be called from any number of concurrent tasks; each
    returns when *its* request completes.  Cancelling the awaiting task
    removes a still-queued request from the scheduler (a request already
    admitted runs on; its slot is reclaimed at the next chunk boundary and
    its result dropped).

    Iteration-level knobs (ignored on the batch-level path): ``quantum``
    decode steps per chunk (admission/eviction granularity), ``prompt_cap``
    longest admissible prompt (longer ones fall back to a solo wave),
    ``prefix_tokens`` budget of the worker-resident prompt-prefix cache
    (LRU by token count; 0 disables), ``arena_cap`` cache capacity
    override, ``lease_ttl_s`` the worker-side state lease.

    Paged knobs (ISSUE 7): ``paged=True`` swaps each slot arena for a
    refcounted block-pool KV arena — prompts above ``prompt_cap`` no
    longer fall back to solo waves (prefill is chunked under
    ``prefill_budget`` tokens per engine call), and the prefix store
    becomes a radix index whose shared prefixes share physical blocks.
    ``block_size`` is the KV block granularity (rounded to a power of
    two), ``pool_blocks`` overrides the pool size.  Ignored on families
    without a paged layout (ssm serves from the slot arena, which already
    admits any prompt length) and on the batch-level path.
    """

    def __init__(self, server: LMServer, *, max_batch: int = 8,
                 slots: int = 2, max_wait_ms: float = 10.0,
                 iteration_level: bool | None = None, quantum: int = 8,
                 prompt_cap: int = 64, prefix_tokens: int = 1 << 16,
                 arena_cap: int | None = None, lease_ttl_s: float = 60.0,
                 paged: bool = False, block_size: int = 16,
                 prefill_budget: int | None = None,
                 pool_blocks: int | None = None, heartbeat: bool = True):
        self._server = server
        self._heartbeat = bool(heartbeat)
        self._max_batch = max(1, max_batch)
        self._n_slots = max(1, slots)
        self._max_wait_s = max(0.0, max_wait_ms) / 1000.0
        self._iteration = iteration_level
        self._quantum = max(1, quantum)
        self._prompt_cap = max(1, prompt_cap)
        self._prefix_tokens = max(0, prefix_tokens)
        self._arena_cap = arena_cap
        self._lease_ttl_s = lease_ttl_s
        self._paged = bool(paged)
        self._block_size = max(1, block_size)
        self._prefill_budget = prefill_budget
        self._pool_blocks = pool_blocks
        self._queue: deque[tuple[Request, asyncio.Future]] = deque()
        self._slots: asyncio.Semaphore | None = None
        self._arrived: asyncio.Event | None = None
        self._scheduler: asyncio.Task | None = None
        self._loops: list[asyncio.Task] = []
        self._batch_tasks: set[asyncio.Task] = set()
        self._closed = False
        # ONE pack/unpack thread, deliberately: payload serialization is
        # GIL-bound python — fanning it across executor threads only adds
        # contention that stretches every in-flight roundtrip.  Transport
        # IO still overlaps across all slots (iteration-level submits
        # return futures immediately; only packing serializes here).
        self._cpu = ThreadPoolExecutor(max_workers=1,
                                       thread_name_prefix="repro-batcher")
        self.stats = BatcherStats()

    # ------------------------------------------------------------ lifecycle
    def _resolve_mode(self) -> bool:
        if self._iteration is not False:
            # auto OR forced-on: both require a resident-state backend and
            # an arena-capable family — a forced-on batcher on e.g. encdec
            # demotes to batch-level rather than wedging every submit
            # behind an engine that cannot be constructed
            from ..models.api import arena_supported
            caps = self._server.session.backend.capabilities
            self._iteration = bool(getattr(caps, "resident_state", False)) \
                and arena_supported(self._server.cfg)
        return bool(self._iteration)

    def _ensure_running(self) -> None:
        running = (self._loops if self._resolve_mode()
                   else (self._scheduler is not None
                         and not self._scheduler.done()))
        if running:
            return
        if self._closed:
            raise RuntimeError("batcher is closed")
        loop = asyncio.get_running_loop()
        self._slots = self._slots or asyncio.Semaphore(self._n_slots)
        self._arrived = self._arrived or asyncio.Event()
        if self._iteration:
            self.stats.mode = "iteration"
            self._loops = [loop.create_task(self._engine_loop(i))
                           for i in range(self._n_slots)]
        else:
            self.stats.mode = "batch"
            self._scheduler = loop.create_task(self._schedule())

    async def __aenter__(self) -> "ContinuousBatcher":
        self._ensure_running()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.aclose()

    async def aclose(self) -> None:
        """Stop admitting, let in-flight work finish, fail queued requests
        that never made it into a batch/arena."""
        self._closed = True
        if self._arrived is not None:
            self._arrived.set()
        if self._scheduler is not None:
            await self._scheduler
        if self._loops:
            await asyncio.gather(*self._loops, return_exceptions=True)
        if self._batch_tasks:
            await asyncio.gather(*self._batch_tasks, return_exceptions=True)
        while self._queue:
            _, fut = self._queue.popleft()
            if not fut.done():
                fut.set_exception(RuntimeError("batcher closed before the "
                                               "request was scheduled"))
        self._cpu.shutdown(wait=False)

    # ------------------------------------------------------------- clients
    async def submit(self, request: Request) -> Completion:
        """Queue one request; resolves when its decode completes."""
        if self._closed:
            raise RuntimeError("batcher is closed")
        self._ensure_running()
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        fut._t_submit = loop.time()
        self._queue.append((request, fut))
        self._arrived.set()
        return await fut

    @property
    def queued(self) -> int:
        return sum(1 for _, f in self._queue if not f.done())

    @property
    def iteration_level(self) -> bool:
        """Which granularity this batcher runs at (resolved lazily)."""
        return self._resolve_mode()

    # ----------------------------------------------------------- scheduler
    def _prune(self) -> None:
        while self._queue and self._queue[0][1].done():
            self._queue.popleft()            # cancelled while queued

    # ======================================================== batch-level =
    def _batch_ready(self) -> bool:
        """A batch can seal without waiting: the head's bucket alone fills
        it, or the whole queue does (top-up keeps the slot busy)."""
        self._prune()
        if not self._queue:
            return False
        b = decode_bucket(self._queue[0][0].max_new)
        live = head = 0
        for r, f in self._queue:
            if f.done():
                continue
            live += 1
            head += decode_bucket(r.max_new) == b
        return head >= self._max_batch or live >= self._max_batch

    def _take_batch(self) -> list[tuple[Request, asyncio.Future]]:
        """Seal a batch: FIFO head defines the preferred decode bucket;
        take up to ``max_batch`` live requests from that bucket first, then
        top up with the oldest other-bucket requests.  Bucketing is a
        *preference*, not a constraint: a pure batch decodes short, a
        topped-up batch decodes at its longest member (what a fixed wave
        would have done anyway) — so grouping can only save compute, never
        idle a free slot behind it.
        """
        self._prune()
        if not self._queue:
            return []
        bucket = decode_bucket(self._queue[0][0].max_new)
        batch: list[tuple[Request, asyncio.Future]] = []
        keep: deque = deque()
        while self._queue:                   # pass 1: the head's bucket
            r, f = self._queue.popleft()
            if f.done():
                continue
            if len(batch) < self._max_batch and \
                    decode_bucket(r.max_new) == bucket:
                batch.append((r, f))
            else:
                keep.append((r, f))
        while keep and len(batch) < self._max_batch:   # pass 2: top up
            batch.append(keep.popleft())
        self._queue.extend(keep)             # leftovers keep arrival order
        return batch

    async def _schedule(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            self._prune()
            if not self._queue:
                if self._closed:
                    return
                self._arrived.clear()
                if self._queue:              # raced an append
                    continue
                await self._arrived.wait()
                continue
            await self._slots.acquire()
            # a slot is ours: give the forming batch up to max_wait to fill
            sealed_by = "full"
            if not self._batch_ready() and self._max_wait_s > 0 \
                    and not self._closed:
                deadline = loop.time() + self._max_wait_s
                while not self._batch_ready() and not self._closed:
                    remaining = deadline - loop.time()
                    if remaining <= 0:
                        sealed_by = "wait"
                        break
                    self._arrived.clear()
                    try:
                        await asyncio.wait_for(self._arrived.wait(), remaining)
                    except asyncio.TimeoutError:
                        sealed_by = "wait"
                        break
            batch = self._take_batch()
            if not batch:
                self._slots.release()
                continue
            if sealed_by == "full":
                self.stats.sealed_full += 1
            else:
                self.stats.sealed_wait += 1
            task = loop.create_task(self._run_batch(batch))
            self._batch_tasks.add(task)
            task.add_done_callback(self._batch_tasks.discard)

    async def _run_batch(self,
                         batch: list[tuple[Request, asyncio.Future]],
                         *, hold_slot: bool = True) -> None:
        loop = asyncio.get_running_loop()
        requests = [r for r, _ in batch]
        bucket = decode_bucket(max(r.max_new for r in requests))
        try:
            # payload packing ships params: keep it off the loop.  min_rows
            # pins the batch-shape bucket so partial batches never compile
            # a fresh entry point mid-serve.
            inv_fut = await loop.run_in_executor(
                self._cpu, lambda: self._server.submit_wave(
                    requests, min_rows=self._max_batch))
            await await_invocation(inv_fut)
            comps = await loop.run_in_executor(
                self._cpu, self._server.unpack_wave, requests, inv_fut)
        except BaseException as e:
            for _, fut in batch:
                if not fut.done():
                    fut.set_exception(
                        e if isinstance(e, Exception)
                        else RuntimeError(f"batch failed: {e!r}"))
        else:
            t_done = loop.time()
            for (_, fut), comp in zip(batch, comps):
                if not fut.done():
                    fut.set_result(_merge_replay(fut, comp, t_done))
        finally:
            self.stats.requests += len(batch)
            self.stats.batches += 1
            self.stats.occupancy_sum += len(batch)
            self.stats.decode_steps += bucket
            self.stats.bucket_histogram[bucket] = \
                self.stats.bucket_histogram.get(bucket, 0) + 1
            if hold_slot:
                self._slots.release()

    # ==================================================== iteration-level =
    def _fallback_wave(self, item: tuple[Request, asyncio.Future]) -> None:
        """A request the arena cannot hold (prompt above ``prompt_cap``):
        serve it as a solo wave so it is never silently starved."""
        self.stats.wave_fallbacks += 1
        task = asyncio.get_running_loop().create_task(
            self._run_batch([item], hold_slot=False))
        self._batch_tasks.add(task)
        task.add_done_callback(self._batch_tasks.discard)

    async def _engine_loop(self, index: int) -> None:
        """One worker-resident arena, driven step-chunk by step-chunk by a
        unified-role :class:`EngineLoop` over the batcher's shared queue:
        admit into free rows, decode ``k`` steps, evict finished rows,
        repeat.  Admission and eviction both happen at chunk boundaries —
        the iteration-level quantum."""
        await EngineLoop(
            self._server, index=index, queue=self._queue,
            arrived=self._arrived, stats=self.stats, cpu=self._cpu,
            is_closed=lambda: self._closed, fallback=self._fallback_wave,
            max_batch=self._max_batch, quantum=self._quantum,
            prompt_cap=self._prompt_cap, prefix_tokens=self._prefix_tokens,
            arena_cap=self._arena_cap, lease_ttl_s=self._lease_ttl_s,
            paged=self._paged, block_size=self._block_size,
            prefill_budget=self._prefill_budget,
            pool_blocks=self._pool_blocks,
            heartbeat=self._heartbeat).run()


def run_continuous(server: LMServer, requests: Sequence[Request], *,
                   concurrency: int = 16, max_batch: int = 8, slots: int = 2,
                   max_wait_ms: float = 10.0,
                   **batcher_kwargs) -> list[Completion]:
    """Closed-loop convenience driver: feed ``requests`` through a
    :class:`ContinuousBatcher` with at most ``concurrency`` outstanding;
    returns completions in request order.  This is what ``--mode
    continuous`` in the serve launcher/example runs.  Extra keyword
    arguments (``iteration_level``, ``quantum``, ``prefix_tokens``, …)
    pass through to the batcher.
    """
    async def go() -> list[Completion]:
        sem = asyncio.Semaphore(max(1, concurrency))
        async with ContinuousBatcher(server, max_batch=max_batch,
                                     slots=slots,
                                     max_wait_ms=max_wait_ms,
                                     **batcher_kwargs) as batcher:
            async def one(r: Request) -> Completion:
                async with sem:
                    return await batcher.submit(r)
            return list(await asyncio.gather(*[one(r) for r in requests]))
    return asyncio.run(go())
