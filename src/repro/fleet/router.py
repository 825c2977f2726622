"""Prefix-aware fleet router over per-member iteration-level engine loops.

Each :class:`FleetMember` wraps one
:class:`~repro.serving.batcher.EngineLoop` with its own request queue and
a unique, never-reused affinity index, so every member is pinned to its
own worker (resident arena + prompt-prefix store).  The router owns
placement:

* ``policy="prefix"`` — a content-hash index (``prefix_key`` over the
  first ``prefix_len`` prompt tokens; the whole prompt when unset —
  exactly the key the worker-resident prefix store uses) remembers which
  member first served each prefix and routes repeats back to it, the
  client-side mirror of the workers' prefix caches.  An owner loaded past
  ``spill_factor × rows`` spills to power-of-two-choices *without*
  reassigning ownership — transient overload must not thrash affinity.
* ``policy="p2c"`` — least-loaded of two random members (the classic
  balanced-allocations bound on max load).
* ``policy="random"`` — uniform; the A/B baseline for prefix routing.
* ``policy="radix"`` — a client-side radix index over block-aligned
  token runs (the router's mirror of the workers' paged radix stores,
  ISSUE 7): a prompt routes to the member owning its *longest shared
  prefix*, so partial overlaps — not just exact repeats — land where the
  shared blocks already live.  Spill semantics match ``prefix``; both
  count as prefix-routed in the stats.

``disaggregate=True`` splits roles: prompts route only to prefill
members, whose freshly-prefilled rows migrate through ``handoff`` into
the least-loaded decode member's intake.  ``elastic=True`` starts a
:class:`~repro.fleet.controller.FleetController` that grows the pool
toward ``n_members`` under backlog and drains it back on sustained low
occupancy.  Draining never kills a worker: the member stops receiving
traffic, serves out its queue and live rows, then releases its lease —
its worker stays warm for the next grow.
"""
from __future__ import annotations

import asyncio
import random
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Sequence

from ..dispatch.retry import CircuitBreaker
from ..obs import trace as obs_trace
from ..runtime.engine import prefix_key
from ..runtime.server import Completion, LMServer, Request
from ..serving.aio import await_invocation
from ..serving.batcher import BatcherStats, EngineLoop

__all__ = ["FleetMember", "FleetRouter", "FleetStats", "run_fleet"]


@dataclass
class FleetStats:
    """Router-side placement accounting (engine-side counters — prefix
    hits, chunks, migrations — live in the shared ``BatcherStats`` and
    per-member ``EngineLoop`` counters)."""
    routed_prefix: int = 0          # placed by the content-hash index
    routed_p2c: int = 0             # least-loaded fallback / p2c policy
    routed_random: int = 0
    spills: int = 0                 # owner over spill threshold
    handoffs: int = 0               # prefill→decode migration groups
    recoveries: int = 0             # rows re-routed after member failure
    scale_events: list = field(default_factory=list)

    @property
    def routed_total(self) -> int:
        return self.routed_prefix + self.routed_p2c + self.routed_random

    @property
    def prefix_route_rate(self) -> float:
        n = self.routed_total
        return self.routed_prefix / n if n else 0.0


class FleetMember:
    """One fleet member: an engine loop, its queue, and its task."""

    def __init__(self, index: int, role: str, loop: EngineLoop,
                 breaker: CircuitBreaker | None = None):
        self.index = index          # == the loop's worker affinity
        self.role = role
        self.loop = loop
        self.task: asyncio.Task | None = None
        # per-member circuit breaker: a row replayed off this member
        # records a failure; an open breaker takes the member out of the
        # routing set until the cooldown admits a half-open probe
        self.breaker = breaker if breaker is not None else CircuitBreaker()
        self.reaped = False         # controller already replaced it

    @property
    def active(self) -> bool:
        """Routable: running and not being drained."""
        return (self.task is not None and not self.task.done()
                and not self.loop.draining)

    @property
    def done(self) -> bool:
        return self.task is not None and self.task.done()

    def summary(self) -> dict:
        lp = self.loop
        return {"index": self.index, "role": self.role,
                "served": lp.served, "chunks": lp.chunks,
                "mean_occupancy": round(lp.chunk_occupancy / lp.chunks, 2)
                if lp.chunks else 0.0,
                "migrated_in": lp.migrated_in,
                "migrated_out": lp.migrated_out,
                "draining": lp.draining, "done": self.done,
                "breaker": self.breaker.snapshot()}


class FleetRouter:
    """Async router fronting a fleet of engine-loop members.

    ::

        async with FleetRouter(server, n_members=3) as fleet:
            completion = await fleet.submit(Request(prompt, max_new=16))

    Requires a resident-state backend and an arena-capable model family
    (the same contract as iteration-level ``ContinuousBatcher``); there
    is no batch-level demotion here — a fleet without worker-resident
    arenas is just N copies of the wave scheduler.
    """

    POLICIES = ("prefix", "p2c", "random", "radix")

    def __init__(self, server: LMServer, *, n_members: int = 3,
                 policy: str = "prefix", prefix_len: int | None = None,
                 spill_factor: float = 2.0, disaggregate: bool = False,
                 prefill_members: int = 1, elastic: bool = False,
                 min_members: int = 1, controller: dict | None = None,
                 max_batch: int = 8, quantum: int = 8, prompt_cap: int = 64,
                 prefix_tokens: int = 1 << 16, arena_cap: int | None = None,
                 lease_ttl_s: float = 60.0, seed: int = 0,
                 paged: bool = False, block_size: int = 16,
                 prefill_budget: int | None = None,
                 pool_blocks: int | None = None,
                 breaker: dict | None = None,
                 heartbeat: bool = True):
        if policy not in self.POLICIES:
            raise ValueError(f"unknown routing policy {policy!r}")
        if paged and disaggregate:
            raise ValueError("paged arenas cannot disaggregate: block "
                             "tables do not migrate between pools")
        from ..models.api import arena_supported
        caps = server.session.backend.capabilities
        if not getattr(caps, "resident_state", False):
            raise ValueError(
                "fleet serving needs a resident-state backend "
                "(inline/threads/processes/http/http-aio) — "
                f"{type(server.session.backend).__name__} keeps none")
        if not arena_supported(server.cfg):
            raise ValueError(f"family {server.cfg.family!r} has no slot "
                             "arena; fleet serving is iteration-level only")
        self._server = server
        self.n_members = max(1, n_members)
        self.policy = policy
        self.prefix_len = prefix_len
        self.spill_factor = max(1.0, spill_factor)
        self.disaggregate = bool(disaggregate)
        self.prefill_members = max(1, prefill_members)
        self.elastic = bool(elastic)
        self.min_members = max(1, min_members)
        self._controller_kw = dict(controller or {})
        self._loop_kw = dict(max_batch=max_batch, quantum=quantum,
                             prompt_cap=prompt_cap,
                             prefix_tokens=prefix_tokens,
                             arena_cap=arena_cap, lease_ttl_s=lease_ttl_s,
                             paged=paged, block_size=block_size,
                             prefill_budget=prefill_budget,
                             pool_blocks=pool_blocks, heartbeat=heartbeat)
        # a single crash is a strong signal for a pinned member — one
        # failure opens the breaker, the cooldown admits a probe, and a
        # quiet probe window closes it again without an explicit success
        self._breaker_kw = dict(threshold=1, cooldown_s=0.25,
                                probe_window_s=0.25)
        self._breaker_kw.update(breaker or {})
        self._rng = random.Random(seed)
        self.members: list[FleetMember] = []
        self._next_index = 0
        self._capacity = 0              # backend workers provisioned so far
        self._owners: dict[str, FleetMember] = {}   # prefix key -> member
        if policy == "radix":
            # the router's longest-shared-prefix mirror; payloads are
            # member indices, one per block-aligned run — same geometry as
            # the workers' radix stores so claims stay block-aligned
            from ..runtime.radix import RadixIndex
            from ..runtime.server import shape_bucket
            self._radix = RadixIndex(shape_bucket(max(1, block_size)),
                                     budget_tokens=max(1, prefix_tokens))
        self._arrived: asyncio.Event | None = None
        self._controller_task: asyncio.Task | None = None
        self._solo_tasks: set[asyncio.Task] = set()
        self._closed = False
        self._started = False
        # one pack/unpack thread shared by every member, same rationale as
        # ContinuousBatcher: payload packing is GIL-bound python, transport
        # IO overlaps across members regardless
        self._cpu = ThreadPoolExecutor(max_workers=1,
                                       thread_name_prefix="repro-fleet")
        self.batcher_stats = BatcherStats(mode="iteration")
        self.stats = FleetStats()
        self._root_span = obs_trace.NOOP

    def _event_span(self, name: str, **attrs) -> None:
        """Instant marker under the fleet root trace (grow/drain/handoff
        are routing-set *moments*, not intervals)."""
        root = self._root_span
        if root:
            obs_trace.TRACER.span_at(name, root.ctx, time.time(), 0.0,
                                     **attrs)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        if self._started:
            return
        if self._closed:
            raise RuntimeError("fleet router is closed")
        self._started = True
        self._arrived = asyncio.Event()
        if obs_trace.TRACER.enabled:
            self._root_span = obs_trace.TRACER.start_trace(
                "fleet.serve", policy=self.policy,
                disaggregate=self.disaggregate, elastic=self.elastic)
        initial = self.min_members if self.elastic else self.n_members
        if self.disaggregate:
            initial = max(initial, 2)   # never fewer than one of each role
            n_pre = min(self.prefill_members, initial - 1)
            roles = ["prefill"] * n_pre + ["decode"] * (initial - n_pre)
        else:
            roles = ["unified"] * initial
        for role in roles:
            self._spawn(role)
        if self.elastic:
            from .controller import FleetController
            ctl = FleetController(self, max_members=self.n_members,
                                  min_members=self.min_members,
                                  **self._controller_kw)
            self._controller_task = asyncio.get_running_loop().create_task(
                ctl.run())

    async def __aenter__(self) -> "FleetRouter":
        self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.aclose()

    async def aclose(self) -> None:
        """Stop routing, serve out every member, fail never-admitted
        leftovers.  Members exit via their normal idle/close path, so
        everything admitted or queued before close still completes."""
        self._closed = True
        if self._controller_task is not None:
            self._controller_task.cancel()
            try:
                await self._controller_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._arrived is not None:
            self._arrived.set()
        tasks = [m.task for m in self.members if m.task is not None]
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
        if self._solo_tasks:
            await asyncio.gather(*self._solo_tasks, return_exceptions=True)
        if self._root_span:
            self._root_span.set("routed", self.stats.routed_total)
            self._root_span.set("scale_events",
                                len(self.stats.scale_events))
            self._root_span.finish()
            self._root_span = obs_trace.NOOP
        for m in self.members:
            for q in (m.loop.queue, m.loop.intake):
                while q:
                    item = q.popleft()
                    fut = item[1] if isinstance(item, tuple) \
                        else item["row"].fut
                    if not fut.done():
                        fut.set_exception(RuntimeError(
                            "fleet closed before the request was scheduled"))
        self._cpu.shutdown(wait=False)

    # ------------------------------------------------------------- members
    def _backend_workers(self) -> int:
        be = self._server.session.backend
        st = getattr(be, "stats", None)
        if callable(st):
            try:
                return int(st().get("n_workers", 1))
            except Exception:
                pass
        return 1

    def _ensure_capacity(self, n: int) -> None:
        """Grow (only) the backend's pinned-worker count so a new member's
        affinity freezes onto its own worker.  Never shrinks — scale-down
        is cooperative draining, the workers stay warm."""
        if self._capacity == 0:
            self._capacity = self._backend_workers()
        if n <= self._capacity:
            return
        scale = getattr(self._server.session.backend, "scale_to", None)
        if scale is not None:
            scale(n)
        self._capacity = n

    def _spawn(self, role: str) -> FleetMember:
        idx = self._next_index
        self._next_index += 1
        self._ensure_capacity(idx + 1)
        loop = EngineLoop(
            self._server, index=idx, queue=deque(), arrived=self._arrived,
            stats=self.batcher_stats, cpu=self._cpu,
            is_closed=lambda: self._closed, fallback=self._fallback_wave,
            role=role, handoff=self._handoff if role == "prefill" else None,
            recover=lambda item, i=idx: self._recover(i, item),
            **self._loop_kw)
        member = FleetMember(idx, role, loop,
                             breaker=CircuitBreaker(**self._breaker_kw))
        member.task = asyncio.get_running_loop().create_task(loop.run())
        self.members.append(member)
        return member

    @property
    def active_members(self) -> list[FleetMember]:
        return [m for m in self.members if m.active]

    def _routable(self) -> list[FleetMember]:
        pool = [m for m in self.members if m.active and m.role != "decode"]
        # breaker-open members sit out; if EVERY breaker is open the pool
        # wins over the breakers — refusing all traffic helps nobody, and
        # the transport respawns dead workers lazily anyway
        ok = [m for m in pool if m.breaker.allow()]
        return ok or pool

    def _decoders(self) -> list[FleetMember]:
        pool = [m for m in self.members if m.active and m.role == "decode"]
        ok = [m for m in pool if m.breaker.allow()]
        return ok or pool

    # ------------------------------------------------------------- scaling
    def record_event(self, action: str, member: FleetMember,
                     reason: str) -> None:
        self.stats.scale_events.append({
            "t": asyncio.get_running_loop().time(), "action": action,
            "member": member.index, "role": member.role, "reason": reason,
            "active": len(self.active_members),
            "queued": self.backlog})
        self._event_span(f"fleet.{action}", member=member.index,
                         role=member.role, reason=reason)

    def grow(self, role: str | None = None,
             reason: str = "manual") -> FleetMember:
        """Add one member (cold worker → warm on first use)."""
        if self._closed:
            raise RuntimeError("fleet router is closed")
        if role is None:
            role = "unified"
            if self.disaggregate:
                intake = sum(len(m.loop.intake) for m in self.members)
                queued = sum(m.loop.load for m in self._routable())
                role = "decode" if intake >= queued else "prefill"
        member = self._spawn(role)
        self.record_event("grow", member, reason)
        return member

    def drain(self, member: FleetMember | None = None,
              reason: str = "manual") -> FleetMember | None:
        """Cooperatively retire one member: it leaves the routing set now,
        serves out everything it already owns, then releases its lease.
        Returns ``None`` when no member can be spared (pool at its role
        minimum) — the controller treats that as "don't shrink"."""
        pool = self.active_members
        if member is None:
            spare = [m for m in pool
                     if sum(1 for o in pool if o.role == m.role) > 1
                     or (not self.disaggregate and len(pool) > 1)]
            if not spare:
                return None
            member = min(spare, key=lambda m: (m.loop.load, -m.index))
        elif not member.active:
            return None
        member.loop.draining = True
        # owners pointing at it reroute lazily (owner not routable → reassign)
        self._arrived.set()
        self.record_event("drain", member, reason)
        return member

    def respawn(self, member: FleetMember,
                reason: str = "member died") -> FleetMember | None:
        """Replace a dead member with a fresh one of the same role and
        move its orphaned queue/intake onto the replacement.  The dead
        member's worker (if its process died too) respawns lazily in the
        transport on first use of its slot."""
        if self._closed:
            return None
        member.reaped = True
        repl = self._spawn(member.role)
        self.record_event("respawn", repl, reason)
        while member.loop.queue:
            repl.loop.queue.append(member.loop.queue.popleft())
        while member.loop.intake:
            repl.loop.intake.append(member.loop.intake.popleft())
        self._arrived.set()
        return repl

    # ------------------------------------------------------------ failover
    def _recover(self, index: int, item) -> None:
        """A member's engine loop lost a live row to a worker crash /
        state loss and replayed it (prompt + generated so far).  Record
        the failure on that member's breaker — taking it out of the
        routing set for the cooldown — and re-route the replay like any
        fresh request, which now lands on a surviving member."""
        member = next((m for m in self.members if m.index == index), None)
        if member is not None:
            member.breaker.record_failure()
            self.record_event("recover", member,
                              "row replayed after worker/state loss")
        self.stats.recoveries += 1
        request, fut = item
        if fut.done():
            return
        try:
            self.route(request, fut)
        except RuntimeError as e:
            fut.set_exception(e)

    # ------------------------------------------------------------- routing
    @property
    def backlog(self) -> int:
        """Queued-but-not-live request rows across the whole fleet."""
        n = 0
        for m in self.members:
            n += sum(1 for _, f in m.loop.queue if not f.done())
            n += len(m.loop.intake)
        return n

    def _p2c(self, targets: list[FleetMember]) -> FleetMember:
        if len(targets) == 1:
            return targets[0]
        a, b = self._rng.sample(targets, 2)
        return min((a, b), key=lambda m: (m.loop.load, m.index))

    def _radix_choose(self, prompt: Sequence[int],
                      targets: list[FleetMember]) -> tuple[FleetMember, str]:
        toks = [int(t) for t in prompt]
        h, owners = self._radix.match(toks)
        owner = None
        if h and owners:
            # deepest matched run names the member holding the most
            # shared blocks
            owner = next((m for m in targets if m.index == owners[-1]),
                         None)
        if owner is not None:
            if owner.loop.load < self.spill_factor * owner.loop.rows:
                return owner, "prefix"
            # transient overload spills to p2c WITHOUT reclaiming the
            # runs — same no-thrash rule as the "prefix" policy
            self.stats.spills += 1
            return self._p2c(targets), "p2c"
        member = self._p2c(targets)
        bs = self._radix.bs
        nb = (len(toks) // bs) * bs
        if nb:
            # claim this prompt's block-aligned head for the chosen member
            # (overwrite: traffic follows the freshest placement, and a
            # drained member's runs are reclaimed by the next claimant)
            self._radix.insert(toks[:nb], [member.index] * (nb // bs),
                               overwrite=True)
            self._radix.evict()
        return member, "p2c"

    def _choose(self, prompt: Sequence[int],
                targets: list[FleetMember]) -> tuple[FleetMember, str]:
        if self.policy == "random":
            return self._rng.choice(targets), "random"
        if self.policy == "p2c":
            return self._p2c(targets), "p2c"
        if self.policy == "radix":
            return self._radix_choose(prompt, targets)
        key = prefix_key(prompt[:self.prefix_len]
                         if self.prefix_len else prompt)
        owner = self._owners.get(key)
        if owner is not None and owner in targets:
            if owner.loop.load < self.spill_factor * owner.loop.rows:
                return owner, "prefix"
            self.stats.spills += 1
            return self._p2c(targets), "p2c"
        member = self._p2c(targets)
        self._owners[key] = member      # claim future traffic for this key
        return member, "p2c"

    def route(self, request: Request, fut: asyncio.Future) -> FleetMember:
        """Place one request on a member's queue (sync, event-loop side)."""
        targets = self._routable()
        if not targets:
            raise RuntimeError("fleet has no routable member "
                               "(all draining or done)")
        member, how = self._choose(request.prompt, targets)
        setattr(self.stats, f"routed_{how}",
                getattr(self.stats, f"routed_{how}") + 1)
        member.loop.queue.append((request, fut))
        self._arrived.set()
        return member

    async def submit(self, request: Request) -> Completion:
        """Route one request; resolves when its decode completes."""
        if self._closed:
            raise RuntimeError("fleet router is closed")
        self.start()
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        fut._t_submit = loop.time()
        self.route(request, fut)
        return await fut

    # ------------------------------------------------------------ handoff
    async def _handoff(self, items: list[dict]) -> None:
        """Prefill→decode migration: place extracted rows in the least-
        loaded decode member's intake.  The payloads are client-side
        bytes, so a decode member lost between extract and insert costs a
        re-route, not the rows."""
        decs = self._decoders()
        if not decs:
            err = RuntimeError("no decode member available for hand-off")
            for ent in items:
                if not ent["row"].fut.done():
                    ent["row"].fut.set_exception(err)
                self.batcher_stats.requests += 1
            return
        member = min(decs, key=lambda m: (m.loop.load, m.index))
        member.loop.intake.extend(items)
        self.stats.handoffs += 1
        self._event_span("fleet.handoff", rows=len(items),
                         to_member=member.index)
        self._arrived.set()

    # ------------------------------------------------------- solo fallback
    def _fallback_wave(self, item: tuple[Request, asyncio.Future]) -> None:
        """A request no arena can hold (prompt above ``prompt_cap``) is
        served as a solo wave so it is never silently starved."""
        self.batcher_stats.wave_fallbacks += 1
        task = asyncio.get_running_loop().create_task(self._run_solo(item))
        self._solo_tasks.add(task)
        task.add_done_callback(self._solo_tasks.discard)

    async def _run_solo(self, item: tuple[Request, asyncio.Future]) -> None:
        loop = asyncio.get_running_loop()
        r, fut = item
        try:
            inv_fut = await loop.run_in_executor(
                self._cpu, lambda: self._server.submit_wave([r]))
            await await_invocation(inv_fut)
            comps = await loop.run_in_executor(
                self._cpu, self._server.unpack_wave, [r], inv_fut)
        except BaseException as e:
            if not fut.done():
                fut.set_exception(e if isinstance(e, Exception)
                                  else RuntimeError(f"solo wave: {e!r}"))
            if isinstance(e, asyncio.CancelledError):
                raise
        else:
            if not fut.done():
                fut.set_result(comps[0])
        finally:
            self.batcher_stats.requests += 1

    # ------------------------------------------------------------- summary
    def summary(self) -> dict:
        st = self.stats
        return {
            "n_members": len(self.members),
            "n_active": len(self.active_members),
            "policy": self.policy,
            "disaggregated": self.disaggregate,
            "elastic": self.elastic,
            "routing": {"prefix": st.routed_prefix, "p2c": st.routed_p2c,
                        "random": st.routed_random, "spills": st.spills,
                        "prefix_route_rate": round(st.prefix_route_rate, 4)},
            "handoffs": st.handoffs,
            "recoveries": st.recoveries,
            "scale_events": list(st.scale_events),
            "members": [m.summary() for m in self.members],
            "batcher": self.batcher_stats.summary(),
        }


def run_fleet(server: LMServer, requests: Sequence[Request], *,
              concurrency: int = 32, return_stats: bool = False,
              **router_kwargs):
    """Closed-loop convenience driver: feed ``requests`` through a
    :class:`FleetRouter` with at most ``concurrency`` outstanding; returns
    completions in request order (plus the router summary when
    ``return_stats``).  This is what ``--fleet N`` runs in the serve
    launcher and benchmark."""
    async def go():
        sem = asyncio.Semaphore(max(1, concurrency))
        async with FleetRouter(server, **router_kwargs) as fleet:
            async def one(r: Request) -> Completion:
                async with sem:
                    return await fleet.submit(r)
            comps = list(await asyncio.gather(*[one(r) for r in requests]))
            return comps, fleet.summary()
    comps, summary = asyncio.run(go())
    return (comps, summary) if return_stats else comps
