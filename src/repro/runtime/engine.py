"""Iteration-level serving engine: worker-resident KV arena + step decode.

PR 4's serving path is batch-level: one deployed entry point runs prefill
*and* the whole decode scan, so a request can only join between batches
and every admission re-runs prefill from scratch.  This module splits
that monolith into the two entry points the paper's warm-state economics
actually want (ISSUE 5):

* :func:`engine_prefill` — prefill arriving prompts in a bucketed side
  buffer and *insert* each row into a worker-resident, slot-allocated
  cache arena (:mod:`repro.runtime.state`), keyed by a client-generated
  handle.  The cache never crosses the wire back; only the first decoded
  token per row returns.  Rows whose full prompt is already resident in
  the arena's prefix store skip prefill compute entirely.
* :func:`engine_decode` — advance *all* live slots ``k`` greedy steps and
  return just the ``(B, k)`` new token ids (a few hundred bytes), freeing
  evicted rows and compacting the arena when the cursor nears capacity.

Both are ordinary shippable functions (``jax_traceable=False``): the
worker imports this module, rebuilds the model from ``cfg`` and pays each
jit once per shape bucket — the same cold-start contract as every other
deployed entry point.  :class:`EngineClient` is the client half: it owns
the handle, mirrors the cursor and the prefix-store LRU (the client is
the single writer, so the mirror is exact), pins every call to one worker
via ``FunctionConfig.affinity`` on cross-process backends, and falls back
to direct :mod:`repro.runtime.state` calls when the backend shares the
client process.
"""
from __future__ import annotations

import hashlib
import itertools
import threading
import uuid
from functools import lru_cache
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models import build_model, transformer
from ..models.api import (PagedArena, SEQ_CACHE_KEYS, _attn_impl,
                          arena_init_cache, arena_supported,
                          cache_extract_rows, cache_free_rows,
                          cache_insert_rows, cache_insert_rows_masked,
                          cache_shift_left, paged_init_pool, paged_supported)
from ..obs.trace import TRACER
from ..serialization import decode_binary, encode_binary
from . import state
from .radix import RadixIndex
from .server import pack_prompts, shape_bucket

DEFAULT_QUANTUM = 8

# CONTROL verbs for arena row migration (disaggregated prefill/decode,
# ISSUE 6): a prefill worker's finished rows ship to a decode worker's
# arena as a binary-archive CONTROL body, client-relayed (the client is
# the single writer of both arenas, so its mirrors stay exact).
MIGRATE_EXTRACT_OP = "state_extract_rows"
MIGRATE_INSERT_OP = "state_insert_rows"


# ---------------------------------------------------------------- hashing --

def prefix_key(tokens: Sequence[int]) -> str:
    """Content hash of a token prefix: length-prefixed over the *raw*
    token ids, never over a padded row.  A prompt that happens to contain
    the pad id therefore cannot collide with a shorter prompt whose
    padded row looks identical (``[pad, x, y]`` vs ``[x, y]``)."""
    h = hashlib.sha256()
    h.update(len(tokens).to_bytes(8, "little"))
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.hexdigest()


def _sync(what: str):
    """``engine.sync``: a span around a device-to-host read (the entry
    waits there for the device to finish its programs)."""
    return TRACER.span("engine.sync", profile=True, what=what)


def is_state_lost(err: BaseException) -> bool:
    """The wire-reconstructed signature of a reclaimed/respawned arena."""
    return isinstance(err, KeyError) and "state handle" in str(err)


# ------------------------------------------------------- worker-side jits --

@lru_cache(maxsize=None)
def _model_for(cfg: ModelConfig):
    return build_model(cfg)


@lru_cache(maxsize=64)
def _prefill_fn(cfg: ModelConfig):
    model = _model_for(cfg)

    def run(params, tokens, lengths):
        logits, cache = model.prefill(params, {"tokens": tokens,
                                               "lengths": lengths})
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        return first, cache

    return jax.jit(run)


@lru_cache(maxsize=256)
def _insert_full_fn(cfg: ModelConfig, width: int):
    """Jitted full-batch masked insert + first-token splice: one compiled
    program per prompt-width bucket, whatever subset of slots admits."""
    def run(arena, last, rows, first, sel, mask, lengths):
        arena = cache_insert_rows_masked(cfg, arena, rows, sel, mask,
                                         lengths, width=width)
        last = jnp.where(mask, first[sel], last).astype(jnp.int32)
        return arena, last

    return jax.jit(run)


@lru_cache(maxsize=256)
def _insert_one_fn(cfg: ModelConfig, width: int):
    """Jitted single-row insert (prefix-cache hits re-insert one stored
    row at a time; shapes fixed by width, so this compiles once each)."""
    def run(arena, last, row, slot, length, first_tok):
        arena = cache_insert_rows(cfg, arena, row, slot, length,
                                  width=width, check=False)
        last = last.at[slot[0]].set(jnp.int32(first_tok))
        return arena, last

    return jax.jit(run)


@lru_cache(maxsize=256)
def _decode_fn(cfg: ModelConfig, k: int):
    model = _model_for(cfg)

    def run(params, cache, tok, free_mask):
        # eviction fused into the step program: freed rows jump their
        # ``start`` to the cursor (no valid keys — junk writes stay
        # masked) and feed the pad id.  A (B,) bool mask keeps the
        # compiled program shared across every eviction pattern, where an
        # eager per-slot update would copy the whole arena per chunk.
        if "start" in cache:
            cache = dict(cache)
            cache["start"] = jnp.where(free_mask,
                                       jnp.int32(cache["idx"]),
                                       cache["start"]).astype(jnp.int32)
        tok = jnp.where(free_mask[:, None], jnp.int32(cfg.pad_id), tok)

        def step(carry, _):
            cache, tok = carry
            logits, cache = model.decode(params, cache, tok)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            return (cache, nxt), nxt[:, 0]

        (cache, tok), toks = jax.lax.scan(step, (cache, tok), None, length=k)
        return cache, tok[:, 0], jnp.moveaxis(toks, 0, 1)   # (B, k)

    return jax.jit(run)


# ------------------------------------------------------ worker entry fns --

def engine_prefill(params, tokens, lengths, *, cfg, handle, batch, cap,
                   cursor0, miss_slots=(), store_keys=(), hit_slots=(),
                   hit_keys=(), evict_keys=(), create=True,
                   ttl_s=state.DEFAULT_TTL_S):
    """Prefill + slot-insert entry point (worker side).

    ``tokens``/``lengths`` carry the prefix-cache *misses* packed by the
    client (``None`` when every row hit); ``miss_slots`` names the arena
    slot per packed row (filler rows beyond it are discarded).
    ``store_keys`` (parallel to ``miss_slots``) asks the worker to retain
    a row's fresh cache in the arena's prefix store; ``hit_slots`` /
    ``hit_keys`` are rows served straight from it; ``evict_keys`` applies
    the client's LRU decisions.  Returns ``{"first": first token per
    inserted row (miss order then hit order), "idx": cursor}`` and the
    call's prompt tokens: prefilled (``prefill_tokens``, and
    ``padded_tokens`` as the program ran them) and served from the store
    (``prefix_tokens``).  The cache itself stays resident and is never
    serialized back.
    """
    def make():
        return {"cache": arena_init_cache(cfg, batch, cap, cursor0),
                "last": jnp.full((batch,), cfg.pad_id, jnp.int32),
                "prefix": {}, "prefix_tokens": 0, "cap": cap,
                "cursor0": cursor0, "cfg": cfg}

    # ``create`` distinguishes building a fresh arena from renewing one
    # that must already exist: an admission into an arena holding live
    # rows must NOT silently recreate an expired lease (the live rows
    # would decode garbage against a blank cache) — it must surface the
    # state-lost KeyError so the scheduler fails those rows and rebuilds.
    a = state.lease(handle, ttl_s=float(ttl_s),
                    make=make if create else None)
    cache, last = a["cache"], a["last"]
    for key in evict_keys:
        ent = a["prefix"].pop(key, None)
        if ent is not None:
            a["prefix_tokens"] -= ent[1]

    first_out: list[int] = []
    real = padded = adopted = 0
    if len(miss_slots):
        n = len(miss_slots)
        with _sync("idx"):
            idx = int(cache["idx"])
        if idx < int(tokens.shape[1]) and cfg.family != "ssm":
            raise ValueError(
                f"prefill width {int(tokens.shape[1])} exceeds arena "
                f"cursor {idx}")
        tokens = jnp.asarray(tokens)
        lengths = np.asarray(lengths, np.int32)
        width = int(tokens.shape[1])
        real, padded = int(lengths[:n].sum()), int(tokens.shape[0]) * width
        first, pcache = _prefill_fn(cfg)(params, tokens,
                                         jnp.asarray(lengths))
        with _sync("first"):
            first = np.asarray(first)
        for j, key in enumerate(store_keys):
            if key is None or key in a["prefix"]:
                continue
            row = cache_extract_rows(cfg, pcache, (j,))
            a["prefix"][key] = (row, int(lengths[j]), int(first[j]), width)
            a["prefix_tokens"] += int(lengths[j])
        # shape-stable masked insert: sel routes packed row j to its slot
        rows_b = last.shape[0]
        sel = np.zeros((rows_b,), np.int32)
        mask = np.zeros((rows_b,), bool)
        len_by_slot = np.zeros((rows_b,), np.int32)
        for j, slot in enumerate(miss_slots):
            sel[slot], mask[slot] = j, True
            len_by_slot[slot] = lengths[j]
        if first.shape[0] < rows_b:
            raise RuntimeError("prefill batch smaller than the arena: "
                               "pack with min_rows == arena rows")
        cache, last = _insert_full_fn(cfg, width)(
            cache, last, pcache, jnp.asarray(first),
            jnp.asarray(sel), jnp.asarray(mask), jnp.asarray(len_by_slot))
        first_out.extend(int(t) for t in first[:n])

    for slot, key in zip(hit_slots, hit_keys):
        ent = a["prefix"].get(key)
        if ent is None:
            raise KeyError(
                f"prefix key {key[:12]}… not resident for state handle "
                f"{handle!r} (stale client mirror)")
        row, length, t0, width = ent
        adopted += length
        cache, last = _insert_one_fn(cfg, width)(
            cache, last, row, jnp.asarray([slot], jnp.int32),
            jnp.asarray([length], jnp.int32), t0)
        first_out.append(t0)

    a["cache"], a["last"] = cache, last
    with _sync("idx"):
        idx = int(cache["idx"])
    return {"first": np.asarray(first_out, np.int32), "idx": idx,
            "prefill_tokens": real, "padded_tokens": padded,
            "prefix_tokens": adopted}


def engine_decode(params, *, cfg, handle, k, free_slots=(),
                  ttl_s=state.DEFAULT_TTL_S):
    """Decode-step entry point (worker side): free evicted rows, compact
    if the cursor nears capacity, advance every slot ``k`` greedy steps.
    Returns ``{"tokens": (B, k) ids, "idx": post-step cursor}``."""
    a = state.get(handle, ttl_s=float(ttl_s))
    cache, last = a["cache"], a["last"]
    k = int(k)
    free_mask = np.zeros((last.shape[0],), bool)
    if len(free_slots):
        free_mask[np.asarray(free_slots, np.int64)] = True
    if cfg.family != "ssm":
        cap = a["cap"]
        with _sync("idx"):
            idx = int(cache["idx"])
        if idx + k >= cap:
            # compaction bound: minimum start over rows that are NOT being
            # freed this call (schedulers pass every non-live slot in
            # free_slots each chunk, so idle freed slots cannot pin the
            # shift at their freeze-time start).  Clamped so the cursor
            # never drops below the prompt-width bucket — otherwise the
            # next admission's insert would have no room to align against.
            starts = np.asarray(cache["start"])
            starts = np.where(free_mask, int(cache["idx"]), starts)
            shift = min(int(starts.min()),
                        int(cache["idx"]) - int(a.get("cursor0", 0)))
            cache = cache_shift_left(cfg, cache, shift)
            if int(cache["idx"]) + k >= cap:
                raise RuntimeError(
                    f"cache arena {handle!r} full: cursor "
                    f"{int(cache['idx'])} + {k} exceeds capacity {cap} "
                    "even after compaction (a live row spans the arena)")
    cache, last, toks = _decode_fn(cfg, k)(params, cache, last[:, None],
                                           jnp.asarray(free_mask))
    a["cache"], a["last"] = cache, last
    with _sync("tokens"):
        toks, idx = np.asarray(toks), int(cache["idx"])
    return {"tokens": toks, "idx": idx}


# ------------------------------------------------- paged-arena entry fns --
# ISSUE 7: the paged twin of the slot entry points above.  The worker
# keeps a refcounted pool of fixed-size KV blocks plus per-row block
# tables (host accounting in models.api.PagedArena, device pools updated
# by the jitted fns below); prefill is CHUNKED — each call advances
# pending rows by at most ``budget`` real tokens, so a long prompt never
# stalls live decode rows for more than one chunk — and the prompt-prefix
# store is a radix index over block-aligned token runs: rows sharing a
# prefix share physical blocks copy-free, and a partial hit skips prefill
# for the matched head only.

@lru_cache(maxsize=256)
def _paged_chunk_fn(cfg: ModelConfig, c: int):
    """One chunk of continued prefill for one row (B == 1, width c)."""
    impl = _attn_impl(cfg)

    def run(params, pool_k, pool_v, tokens, table, m, n_real):
        logits, pk, pv = transformer.lm_prefill_paged_chunk(
            params, cfg, tokens, pool_k, pool_v, table, m, n_real,
            attn_impl=impl)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        return first, pk, pv

    return jax.jit(run)


@lru_cache(maxsize=256)
def _paged_decode_fn(cfg: ModelConfig, k: int):
    impl = _attn_impl(cfg)

    def run(params, pool_k, pool_v, table, lens, live, last):
        tok = jnp.where(live, last, jnp.int32(cfg.pad_id))[:, None]

        def step(carry, _):
            pk, pv, lens, tok = carry
            logits, pk, pv = transformer.lm_decode_paged(
                params, cfg, pk, pv, table, lens, live, tok, attn_impl=impl)
            nxt = jnp.where(live, jnp.argmax(logits, -1).astype(jnp.int32),
                            jnp.int32(cfg.pad_id))
            lens = lens + live.astype(jnp.int32)
            return (pk, pv, lens, nxt[:, None]), nxt

        (pk, pv, lens, tok), toks = jax.lax.scan(
            step, (pool_k, pool_v, lens, tok), None, length=k)
        return pk, pv, tok[:, 0], jnp.moveaxis(toks, 0, 1)       # (B, k)

    return jax.jit(run)


def _paged_reserve(pa: PagedArena, radix: RadixIndex, slot: int,
                   n_tokens: int, handle) -> None:
    """Allocate blocks so row ``slot`` can hold ``n_tokens``; on pool
    exhaustion, evict LRU radix runs (refcount drop — blocks free only if
    no live row shares them) and retry before giving up."""
    while True:
        try:
            pa.ensure(slot, n_tokens)
            return
        except IndexError:
            dropped = radix.evict_blocks(1)
            if not dropped:
                raise RuntimeError(
                    f"paged arena {handle!r} out of blocks: "
                    f"{pa.occupancy()} and nothing evictable") from None
            pa.ref_dec(dropped)


def _paged_match(pa: PagedArena, radix: RadixIndex, slot: int,
                 toks: list, done: int) -> int:
    """Adopt any radix-shared prefix blocks beyond ``done`` (refcount++,
    copy-free).  The match is capped one block short of the full prompt so
    at least one token always re-prefills — the chunk path needs a real
    last-token forward for the first output logits."""
    bs = pa.bs
    if done % bs:
        return done
    h, payloads = radix.match(toks)
    h = min(h, ((len(toks) - 1) // bs) * bs)
    if h <= done:
        return done
    ids = payloads[done // bs:h // bs]
    if any(pa.table[slot, done // bs:h // bs]):
        return done                      # row already allocated past here
    pa.ref_inc(ids)
    pa.table[slot, done // bs:h // bs] = ids
    pa.owned[slot].extend(int(i) for i in ids)
    return h


def engine_paged_prefill(params, *, cfg, handle, batch, blocks, table_width,
                         block_size, admit=(), free=(), budget=0,
                         radix_tokens=1 << 16, create=True,
                         ttl_s=state.DEFAULT_TTL_S):
    """Paged prefill entry point: admit rows, advance chunked prefill.

    ``free``: slots evicted since the last call — released FIRST (refcount
    drops), because a slot must give its blocks back before the same slot
    id is re-admitted: an un-released table row would alias the new row's
    writes onto blocks the radix index may still share with live rows.
    ``admit``: ``[(slot, prompt_tokens), ...]`` new rows (the worker is
    authoritative for prefix matching — no client mirror).  Each call then
    advances pending rows FIFO by at most ``budget`` real tokens total
    (``budget <= 0`` = finish everything), so one call's prefill stall is
    bounded no matter how long the prompt.  Completed rows land live with
    their first decoded token; their full blocks are inserted into the
    radix index (refcount++) and the index is LRU-evicted back under
    ``radix_tokens``.  Returns per-slot progress + pool occupancy.
    """
    def make():
        pool = paged_init_pool(cfg, blocks, block_size)
        return {"paged": True, "cfg": cfg,
                "pool_k": pool["k"], "pool_v": pool["v"],
                "pa": PagedArena(batch, blocks, table_width, block_size),
                "radix": RadixIndex(block_size, radix_tokens),
                "pending": {}, "order": [],
                "last": np.full((batch,), cfg.pad_id, np.int32),
                "prefix_tokens": 0}

    a = state.lease(handle, ttl_s=float(ttl_s),
                    make=make if create else None)
    pa, radix = a["pa"], a["radix"]
    pending, order = a["pending"], a["order"]

    for slot in free:
        slot = int(slot)
        pa.ref_dec(radix.evict())
        pa.release(slot)
        pending.pop(slot, None)

    adopted = 0          # prompt tokens this call took from the radix index
    for slot, toks in admit:
        slot = int(slot)
        toks = [int(t) for t in toks]
        matched = _paged_match(pa, radix, slot, toks, 0)
        adopted += matched
        pending[slot] = {"tokens": toks, "done": matched, "matched": matched}
        order.append(slot)

    spent = padded = 0
    out: dict[int, dict] = {}
    while order:
        slot = order[0]
        ent = pending.get(slot)
        if ent is None:                       # freed mid-prefill
            order.pop(0)
            continue
        toks, done0 = ent["tokens"], ent["done"]
        done = _paged_match(pa, radix, slot, toks, done0)
        adopted += done - done0
        need = len(toks) - done
        room = (len(toks) if budget <= 0
                else budget - spent)
        c_real = min(need, room)
        if c_real <= 0:
            break                             # budget exhausted this call
        _paged_reserve(pa, radix, slot, done + c_real, handle)
        c_b = shape_bucket(c_real)
        chunk = np.full((1, c_b), cfg.pad_id, np.int32)
        chunk[0, :c_real] = toks[done:done + c_real]
        first, pk, pv = _paged_chunk_fn(cfg, c_b)(
            params, a["pool_k"], a["pool_v"], jnp.asarray(chunk),
            jnp.asarray(pa.table[slot:slot + 1]),
            jnp.int32(done), jnp.int32(c_real))
        a["pool_k"], a["pool_v"] = pk, pv
        done += c_real
        spent += c_real
        padded += c_b
        ent["done"] = done
        if done == len(toks):
            order.pop(0)
            pending.pop(slot)
            pa.len[slot] = done
            pa.live[slot] = True
            with _sync("first"):
                t0 = int(np.asarray(first)[0])
            a["last"][slot] = t0
            nb_full = (done // pa.bs) * pa.bs
            if nb_full and radix_tokens > 0:
                new = radix.insert(toks[:nb_full],
                                   list(pa.table[slot, :nb_full // pa.bs]))
                pa.ref_inc(new)
                pa.ref_dec(radix.evict())
            out[slot] = {"live": True, "first": t0, "done": done,
                         "matched": ent["matched"], "total": done}
        else:
            out[slot] = {"live": False, "first": None, "done": done,
                         "matched": ent["matched"], "total": len(toks)}
    a["prefix_tokens"] = radix.tokens
    occ = pa.occupancy()
    occ["radix_tokens"] = radix.tokens
    a["occupancy"] = occ
    # str slot keys: the wire serializer only carries str-keyed dicts
    return {"slots": {str(s): v for s, v in out.items()},
            "pending": len(pending), "occupancy": occ,
            "prefill_tokens": spent, "padded_tokens": padded,
            "prefix_tokens": adopted}


def engine_paged_decode(params, *, cfg, handle, k, free_slots=(),
                        ttl_s=state.DEFAULT_TTL_S):
    """Paged decode-step entry point: release evicted rows (refcount drops
    — the paged analogue of compaction), reserve blocks for ``k`` new
    tokens per live row, advance every live row ``k`` greedy steps."""
    a = state.get(handle, ttl_s=float(ttl_s))
    pa, radix = a["pa"], a["radix"]
    k = int(k)
    for slot in free_slots:
        slot = int(slot)
        pa.ref_dec(radix.evict())            # keep index inside its budget
        pa.release(slot)
        a["pending"].pop(slot, None)
    for slot in np.nonzero(pa.live)[0]:
        _paged_reserve(pa, radix, int(slot), int(pa.len[slot]) + k, handle)
    pk, pv, last, toks = _paged_decode_fn(cfg, k)(
        params, a["pool_k"], a["pool_v"], jnp.asarray(pa.table),
        jnp.asarray(pa.len), jnp.asarray(pa.live), jnp.asarray(a["last"]))
    a["pool_k"], a["pool_v"] = pk, pv
    with _sync("tokens"):
        a["last"] = np.asarray(last).astype(np.int32)
        toks = np.asarray(toks)
    pa.len[pa.live] += k
    occ = pa.occupancy()
    occ["radix_tokens"] = radix.tokens
    a["occupancy"] = occ
    return {"tokens": toks, "occupancy": occ}


# ------------------------------------------------------- row migration ------

def migration_control(op: str, data: dict, body: bytes = b""):
    """Worker-side CONTROL surface for arena row migration — runs wherever
    the state registry lives (the pinned worker process on cross-process
    backends, the client process otherwise).  Returns ``(reply_data,
    reply_body)``; errors raise (the worker host wraps them in ERROR
    envelopes, so an expired arena surfaces as the usual state-lost
    ``KeyError`` client-side)."""
    if op == MIGRATE_EXTRACT_OP:
        return _migrate_extract(data)
    if op == MIGRATE_INSERT_OP:
        return _migrate_insert(data, body)
    raise ValueError(f"unknown migration op {op!r}")


def _migrate_extract(data: dict):
    """Window-extract rows from a resident arena (and free their slots):
    the prefill half of a prefill→decode hand-off.  The body is a binary
    archive of ``{"rows", "lengths", "last"}`` with the row axis at
    position 1 everywhere (:func:`cache_extract_rows` layout), seq keys
    trimmed to the trailing ``width`` positions so only each row's live
    window crosses the wire."""
    a = state.get(data["handle"],
                  ttl_s=float(data.get("ttl_s") or state.DEFAULT_TTL_S))
    cfg = a["cfg"]
    cache, last = a["cache"], a["last"]
    slots = [int(s) for s in data["slots"]]
    rows = cache_extract_rows(cfg, cache, slots)
    last_np = np.asarray(last)[slots].astype(np.int64)
    if cfg.family == "ssm":
        width = 0                        # O(1) state: whole-row, no window
        lengths = np.asarray(data.get("lengths", [0] * len(slots)), np.int64)
        payload = {k: np.asarray(v) for k, v in rows.items()
                   if k not in ("idx", "start")}
    else:
        idx = int(cache["idx"])
        width = int(data.get("width") or idx)
        lengths = (idx - np.asarray(cache["start"])[slots]).astype(np.int64)
        if width > idx or (len(lengths) and int(lengths.max()) > width):
            raise ValueError(
                f"migration window {width} cannot carry rows of lengths "
                f"{lengths.tolist()} from an arena at cursor {idx}")
        payload = {}
        for k, v in rows.items():
            if k in ("idx", "start"):
                continue
            v = np.asarray(v)
            payload[k] = v[:, :, idx - width:idx] \
                if k in SEQ_CACHE_KEYS else v
        if bool(data.get("free", True)):
            a["cache"] = cache_free_rows(cfg, cache, slots)
    body = encode_binary({"rows": payload, "lengths": lengths,
                          "last": last_np})
    return ({"ok": True, "width": width,
             "lengths": [int(x) for x in lengths],
             "last": [int(x) for x in last_np]}, body)


def _migrate_insert(data: dict, body: bytes):
    """Insert migrated rows into a resident arena: the decode half.  The
    target arena's cursor must already sit at or past the migration width
    (both sides bucket ``prompt_cap`` identically, and decode compaction
    clamps the cursor at ``cursor0``, so this holds by construction)."""
    a = state.get(data["handle"],
                  ttl_s=float(data.get("ttl_s") or state.DEFAULT_TTL_S))
    cfg = a["cfg"]
    cache, last = a["cache"], a["last"]
    blob = decode_binary(body)
    rows = {k: jnp.asarray(np.ascontiguousarray(v))
            for k, v in blob["rows"].items()}
    lengths = np.asarray(blob["lengths"], np.int64)
    last_in = np.asarray(blob["last"], np.int64)
    slots = [int(s) for s in data["slots"]]
    width = int(data.get("width") or 0)
    if cfg.family != "ssm" and width > int(cache["idx"]):
        raise ValueError(
            f"migrated width {width} exceeds arena cursor "
            f"{int(cache['idx'])} for state handle {data['handle']!r}")
    cache = cache_insert_rows(cfg, cache, rows, slots, lengths,
                              width=width, check=False)
    last = last.at[jnp.asarray(slots, jnp.int32)].set(
        jnp.asarray(last_in, jnp.int32))
    a["cache"], a["last"] = cache, last
    return ({"ok": True, "idx": int(cache["idx"])}, b"")


def split_rows(blob: bytes) -> list[dict]:
    """Decode an extraction body into per-row client-side entries, so a
    router can scatter one prefill group across several decode workers.
    Each entry: ``{"rows": {key: (L, 1, ...)}, "length", "last"}``."""
    doc = decode_binary(blob)
    rows, lengths, last = doc["rows"], doc["lengths"], doc["last"]
    n = len(np.asarray(lengths))
    return [{"rows": {k: np.asarray(v)[:, j:j + 1] for k, v in rows.items()},
             "length": int(np.asarray(lengths)[j]),
             "last": int(np.asarray(last)[j])}
            for j in range(n)]


def merge_rows(entries: Sequence[dict]) -> bytes:
    """Concatenate per-row entries (row axis 1) back into one insert body."""
    keys = entries[0]["rows"].keys()
    rows = {k: np.concatenate([e["rows"][k] for e in entries], axis=1)
            for k in keys}
    return encode_binary(
        {"rows": rows,
         "lengths": np.asarray([e["length"] for e in entries], np.int64),
         "last": np.asarray([e["last"] for e in entries], np.int64)})


# ------------------------------------------------------------ client half --

_affinity_counter = itertools.count()


class EngineClient:
    """Client handle for one worker-resident decode arena.

    Owns the state handle, the cursor mirror and the prefix-LRU mirror
    (exact: this client is the arena's only writer), and the bound entry
    points — pinned to one worker via ``affinity`` on cross-process
    backends.  Methods are synchronous and must be driven by a single
    scheduler loop (the iteration-level batcher runs one loop per engine).
    """

    def __init__(self, server, *, rows: int, prompt_cap: int = 64,
                 quantum: int = DEFAULT_QUANTUM, prefix_tokens: int = 1 << 16,
                 ttl_s: float = state.DEFAULT_TTL_S, cap: int | None = None,
                 affinity: int | None = None, paged: bool = False,
                 block_size: int = 16, prefill_budget: int | None = None,
                 pool_blocks: int | None = None):
        cfg = server.cfg
        if not arena_supported(cfg):
            raise ValueError(f"family {cfg.family!r} does not support "
                             "slot-arena serving (wave fallback only)")
        self.server = server
        self.cfg = cfg
        self.rows = int(rows)
        self.quantum = shape_bucket(max(1, quantum))
        self.cursor0 = shape_bucket(max(1, prompt_cap))
        # Paged serving needs the block-pool KV layout; ssm state is O(1)
        # per row (no KV to page) and already admits arbitrary prompt
        # lengths from the slot path, so a paged request degrades to the
        # slot arena there — same contract, nothing to page.
        self.paged = bool(paged) and cfg.family != "ssm" \
            and paged_supported(cfg)
        if self.paged:
            self.block_size = shape_bucket(max(1, block_size))
            # per-row token capacity; MUST stay a power of two — the
            # gathered table view's reduction width is what keeps paged
            # decode bit-identical to the contiguous solo path
            self.cap = shape_bucket(cap) if cap is not None else \
                shape_bucket(4 * max(self.cursor0,
                                     server.max_new + self.quantum))
            self.table_width = self.cap // self.block_size
            self.pool_blocks = (int(pool_blocks) if pool_blocks is not None
                                else 1 + self.rows * self.table_width)
            self.prefill_budget = (int(prefill_budget)
                                   if prefill_budget is not None
                                   else max(4 * self.quantum, 16))
        else:
            self.cap = int(cap) if cap is not None else shape_bucket(
                self.cursor0 + max(4 * self.quantum, 2 * server.max_new))
        self.ttl_s = float(ttl_s)
        self.affinity = (next(_affinity_counter) if affinity is None
                         else int(affinity))
        self.handle = uuid.uuid4().hex
        self.prefix_budget = int(prefix_tokens)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.occupancy: dict = {}
        self._cursor = self.cursor0
        self._prefix: dict[str, int] = {}       # key -> token count, LRU order
        self._prefix_total = 0
        self._closed = False
        self._hb_thread: threading.Thread | None = None
        self._hb_stop: threading.Event | None = None
        sess = server.session
        self._local_state = not sess.backend.capabilities.cross_process
        common = dict(memory_mb=server._memory_mb, serializer="binary",
                      affinity=self.affinity)
        if self.paged:
            self._f_prefill = sess.function(
                engine_paged_prefill, name=f"engine_paged_prefill_{cfg.name}",
                jax_traceable=False, **common)
            self._f_decode = sess.function(
                engine_paged_decode, name=f"engine_paged_decode_{cfg.name}",
                jax_traceable=False, **common)
        else:
            self._f_prefill = sess.function(
                engine_prefill, name=f"engine_prefill_{cfg.name}",
                jax_traceable=False, **common)
            self._f_decode = sess.function(
                engine_decode, name=f"engine_decode_{cfg.name}",
                jax_traceable=False, **common)

    # ------------------------------------------------------------ sizing --
    def fits(self, prompt_len: int, max_new: int) -> bool:
        """Whether a request can ever live in this arena: its prompt must
        fit below the initial cursor and its whole span (prompt + decode +
        one quantum of slack) below capacity after compaction.  Paged
        arenas have no prompt-cap bound — long prompts chunk-prefill —
        only the per-row table capacity."""
        if self.cfg.family == "ssm":
            return True                      # O(1) state: no capacity bound
        if self.paged:
            return prompt_len + max_new + 2 * self.quantum <= self.cap
        return prompt_len <= self.cursor0 and \
            self.cursor0 + max_new + 2 * self.quantum <= self.cap

    @property
    def cursor(self) -> int:
        return self._cursor

    # ----------------------------------------------------------- prefix --
    def _prefix_plan(self, prompts):
        """Split an admission group into prefix hits and misses, and emit
        the store/evict commands that keep the worker's store equal to the
        client's LRU mirror (LRU by token count, budget ``prefix_tokens``).

        A key stored *and* LRU-evicted within the same plan is cancelled
        out client-side (store slot nulled, no evict emitted): the worker
        applies evicts before stores, so emitting both would leak the
        entry past the budget forever (the mirror forgets a key the
        worker still holds)."""
        hits, misses, store, evict = [], [], [], []
        added_at: dict[str, int] = {}        # keys stored by THIS plan
        for i, p in enumerate(prompts):
            key = prefix_key(p)
            if key in self._prefix:
                self._prefix[key] = self._prefix.pop(key)   # LRU touch
                hits.append((i, key))
                continue
            misses.append(i)
            if self.prefix_budget and len(p) <= self.prefix_budget:
                while self._prefix and \
                        self._prefix_total + len(p) > self.prefix_budget:
                    old, n = next(iter(self._prefix.items()))
                    del self._prefix[old]
                    self._prefix_total -= n
                    if old in added_at:
                        store[added_at.pop(old)] = None     # never stored
                    else:
                        evict.append(old)
                self._prefix[key] = len(p)
                self._prefix_total += len(p)
                added_at[key] = len(store)
                store.append(key)
            else:
                store.append(None)
        self.prefix_hits += len(hits)
        self.prefix_misses += len(misses)
        return hits, misses, store, evict

    # ------------------------------------------------------------- calls --
    def _params(self):
        ref = self.server._params_ref
        if ref is None or self._closed:
            raise RuntimeError("engine is closed (or its LMServer released "
                               "the params artifact)")
        return ref

    def submit_admit(self, items, create: bool = True, free_slots=()):
        """Pack and dispatch one admission group.

        ``items``: ``[(slot, prompt), ...]``.  Returns ``(future,
        slot_order)`` — the future resolves to the worker reply, with
        first tokens aligned to ``slot_order`` (misses first, then hits).
        ``create=False`` asserts the arena already exists (the scheduler
        has live rows in it): an expired lease then surfaces as state
        lost instead of being silently rebuilt under those rows.

        Paged mode sends the raw prompts (the worker's radix index is
        authoritative for prefix matching — no client mirror) plus the
        slots freed since the last call (``free_slots``, released
        worker-side before any slot is re-admitted); the reply is
        per-slot chunked-prefill progress, folded via
        :meth:`observe_paged_prefill`.  ``free_slots`` is ignored on the
        slot path (idle slots are masked by the decode step instead).
        """
        params = self._params()
        if self.paged:
            admit = tuple((int(s), tuple(int(t) for t in p))
                          for s, p in items)
            fut = self._f_prefill.submit(
                params, cfg=self.cfg, handle=self.handle, batch=self.rows,
                blocks=self.pool_blocks, table_width=self.table_width,
                block_size=self.block_size, admit=admit,
                free=tuple(int(s) for s in free_slots),
                budget=self.prefill_budget,
                radix_tokens=self.prefix_budget, create=bool(create),
                ttl_s=self.ttl_s)
            return fut, [s for s, _ in items]
        slots = [s for s, _ in items]
        prompts = [p for _, p in items]
        hits, misses, store, evict = self._prefix_plan(prompts)
        miss_slots = tuple(slots[i] for i in misses)
        hit_slots = tuple(slots[i] for i, _ in hits)
        hit_keys = tuple(k for _, k in hits)
        if misses:
            # min_rows pins the admission batch's row bucket to the arena
            # size: exactly ONE compiled prefill shape per prompt-width
            # bucket ever exists (same trade the batch-level scheduler
            # makes via submit_wave min_rows) — padded filler compute in
            # exchange for never compiling mid-serve
            tokens, lengths = pack_prompts([prompts[i] for i in misses],
                                           pad=self.cfg.pad_id,
                                           min_rows=self.rows)
            tokens, lengths = jnp.asarray(tokens), jnp.asarray(lengths)
        else:
            tokens = lengths = None
        fut = self._f_prefill.submit(
            params, tokens, lengths, cfg=self.cfg, handle=self.handle,
            batch=self.rows, cap=self.cap, cursor0=self.cursor0,
            miss_slots=miss_slots, store_keys=tuple(store),
            hit_slots=hit_slots, hit_keys=hit_keys,
            evict_keys=tuple(evict), create=bool(create), ttl_s=self.ttl_s)
        return fut, list(miss_slots) + list(hit_slots)

    def submit_prefill_step(self, free_slots=()):
        """Paged only: advance pending chunked prefills by one budget's
        worth of tokens (no new admissions).  Returns the future."""
        return self._f_prefill.submit(
            self._params(), cfg=self.cfg, handle=self.handle,
            batch=self.rows, blocks=self.pool_blocks,
            table_width=self.table_width, block_size=self.block_size,
            admit=(), free=tuple(int(s) for s in free_slots),
            budget=self.prefill_budget,
            radix_tokens=self.prefix_budget, create=False, ttl_s=self.ttl_s)

    def submit_step(self, k: int, free_slots=()):
        """Dispatch one ``k``-step decode chunk (optionally freeing evicted
        slots first); returns the invocation future."""
        return self._f_decode.submit(
            self._params(), cfg=self.cfg, handle=self.handle, k=int(k),
            free_slots=tuple(free_slots), ttl_s=self.ttl_s)

    def observe(self, reply: dict) -> dict:
        """Fold a worker reply into the client mirrors (cursor /
        occupancy)."""
        if self.paged:
            if "occupancy" in reply:
                self.occupancy = dict(reply["occupancy"])
            return reply
        self._cursor = int(reply["idx"])
        return reply

    def observe_paged_prefill(self, reply: dict) -> dict:
        """Fold a paged prefill reply: occupancy mirror + prefix counters
        (a slot whose matched head is non-empty counts as a prefix hit —
        the paged analogue of the exact-match store hit)."""
        self.observe(reply)
        for info in reply.get("slots", {}).values():
            if info.get("live"):
                if info.get("matched", 0) > 0:
                    self.prefix_hits += 1
                else:
                    self.prefix_misses += 1
        return reply

    # -------------------------------------------------------- migration --
    def control(self, op: str, body: bytes = b"", **data):
        """One state CONTROL verb against this engine's pinned worker
        (direct registry call on in-process backends).  Returns
        ``(reply_data, reply_body)``."""
        if self._local_state:
            if op in (MIGRATE_EXTRACT_OP, MIGRATE_INSERT_OP):
                return migration_control(op, data, body)
            return state.control(op, data), b""
        backend = self.server.session.backend
        reply = dict(backend.state_control(self.affinity, op, body=body,
                                           **data))
        return reply, reply.pop("_body", b"")

    def extract_rows(self, slots, *, free: bool = True) -> list[dict]:
        """Pull finished rows out of this arena (freeing their slots by
        default) as per-row client-side entries — the prefill half of a
        disaggregated hand-off.  Synchronous round-trip; run it off the
        event loop like every other engine call."""
        _, body = self.control(
            MIGRATE_EXTRACT_OP, handle=self.handle,
            slots=tuple(int(s) for s in slots),
            width=self.cursor0 if self.cfg.family != "ssm" else 0,
            free=bool(free), ttl_s=self.ttl_s)
        return split_rows(body)

    def insert_rows(self, slots, entries) -> None:
        """Insert migrated per-row entries into this arena's ``slots`` —
        the decode half.  The arena must already exist (``submit_admit([])``
        creates one); an expired lease raises the state-lost ``KeyError``."""
        width = 0          # read the window off the rows themselves: the
        for k, v in entries[0]["rows"].items():   # source arena chose it
            if k in SEQ_CACHE_KEYS:
                width = int(np.asarray(v).shape[2])
                break
        reply, _ = self.control(
            MIGRATE_INSERT_OP, body=merge_rows(entries),
            handle=self.handle, slots=tuple(int(s) for s in slots),
            width=width, ttl_s=self.ttl_s)
        self._cursor = int(reply.get("idx", self._cursor))

    def choose_k(self, max_remaining: int) -> int:
        """Decode-chunk length: the quantum, shrunk (to a pow2 bucket, so
        compiled step programs stay shared) when every live row is nearly
        done — bounded overshoot, bounded compile variants."""
        return shape_bucket(max(1, min(self.quantum, max_remaining)))

    # --------------------------------------------------------- heartbeat --
    def renew_lease(self) -> bool:
        """Extend this arena's lease WITHOUT touching its data — the
        ``state_renew`` heartbeat verb (ISSUE 10).  Returns whether the
        handle was still resident; any transport failure reads as "not
        renewed" (the next engine call will surface the real error)."""
        try:
            reply, _ = self.control("state_renew", handle=self.handle,
                                    ttl_s=self.ttl_s)
            return bool(reply.get("renewed", False))
        except Exception:
            return False

    def start_heartbeat(self, interval_s: float | None = None) -> None:
        """Run a daemon thread renewing the lease every ``ttl/3`` (or
        ``interval_s``).  ``get``/``lease`` renew only on touch, so a long
        client-side stall between engine calls — a chaos-injected straggle,
        a GC pause — would otherwise expire the lease under LIVE rows.  A
        separate thread keeps the lease honest precisely when the loop
        thread is stuck waiting.  Reads ``self.handle`` each beat, so it
        follows :meth:`reset` to the replacement arena automatically."""
        if self._hb_thread is not None or self._closed:
            return
        interval = (float(interval_s) if interval_s is not None
                    else self.ttl_s / 3.0)
        stop = threading.Event()

        def beat() -> None:
            while not stop.wait(interval):
                self.renew_lease()

        self._hb_stop = stop
        self._hb_thread = threading.Thread(
            target=beat, name=f"repro-heartbeat-{self.handle[:8]}",
            daemon=True)
        self._hb_thread.start()

    def stop_heartbeat(self) -> None:
        if self._hb_stop is not None:
            self._hb_stop.set()
        self._hb_thread = None
        self._hb_stop = None

    # ------------------------------------------------------------- reset --
    def reset(self) -> None:
        """After state loss (worker respawn / lease expiry): new handle,
        cold mirrors.  The next admission rebuilds the arena."""
        self.handle = uuid.uuid4().hex
        self._cursor = self.cursor0
        self._prefix.clear()
        self._prefix_total = 0

    def close(self) -> None:
        """Release the worker-side lease (best effort, idempotent)."""
        if self._closed:
            return
        self._closed = True
        self.stop_heartbeat()
        try:
            if self._local_state:
                state.release(self.handle)
            else:
                backend = self.server.session.backend
                ctrl = getattr(backend, "state_control", None)
                if ctrl is not None:
                    ctrl(self.affinity, "state_release", handle=self.handle)
        except Exception:
            pass                    # lease TTL reclaims it regardless
