"""Unified model API: one ``Model`` facade per architecture family.

  model.init(key)                  -> (params, logical_specs)
  model.forward(params, batch)     -> (logits (B,S,V), metrics)   [train]
  model.prefill(params, batch)     -> (last logits (B,V), cache)
  model.decode(params, cache, tok) -> (logits (B,V), cache')
  model.init_cache(batch, cap)     -> family-specific cache pytree

Ragged batches: ``batch["lengths"]`` (B,) int32 marks how many REAL tokens
each left-padded row holds (see ``runtime/server.pack_prompts``).  Every
family masks pad slots out of attention / gates them out of recurrent
state, and attention-family caches carry the per-row first valid slot as
``cache["start"]`` so decode keeps masking them — greedy decode of a
prompt is invariant to the batch it was packed into.

``input_specs(cfg, shape)`` produces ShapeDtypeStruct stand-ins for every
entry-point input — the shape-only payloads the dry-run lowers against
(no allocation), mirroring how Cppless deploys against abstract payloads.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, ShapeConfig
from . import encdec, hybrid, rwkv_model, transformer


@dataclass
class Model:
    cfg: ModelConfig
    init: Callable
    forward: Callable
    prefill: Callable
    decode: Callable
    init_cache: Callable


def _attn_impl(cfg: ModelConfig) -> str:
    """pallas on the TPU runtime; the query-chunked XLA path elsewhere
    (same math, flash-like memory; SPMD-partitionable, unlike interpret)."""
    if cfg.attn_impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return cfg.attn_impl


def build_model(cfg: ModelConfig) -> Model:
    impl = _attn_impl(cfg)

    if cfg.family in ("dense", "moe", "vlm"):
        def forward(p, batch):
            return transformer.lm_forward(
                p, cfg, tokens=batch.get("tokens"),
                embeds=batch.get("embeds"), pos3d=batch.get("pos3d"),
                attn_impl=impl, lengths=batch.get("lengths"))

        def prefill(p, batch):
            return transformer.lm_prefill(
                p, cfg, tokens=batch.get("tokens"),
                embeds=batch.get("embeds"), pos3d=batch.get("pos3d"),
                attn_impl=impl, lengths=batch.get("lengths"))

        def decode(p, cache, tokens):
            return transformer.lm_decode(p, cfg, cache, tokens,
                                         attn_impl=impl)

        return Model(cfg, lambda k: transformer.lm_init(k, cfg), forward,
                     prefill, decode,
                     lambda b, cap, **kw: transformer.lm_init_cache(
                         cfg, b, cap, **kw))

    if cfg.family == "hybrid":
        def forward(p, batch):
            return hybrid.hybrid_forward(p, cfg, batch["tokens"],
                                         attn_impl=impl,
                                         lengths=batch.get("lengths"))

        def prefill(p, batch):
            lengths = batch.get("lengths")
            logits, caches = hybrid.hybrid_forward(
                p, cfg, batch["tokens"], attn_impl=impl,
                collect_cache=True, last_only=True, lengths=lengths)
            msts, (ck, cv) = caches
            b, s_len = batch["tokens"].shape

            def _flat(a):   # (G, k, ...) -> (L, ...)
                return a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])

            cache = {
                "conv_x": _flat(msts["conv"][0]),
                "conv_B": _flat(msts["conv"][1]),
                "conv_C": _flat(msts["conv"][2]),
                "ssd": _flat(msts["ssd"]), "k": ck, "v": cv,
                "idx": jnp.int32(s_len),
                "start": (jnp.zeros((b,), jnp.int32) if lengths is None
                          else (s_len - lengths).astype(jnp.int32)),
            }
            return logits[:, -1], cache

        def decode(p, cache, tokens):
            return hybrid.hybrid_decode(p, cfg, cache, tokens,
                                        attn_impl=impl)

        return Model(cfg, lambda k: hybrid.hybrid_init(k, cfg), forward,
                     prefill, decode,
                     lambda b, cap, **kw: hybrid.hybrid_init_cache(
                         cfg, b, cap, **kw))

    if cfg.family == "ssm":
        def forward(p, batch):
            return rwkv_model.rwkv_forward(p, cfg, batch["tokens"],
                                           lengths=batch.get("lengths"))

        def prefill(p, batch):
            logits, cache = rwkv_model.rwkv_forward(
                p, cfg, batch["tokens"], collect_cache=True, last_only=True,
                lengths=batch.get("lengths"))
            return logits[:, -1], cache

        def decode(p, cache, tokens):
            return rwkv_model.rwkv_decode(p, cfg, cache, tokens)

        return Model(cfg, lambda k: rwkv_model.rwkv_init(k, cfg), forward,
                     prefill, decode,
                     lambda b, cap, **kw: rwkv_model.rwkv_init_cache(
                         cfg, b, cap, **kw))

    if cfg.family == "encdec":
        def forward(p, batch):
            enc = encdec.encode(p, cfg, batch["frames"], attn_impl=impl)
            logits, _ = encdec.decode_train(p, cfg, batch["tokens"], enc,
                                            attn_impl=impl)
            return logits, {}

        def prefill(p, batch):
            enc = encdec.encode(p, cfg, batch["frames"], attn_impl=impl)
            logits, cache = encdec.decode_train(
                p, cfg, batch["tokens"], enc, attn_impl=impl,
                collect_cache=True, last_only=True)
            return logits[:, -1], cache

        def decode(p, cache, tokens):
            return encdec.encdec_decode(p, cfg, cache, tokens,
                                        attn_impl=impl)

        return Model(cfg, lambda k: encdec.encdec_init(k, cfg), forward,
                     prefill, decode,
                     lambda b, cap, **kw: encdec.encdec_init_cache(
                         cfg, b, cap, **kw))

    raise ValueError(f"unknown family {cfg.family}")


# ------------------------------------------------------------ input specs --

def cache_specs(cfg: ModelConfig):
    """Logical-axis tree for the family's cache pytree (mirrors init_cache).

    ``act_kv_seq`` defaults to replicated; re-mapping it to a mesh axis is
    the flash-decode sequence-parallel hillclimb lever.
    """
    kv = ("layers", "act_batch", "act_kv_seq", "act_kv_heads", None)
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.kv_quant == "int8":
            sc = ("layers", "act_batch", "act_kv_seq", "act_kv_heads")
            return {"k": kv, "v": kv, "k_scale": sc, "v_scale": sc,
                    "idx": (), "start": ("act_batch",)}
        return {"k": kv, "v": kv, "idx": (), "start": ("act_batch",)}
    if cfg.family == "hybrid":
        gkv = ("group", "act_batch", "act_kv_seq", "act_kv_heads", None)
        return {"conv_x": ("layers", "act_batch", None, "act_inner"),
                "conv_B": ("layers", "act_batch", None, None),
                "conv_C": ("layers", "act_batch", None, None),
                "ssd": ("layers", "act_batch", "act_inner", None, None),
                "k": gkv, "v": gkv, "idx": (), "start": ("act_batch",)}
    if cfg.family == "ssm":
        return {"wkv": ("layers", "act_batch", "act_inner", None, None),
                "shift_att": ("layers", "act_batch", "act_embed"),
                "shift_ffn": ("layers", "act_batch", "act_embed"),
                "idx": ()}
    if cfg.family == "encdec":
        return {"k": kv, "v": kv, "cross_k": kv, "cross_v": kv, "idx": ()}
    raise ValueError(cfg.family)


def grow_cache(cfg: ModelConfig, cache, new_cap: int, bucket: bool = True):
    """Pad the seq-capacity dimension of a prefill cache so decode can
    append: dynamic_update_slice clamps out-of-range starts, so writing
    token S into a capacity-S cache silently corrupts the last slot.

    ``bucket`` rounds the grown capacity up to the next power of two.
    Entry-point identity fingerprints every cache shape, so exact-fit
    growth compiles a fresh decode program per distinct ``s + max_new`` —
    pow2 buckets make nearby lengths share one compiled entry point (at
    worst 2x padded capacity, whose extra slots are masked out of
    attention exactly like left pad)."""
    if cfg.family == "ssm":
        return cache                                # O(1) state, no seq dim
    if bucket:
        new_cap = 1 << max(0, int(new_cap) - 1).bit_length()
    out = dict(cache)
    for k in ("k", "v", "k_scale", "v_scale"):      # NOT cross_k/v (static)
        if k not in cache:
            continue
        a = cache[k]
        pad = new_cap - a.shape[2]
        if pad > 0:
            widths = [(0, 0)] * a.ndim
            widths[2] = (0, pad)
            out[k] = jnp.pad(a, widths)
    return out


# ------------------------------------------------- slot-arena primitives --
# Iteration-level serving (ISSUE 5) keeps one *arena* cache resident on a
# worker: a batch of B row slots sharing one write cursor ``idx``.  A row
# prefilled separately (in its own width-s buffer) drops into a slot by
# aligning its content so the last real token sits at ``idx - 1`` and
# setting the row's ``start`` to ``idx - length`` — exactly the left-pad
# layout PR 4's masks already handle, so a newly admitted request never
# touches its neighbours' math.  Every non-scalar cache leaf carries batch
# at axis 1 (see ``cache_specs``); the seq-capacity leaves below are the
# only ones needing cursor alignment — everything else is per-row O(1)
# state copied wholesale.

SEQ_CACHE_KEYS = ("k", "v", "k_scale", "v_scale")


def arena_supported(cfg: ModelConfig) -> bool:
    """Families whose caches support slot insert/free (all token-prompt LM
    families; encdec needs frames and modality stubs stay wave-only)."""
    return cfg.family in ("dense", "moe", "vlm", "hybrid", "ssm") \
        and not cfg.embeds_input


def arena_init_cache(cfg: ModelConfig, batch: int, cap: int, cursor: int):
    """A fresh arena: capacity ``cap``, write cursor ``cursor``, every row
    fully masked (``start == cursor``) until something is inserted."""
    model = build_model(cfg)
    if cfg.family == "ssm":
        return model.init_cache(batch, cap, filled=cursor)
    return model.init_cache(batch, cap, filled=cursor,
                            start=jnp.full((batch,), cursor, jnp.int32))


def cache_extract_rows(cfg: ModelConfig, cache, rows):
    """Row-subset of a cache pytree (batch axis 1 everywhere; per-row
    ``start`` subset; scalar ``idx`` kept) — the primitive behind prefix-
    cache capture and slot hand-off."""
    rows = jnp.asarray(rows, jnp.int32)
    out = {}
    for key, a in cache.items():
        if key == "idx":
            out[key] = a
        elif key == "start":
            out[key] = a[rows]
        else:
            out[key] = a[:, rows]
    return out


def cache_insert_rows(cfg: ModelConfig, arena, rows, slots, lengths,
                      width: int | None = None, check: bool = True):
    """Insert per-row caches (a prefill result of seq width ``width``) into
    arena slots, aligned so each row's last real token lands at the arena
    cursor minus one; the row's ``start`` becomes ``idx - length`` (its
    left pad and whatever junk precedes it stay masked).  Requires
    ``width <= idx`` — iteration-level schedulers initialise the cursor at
    the prompt-capacity bucket so this always holds.  Jit-compatible with
    ``check=False`` (the cursor bound cannot be asserted on a tracer)."""
    slots = jnp.asarray(slots, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    cur = arena["idx"]
    out = dict(arena)
    if cfg.family == "ssm":
        for key, a in arena.items():
            if key == "idx":
                continue
            out[key] = a.at[:, slots].set(rows[key].astype(a.dtype))
        return out
    if width is None:
        width = int(rows["idx"])
    if check and width > int(cur):
        raise ValueError(
            f"cache_insert_rows: row width {width} exceeds arena cursor "
            f"{int(cur)} — the arena must be initialised with cursor >= "
            "the prompt-capacity bucket")
    pos = cur - width + jnp.arange(width)
    for key, a in arena.items():
        if key == "idx":
            continue
        if key == "start":
            out[key] = a.at[slots].set((cur - lengths).astype(jnp.int32))
            continue
        r = rows[key]
        if key in SEQ_CACHE_KEYS:
            out[key] = a.at[:, slots[:, None], pos[None, :]].set(
                r.astype(a.dtype))
        else:
            out[key] = a.at[:, slots].set(r.astype(a.dtype))
    return out


def cache_insert_rows_masked(cfg: ModelConfig, arena, rows, sel, mask,
                             lengths, width: int):
    """Shape-stable variant of :func:`cache_insert_rows` for jitted
    admission: every arena row is (conditionally) written in one fused op.

    ``rows`` carries a full arena-batch of candidate rows (a ``min_rows``-
    pinned prefill); ``sel (B,)`` names each arena slot's source row,
    ``mask (B,)`` which slots are actually replaced, ``lengths (B,)`` the
    per-slot real token count (ignored where unmasked).  All shapes are
    fixed by ``(B, width)``, so ONE program compiles per prompt-width
    bucket — an index-scattered insert would compile per admission size,
    which is a multi-hundred-ms stall on the serve path.
    """
    sel = jnp.asarray(sel, jnp.int32)
    mask = jnp.asarray(mask, bool)
    lengths = jnp.asarray(lengths, jnp.int32)
    cur = arena["idx"]
    out = dict(arena)
    if cfg.family == "ssm":
        for key, a in arena.items():
            if key == "idx":
                continue
            r = rows[key][:, sel].astype(a.dtype)
            m = mask.reshape((1, -1) + (1,) * (a.ndim - 2))
            out[key] = jnp.where(m, r, a)
        return out
    pos = cur - width + jnp.arange(width)
    for key, a in arena.items():
        if key == "idx":
            continue
        if key == "start":
            out[key] = jnp.where(mask, (cur - lengths).astype(jnp.int32),
                                 a).astype(jnp.int32)
            continue
        r = rows[key][:, sel].astype(a.dtype)
        if key in SEQ_CACHE_KEYS:
            window = a[:, :, pos]
            m = mask.reshape((1, -1) + (1,) * (window.ndim - 2))
            out[key] = a.at[:, :, pos].set(jnp.where(m, r, window))
        else:
            m = mask.reshape((1, -1) + (1,) * (a.ndim - 2))
            out[key] = jnp.where(m, r, a)
    return out


def cache_free_rows(cfg: ModelConfig, arena, slots):
    """Evict rows: ``start`` jumps to the cursor so a freed slot holds no
    valid keys (its future junk writes stay masked) and stops pinning
    compaction.  O(1)-state families have nothing to mask — a freed row's
    output is simply never read."""
    if "start" not in arena:
        return arena
    slots = jnp.asarray(slots, jnp.int32)
    out = dict(arena)
    out["start"] = arena["start"].at[slots].set(
        jnp.int32(int(arena["idx"])))
    return out


def cache_shift_left(cfg: ModelConfig, arena, shift: int):
    """Compact the arena: roll every seq-capacity leaf left by ``shift``
    (the minimum live ``start``), rebasing ``start``/``idx``.  Wrapped
    junk lands beyond the new cursor, where the decode mask never looks —
    this is what lets a long-running arena's cursor stay bounded."""
    if cfg.family == "ssm" or shift <= 0:
        return arena
    out = dict(arena)
    for key in SEQ_CACHE_KEYS:
        if key in arena:
            out[key] = jnp.roll(arena[key], -shift, axis=2)
    out["start"] = (arena["start"] - shift).astype(jnp.int32)
    out["idx"] = arena["idx"] - jnp.int32(shift)
    return out


# ------------------------------------------------- paged-arena primitives --
# ISSUE 7 generalises the slot arena to a refcounted pool of fixed-size KV
# *blocks* plus a per-row int32 block table: capacity is live tokens, not
# slots × max-len, rows sharing a block-aligned prompt prefix share the
# physical blocks (refcount++), and "compaction" is dropping refcounts —
# no arena rolls.  Block id 0 is reserved as the TRASH block: never
# allocated, pinned at refcount 1, the landing zone for dead-row and
# pad-position writes (always masked out of attention by kv_len).
#
# The device side is just two pool tensors (L, NB, Hkv, BS, D) — the
# paged decode kernel's own layout, a block's minor dims (BS, D) — that
# the jitted model fns update in place; everything below is HOST
# accounting (numpy), kept in the worker's state-registry entry next to
# the pools.

def paged_supported(cfg: ModelConfig) -> bool:
    """Families servable from a paged arena.  Attention families need the
    plain (unquantized) KV pool layout; ssm has O(1) state and is served
    paged via whole-state snapshots at the engine layer (no block pool).
    hybrid keeps per-row conv/ssd state interleaved with KV — it stays on
    the slot arena."""
    if cfg.family == "ssm":
        return True
    return (cfg.family in ("dense", "moe", "vlm")
            and not cfg.embeds_input and cfg.kv_quant != "int8")


def paged_init_pool(cfg: ModelConfig, blocks: int, block_size: int):
    """Zeroed K/V block pools: (L, NB, Hkv, BS, D) in the cache dtype.
    Block 0 is the trash block — part of the tensor, never handed out."""
    cdt = jnp.dtype(cfg.param_dtype)
    shp = (cfg.n_layers, blocks, cfg.n_kv_heads, block_size, cfg.head_dim)
    return {"k": jnp.zeros(shp, cdt), "v": jnp.zeros(shp, cdt)}


class PagedArena:
    """Host-side block accounting for one worker's paged KV pool.

    Tracks, per physical block, a refcount (rows holding it + the radix
    index holding it each count one reference); per row, the int32 block
    table, resident token count, and liveness.  A block returns to the
    free list only when its refcount hits zero — which is why LRU index
    eviction can never free a block a live row references.
    """

    def __init__(self, batch: int, blocks: int, table_width: int,
                 block_size: int):
        self.batch = int(batch)
        self.nb = int(blocks)
        self.T = int(table_width)
        self.bs = int(block_size)
        self.table = np.zeros((batch, table_width), np.int32)
        self.ref = np.zeros((blocks,), np.int32)
        self.ref[0] = 1                         # pin the trash block
        self.free = list(range(blocks - 1, 0, -1))
        self.len = np.zeros((batch,), np.int32)
        self.live = np.zeros((batch,), bool)
        self.owned: dict[int, list[int]] = {s: [] for s in range(batch)}

    # ---- block lifecycle ----
    def alloc(self) -> int:
        """One fresh block at refcount 1; raises IndexError when exhausted
        (callers relieve pressure by evicting radix-held blocks first)."""
        if not self.free:
            raise IndexError("paged arena: block pool exhausted")
        bid = self.free.pop()
        self.ref[bid] = 1
        return bid

    def ref_inc(self, ids) -> None:
        for bid in ids:
            assert bid != 0 and self.ref[bid] > 0, bid
            self.ref[bid] += 1

    def ref_dec(self, ids) -> list[int]:
        """Drop one reference per id; returns the ids that hit zero (their
        slots are back on the free list — physical contents are stale
        garbage, always masked until overwritten)."""
        freed = []
        for bid in ids:
            assert bid != 0 and self.ref[bid] > 0, bid
            self.ref[bid] -= 1
            if self.ref[bid] == 0:
                self.free.append(bid)
                freed.append(bid)
        return freed

    # ---- row lifecycle ----
    def adopt(self, slot: int, ids, n_tokens: int) -> None:
        """Bind already-referenced blocks (a radix prefix hit, refcounts
        bumped by the caller) as the row's head: table[:len(ids)] = ids."""
        self.table[slot, :len(ids)] = ids
        self.owned[slot].extend(int(i) for i in ids)
        self.len[slot] = n_tokens

    def ensure(self, slot: int, n_tokens: int) -> list[int]:
        """Allocate blocks so the row can hold ``n_tokens`` tokens; returns
        the newly allocated ids (table entries already set)."""
        need = -(-int(n_tokens) // self.bs)     # ceil
        if need > self.T:
            raise ValueError(
                f"paged arena: row needs {need} blocks > table width "
                f"{self.T}")
        new = []
        for bi in range(need):
            if self.table[slot, bi] == 0:
                bid = self.alloc()
                self.table[slot, bi] = bid
                self.owned[slot].append(bid)
                new.append(bid)
        return new

    def release(self, slot: int) -> list[int]:
        """Free a row: drop one reference on every block it holds, clear
        its table row.  Returns the block ids whose refcount hit zero."""
        freed = self.ref_dec(self.owned[slot])
        self.owned[slot] = []
        self.table[slot, :] = 0
        self.len[slot] = 0
        self.live[slot] = False
        return freed

    # ---- observability ----
    def occupancy(self) -> dict:
        allocated = self.nb - 1 - len(self.free)
        shared = int((self.ref[1:] > 1).sum())
        return {"live_tokens": int(self.len[self.live].sum()),
                "allocated_blocks": int(allocated),
                "shared_blocks": shared,
                "free_blocks": len(self.free),
                "total_blocks": self.nb - 1,
                "block_size": self.bs}


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    """Abstract entry-point inputs for one (arch × shape) cell.

    train/prefill -> {"batch": {...}};  decode -> {"cache": ..., "tokens"}.
    Modality frontends are stubs: vlm/audio cells receive precomputed
    patch/frame embeddings (embeds_input), per the assignment.
    """
    b, s = shape.global_batch, shape.seq_len
    cdt = cfg.compute_dtype
    if shape.kind in ("train", "prefill"):
        batch: dict[str, Any] = {}
        if cfg.family == "encdec":
            batch["frames"] = _sds((b, s, cfg.d_model), cdt)
            batch["tokens"] = _sds((b, s), "int32")
        elif cfg.embeds_input:
            batch["embeds"] = _sds((b, s, cfg.d_model), cdt)
            if cfg.mrope_sections:
                batch["pos3d"] = _sds((3, b, s), "int32")
        else:
            batch["tokens"] = _sds((b, s), "int32")
        if shape.kind == "train":
            batch["labels"] = _sds((b, s), "int32")
        return {"batch": batch}

    # decode: one new token against a cache of capacity seq_len
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(b, s))
    return {"cache": cache, "tokens": _sds((b, 1), "int32")}
