"""Paged KV arena (ISSUE 7): block-table decode attention, the radix
prefix index, host-side block accounting, chunked prefill, and the paged
composition-invariance matrix — paged serving (radix sharing, chunked
prefill, block-table decode) must produce bit-identical greedy tokens to
solo wave decode, with NO wave fallback for prompts above prompt_cap."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from conftest import FAMILY_ARCHS, make_ragged_requests, solo_reference
from repro.cloud import Session
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref)
from repro.kernels.decode_attention.ops import decode_attention_paged
from repro.models.api import PagedArena, paged_init_pool, paged_supported
from repro.runtime import state
from repro.runtime.radix import RadixIndex
from repro.runtime.server import LMServer, Request
from repro.serving import ContinuousBatcher, run_continuous

RNG = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _clean_state_registry():
    yield
    for h in list(state.stats()["handles"]):
        state.release(h)


# ------------------------------------------------------------ radix index --

def test_radix_match_is_block_aligned():
    """Only whole blocks match: a 7-token prompt over bs=4 has one
    indexable block; the ragged tail never enters the index."""
    ix = RadixIndex(4)
    stored = ix.insert([1, 2, 3, 4, 5, 6, 7], ["b0"])
    assert stored == ["b0"] and ix.tokens == 4
    n, payloads = ix.match([1, 2, 3, 4, 5, 6, 7])
    assert (n, payloads) == (4, ["b0"])
    # agreeing on 3 of 4 tokens is no match at all
    assert ix.match([1, 2, 3, 9]) == (0, [])


def test_radix_divergence_splits_runs_at_block_boundaries():
    """Two prompts sharing their first block: the insert that diverges
    mid-run splits the node exactly at the block boundary, so the shared
    head stays one run with one payload per block."""
    ix = RadixIndex(2)
    ix.insert([1, 2, 3, 4, 5, 6], ["a", "b", "c"])
    assert ix.n_nodes == 1                       # one compressed run
    ix.insert([1, 2, 9, 9], ["a", "d"])
    # split: shared run [1,2] + two tails
    assert ix.n_nodes == 3
    assert ix.match([1, 2, 3, 4, 5, 6]) == (6, ["a", "b", "c"])
    assert ix.match([1, 2, 9, 9]) == (4, ["a", "d"])
    # partial hit: longest shared block-aligned prefix only
    n, payloads = ix.match([1, 2, 3, 4, 7, 7])
    assert (n, payloads) == (4, ["a", "b"])


def test_radix_insert_overwrite_replaces_in_place():
    ix = RadixIndex(2)
    ix.insert([5, 6, 7, 8], [0, 0])
    assert ix.insert([5, 6, 7, 8], [1, 1]) == []          # already present
    assert ix.match([5, 6, 7, 8]) == (4, [0, 0])
    ix.insert([5, 6, 7, 8], [2, 2], overwrite=True)
    assert ix.match([5, 6, 7, 8]) == (4, [2, 2])


def test_radix_lru_eviction_returns_payloads_oldest_first():
    ix = RadixIndex(2, budget_tokens=8)
    ix.insert([1, 1, 1, 1], ["old0", "old1"])
    ix.insert([2, 2, 2, 2], ["mid0", "mid1"])
    ix.match([1, 1, 1, 1])                       # renew the first run
    ix.insert([3, 3, 3, 3], ["new0", "new1"])    # 12 tokens > budget 8
    dropped = ix.evict()
    assert dropped == ["mid0", "mid1"]           # LRU, not insertion order
    assert ix.tokens == 8
    assert ix.match([1, 1, 1, 1])[0] == 4        # renewed run survived
    assert ix.match([2, 2, 2, 2])[0] == 0


def test_radix_eviction_never_frees_live_referenced_blocks():
    """The index holds its OWN reference per stored block; eviction hands
    payloads back and only refcount-zero actually frees — a block a live
    row also references survives its index eviction."""
    pa = PagedArena(batch=2, blocks=8, table_width=4, block_size=2)
    ix = RadixIndex(2, budget_tokens=4)
    # row 0 prefills [1,2,3,4]: two blocks, then the index adopts a ref
    b0, b1 = pa.alloc(), pa.alloc()
    pa.adopt(0, [b0, b1], 4)
    pa.live[0] = True
    pa.ref_inc(ix.insert([1, 2, 3, 4], [b0, b1]))
    assert pa.ref[b0] == 2 and pa.ref[b1] == 2
    # pressure evicts the run from the index -> ref_dec, nothing freed
    ix.insert([9, 9, 9, 9], [0, 0])              # over budget
    freed = pa.ref_dec([i for i in ix.evict() if i != 0])
    assert freed == []                           # live row still holds them
    assert pa.ref[b0] == 1 and pa.ref[b1] == 1
    # releasing the row is what frees the physical blocks
    assert sorted(pa.release(0)) == sorted([b0, b1])
    assert pa.ref[b0] == 0 and b0 in pa.free


def test_radix_evict_blocks_pressure_path():
    ix = RadixIndex(2)
    ix.insert([1, 1, 1, 1], ["a", "b"])
    ix.insert([2, 2], ["c"])
    dropped = ix.evict_blocks(1)
    assert len(dropped) >= 1 and ix.tokens <= 4


# ----------------------------------------------------------- paged arena --

def test_paged_arena_trash_block_is_pinned():
    pa = PagedArena(batch=1, blocks=4, table_width=2, block_size=4)
    assert pa.ref[0] == 1 and 0 not in pa.free
    assert pa.occupancy()["total_blocks"] == 3   # trash block not countable


def test_paged_arena_ensure_release_roundtrip():
    pa = PagedArena(batch=2, blocks=6, table_width=3, block_size=4)
    new = pa.ensure(0, 9)                        # ceil(9/4) = 3 blocks
    assert len(new) == 3 and all(pa.ref[b] == 1 for b in new)
    assert pa.ensure(0, 12) == []                # already covered
    with pytest.raises(ValueError, match="table width"):
        pa.ensure(1, 13)                         # 4 blocks > width 3
    pa.len[0], pa.live[0] = 9, True
    occ = pa.occupancy()
    assert occ["live_tokens"] == 9 and occ["allocated_blocks"] == 3
    freed = pa.release(0)
    assert sorted(freed) == sorted(new)
    assert not pa.table[0].any() and pa.occupancy()["allocated_blocks"] == 0


def test_paged_arena_shared_blocks_free_only_at_refcount_zero():
    pa = PagedArena(batch=2, blocks=8, table_width=4, block_size=2)
    head = pa.ensure(0, 4)                       # row 0 owns two blocks
    pa.len[0], pa.live[0] = 4, True
    pa.ref_inc(head)                             # row 1 adopts the same head
    pa.adopt(1, head, 4)
    pa.live[1] = True
    assert pa.occupancy()["shared_blocks"] == 2
    assert pa.release(0) == []                   # row 1 still references
    assert sorted(pa.release(1)) == sorted(head)


def test_paged_arena_pool_exhaustion_raises():
    pa = PagedArena(batch=1, blocks=3, table_width=4, block_size=2)
    pa.alloc(), pa.alloc()
    with pytest.raises(IndexError, match="exhausted"):
        pa.alloc()


# ----------------------------------------- block-table decode attention --

def _as_pool(k, v, bs, layers=1, layer=0):
    """Contiguous (B,Skv,Hkv,D) caches -> stacked block pools
    (layers,NB,Hkv,BS,D) + table such that the paged gather of ``layer``
    reconstructs them exactly (block 0 = trash; other layers hold noise,
    so reading the wrong layer shows)."""
    b, skv, hkv, d = k.shape
    t = skv // bs
    noise = np.random.default_rng(layers)
    shape = (layers, 1 + b * t, hkv, bs, d)
    pool_k = noise.normal(size=shape).astype(k.dtype)
    pool_v = noise.normal(size=shape).astype(v.dtype)
    pool_k[layer] = 0
    pool_v[layer] = 0
    table = np.zeros((b, t), np.int32)
    for r in range(b):
        for c in range(t):
            bid = 1 + r * t + c
            pool_k[layer, bid] = k[r, c * bs:(c + 1) * bs].swapaxes(0, 1)
            pool_v[layer, bid] = v[r, c * bs:(c + 1) * bs].swapaxes(0, 1)
            table[r, c] = bid
    return jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(table)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_paged_decode_matches_contiguous(impl):
    b, skv, bs, hq, hkv, d = 3, 32, 8, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), jnp.float32)
    k = np.asarray(RNG.normal(size=(b, skv, hkv, d)), np.float32)
    v = np.asarray(RNG.normal(size=(b, skv, hkv, d)), np.float32)
    kv_len = jnp.asarray([32, 17, 5], jnp.int32)
    pool_k, pool_v, table = _as_pool(k, v, bs, layers=3, layer=1)
    ref = decode_attention_ref(q, jnp.asarray(k), jnp.asarray(v), kv_len)
    out = decode_attention_paged(q, pool_k, pool_v, table, kv_len, 1,
                                 impl=impl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_bitwise_at_pow2_width():
    """The serving invariant: at a power-of-two gathered width the paged
    ref path is BITWISE the contiguous masked decode — this equality is
    why paged tokens match the left-padded solo path exactly (the engine
    enforces pow2 caps via shape_bucket)."""
    b, skv, bs, hq, hkv, d = 2, 64, 16, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), jnp.float32)
    k = np.asarray(RNG.normal(size=(b, skv, hkv, d)), np.float32)
    v = np.asarray(RNG.normal(size=(b, skv, hkv, d)), np.float32)
    kv_len = jnp.asarray([40, 23], jnp.int32)
    pool_k, pool_v, table = _as_pool(k, v, bs)
    ref = decode_attention_ref(q, jnp.asarray(k), jnp.asarray(v), kv_len)
    out = decode_attention_paged(q, pool_k, pool_v, table, kv_len,
                                 impl="ref")
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_paged_decode_scrambled_table_and_trash_tail():
    """Physical placement must be invisible: permuting which physical
    block holds each logical column, and pointing every column past
    kv_len at the trash block, changes nothing."""
    b, skv, bs, hq, hkv, d = 2, 32, 8, 4, 2, 16
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), jnp.float32)
    k = np.asarray(RNG.normal(size=(b, skv, hkv, d)), np.float32)
    v = np.asarray(RNG.normal(size=(b, skv, hkv, d)), np.float32)
    kv_len = jnp.asarray([20, 9], jnp.int32)
    pool_k, pool_v, table = _as_pool(k, v, bs)
    # scramble: reverse the physical pool, remap the table accordingly
    perm = np.arange(pool_k.shape[1])[::-1].copy()
    perm[perm == 0], perm[0] = perm[0], 0        # keep trash at 0... swap
    inv = np.argsort(perm)
    s_pool_k = jnp.asarray(np.asarray(pool_k)[:, perm])
    s_pool_v = jnp.asarray(np.asarray(pool_v)[:, perm])
    s_table = jnp.asarray(inv[np.asarray(table)].astype(np.int32))
    base = decode_attention_paged(q, pool_k, pool_v, table, kv_len,
                                  impl="ref")
    scr = decode_attention_paged(q, s_pool_k, s_pool_v, s_table, kv_len,
                                 impl="ref")
    assert (np.asarray(base) == np.asarray(scr)).all()
    # masked tail -> trash block: also identical
    tbl = np.asarray(table).copy()
    tbl[0, 3:] = 0                               # row 0 holds 20 <= 3*8 toks
    tbl[1, 2:] = 0                               # row 1 holds 9 <= 2*8 toks
    trash = decode_attention_paged(q, pool_k, pool_v, jnp.asarray(tbl),
                                   kv_len, impl="ref")
    assert (np.asarray(trash) == np.asarray(base)).all()


@settings(max_examples=12, deadline=None)
@given(bs=st.sampled_from([4, 8, 16]), t=st.integers(1, 4),
       hkv=st.sampled_from([1, 2]), g=st.sampled_from([1, 2]),
       data=st.data())
def test_paged_decode_hypothesis(bs, t, hkv, g, data):
    """Property: for ANY ragged kv_len over any (block_size, table_width)
    geometry, block-table decode equals contiguous decode."""
    b, d, skv = 2, 16, bs * t
    lens = [data.draw(st.integers(1, skv)) for _ in range(b)]
    rng = np.random.default_rng(bs * 100 + t * 10 + hkv)
    q = jnp.asarray(rng.normal(size=(b, hkv * g, d)), jnp.float32)
    k = np.asarray(rng.normal(size=(b, skv, hkv, d)), np.float32)
    v = np.asarray(rng.normal(size=(b, skv, hkv, d)), np.float32)
    kv_len = jnp.asarray(lens, jnp.int32)
    pool_k, pool_v, table = _as_pool(k, v, bs)
    ref = decode_attention_ref(q, jnp.asarray(k), jnp.asarray(v), kv_len)
    out = decode_attention_paged(q, pool_k, pool_v, table, kv_len,
                                 impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_ragged_skv_not_block_multiple():
    """Regression: Skv that does not divide block_k pads with ZEROS (not
    garbage) — the masked tail must not poison the softmax."""
    b, skv, hq, hkv, d = 2, 40, 4, 2, 32        # 40 % 128 != 0
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), jnp.float32)
    kv_len = jnp.asarray([40, 33], jnp.int32)
    ref = decode_attention_ref(q, k, v, kv_len)
    pal = decode_attention(q, k, v, kv_len, impl="pallas_interpret",
                           block_k=128)
    assert bool(jnp.all(jnp.isfinite(pal)))
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------- stacked pool carried through the layers --
# The engine's paged programs carry the stacked (L,NB,Hkv,BS,D) pool
# through the layer loop and write and attend in place.  The reference
# below moves the pool as a per-layer program would: slice each layer's
# pool out, write the new K/V, attend over the row's gathered blocks,
# stack the layers back.  Tokens and pools must agree bit for bit; block
# 0 (trash, never read unmasked) is left out of the pool comparison.

def _ref_layers(params, cfg, x, pool_k, pool_v, layer_fn):
    """The layer loop with each layer's pool sliced in as the scan's
    input and stacked back as its output."""
    from repro.models.layers import rms_norm
    from repro.models.transformer import _ffn

    def body(x, xs):
        lp, pk, pv = xs
        h, pk, pv = layer_fn(lp["attn"], rms_norm(x, lp["ln1"], cfg.rms_eps),
                             pk, pv)
        x = x + h
        h, _ = _ffn(lp, cfg, rms_norm(x, lp["ln2"], cfg.rms_eps),
                    dropless=True)
        return x + h, (pk, pv)

    x, (pk, pv) = jax.lax.scan(body, x, (params["layers"], pool_k, pool_v))
    return x, pk, pv


def _ref_view(pool, row_table):
    """One layer's (NB,Hkv,BS,D) pool, one row's table -> (1,T·BS,Hkv,D)."""
    t, (_, hkv, bs, d) = row_table.shape[0], pool.shape
    return jnp.swapaxes(pool[row_table], 1, 2).reshape(1, t * bs, hkv, d)


def _ref_decode_step(params, cfg, pool_k, pool_v, table, lens, live, tok):
    from repro.models.attention import _project
    from repro.models.transformer import _embed_in, _logits

    b, bs = table.shape[0], pool_k.shape[3]
    blk = jnp.where(live, table[jnp.arange(b), lens // bs], 0)
    kv_len = jnp.where(live, lens + 1, 0)

    def layer_fn(p, h, pk, pv):
        q, k, v = _project(p, h, lens[:, None], rope_theta=cfg.rope_theta,
                           mrope_sections=(), pos3d=None)
        pk = pk.at[blk, :, lens % bs].set(k[:, 0].astype(pk.dtype))
        pv = pv.at[blk, :, lens % bs].set(v[:, 0].astype(pv.dtype))
        kc = jnp.concatenate([_ref_view(pk, r) for r in table])
        vc = jnp.concatenate([_ref_view(pv, r) for r in table])
        o = decode_attention_ref(q[:, 0], kc, vc, kv_len)
        return jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None], pk, pv

    x, pk, pv = _ref_layers(params, cfg, _embed_in(params, cfg, tok, None),
                            pool_k, pool_v, layer_fn)
    return _logits(params, cfg, x[:, -1]), pk, pv


def _ref_chunk(params, cfg, pool_k, pool_v, tokens, table, m, n_real):
    from repro.kernels.flash_attention import attention
    from repro.models.attention import _project
    from repro.models.transformer import _embed_in, _logits

    c, bs = tokens.shape[1], pool_k.shape[3]
    j = jnp.arange(c)
    real = j < n_real
    blk = jnp.where(real, table[0, jnp.where(real, (m + j) // bs, 0)], 0)

    def layer_fn(p, h, pk, pv):
        q, k, v = _project(p, h, (m + j)[None], rope_theta=cfg.rope_theta,
                           mrope_sections=(), pos3d=None)
        pk = pk.at[blk, :, (m + j) % bs].set(k[0].astype(pk.dtype))
        pv = pv.at[blk, :, (m + j) % bs].set(v[0].astype(pv.dtype))
        o = attention(q, _ref_view(pk, table[0]).astype(q.dtype),
                      _ref_view(pv, table[0]).astype(q.dtype), causal=True,
                      q_offset=m, kv_len=jnp.full((1,), m + n_real),
                      impl="xla")
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), pk, pv

    x, pk, pv = _ref_layers(params, cfg, _embed_in(params, cfg, tokens, None),
                            pool_k, pool_v, layer_fn)
    logits = _logits(params, cfg, x[:, n_real - 1])
    return jnp.argmax(logits, -1).astype(jnp.int32), pk, pv


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_programs_match_a_per_layer_pool_loop(dtype):
    """`_paged_chunk_fn` chained over two rows' prompts, then
    `_paged_decode_fn` for a quantum of 8 with one dead row: the same
    first tokens, decoded tokens and pools as the per-layer reference."""
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.runtime import engine

    cfg = get_smoke("smollm-360m").replace(param_dtype=dtype,
                                           compute_dtype=dtype)
    params, _ = build_model(cfg).init(jax.random.PRNGKey(3))
    rows, t, bs, k = 3, 4, 4, 8
    pool = paged_init_pool(cfg, 1 + rows * t, bs)
    ids = np.random.default_rng(5).permutation(np.arange(1, 1 + rows * t))
    table = ids.reshape(rows, t).astype(np.int32)
    table[2] = 0                                   # row 2 holds nothing
    prompts = {0: [5, 17, 3, 99, 42, 7, 8], 1: [11, 12, 13, 14, 15]}
    chunks = [(0, 0, 4), (1, 0, 8), (0, 4, 4)]     # (row, done, width)
    ref_chunk = jax.jit(_ref_chunk, static_argnums=1)
    ref_step = jax.jit(_ref_decode_step, static_argnums=1)

    got, ref = (pool["k"], pool["v"]), (pool["k"], pool["v"])
    firsts = {}
    for row, done, width in chunks:
        real = prompts[row][done:done + width]
        toks = np.full((1, width), cfg.pad_id, np.int32)
        toks[0, :len(real)] = real
        args = (toks, table[row:row + 1], jnp.int32(done),
                jnp.int32(len(real)))
        first, *got = engine._paged_chunk_fn(cfg, width)(params, *got, *args)
        want, *ref = ref_chunk(params, cfg, *ref, *args)
        assert int(first[0]) == int(want[0])
        firsts[row] = int(first[0])

    lens = np.array([7, 5, 0], np.int32)
    live = np.array([True, True, False])
    last = np.array([firsts[0], firsts[1], cfg.pad_id], np.int32)
    pk, pv, nxt, toks = engine._paged_decode_fn(cfg, k)(
        params, *got, table, lens, live, last)

    tok = jnp.asarray(np.where(live, last, cfg.pad_id)[:, None])
    want = []
    for _ in range(k):
        logits, *ref = ref_step(params, cfg, *ref, table, lens, live, tok)
        tok = jnp.where(live, jnp.argmax(logits, -1), cfg.pad_id)[:, None]
        want.append(np.asarray(tok[:, 0]))
        lens = lens + live
    want = np.stack(want, axis=1)
    np.testing.assert_array_equal(np.asarray(toks), want)
    np.testing.assert_array_equal(np.asarray(nxt), want[:, -1])
    for a, b in zip((pk, pv), ref):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:],
                                      np.asarray(b)[:, 1:])


# -------------------------------------- paged serving invariance matrix --
# Paged admission (radix sharing + chunked prefill + block-table decode)
# must be invisible in the tokens, for an attention family (true paged
# pool) and the ssm family (silent demotion to the slot arena), inline
# and on real worker processes.

PAGED_FAMILIES = ("dense", "ssm")


@pytest.fixture(scope="module", params=PAGED_FAMILIES, ids=PAGED_FAMILIES)
def paged_family(request):
    from repro.configs import get_smoke
    from repro.models import build_model

    cfg = get_smoke(FAMILY_ARCHS[request.param]).replace(
        param_dtype="float32", compute_dtype="float32")
    params, _ = build_model(cfg).init(jax.random.PRNGKey(0))
    return request.param, cfg, params


@pytest.mark.parametrize("backend", ("inline", "processes"))
def test_paged_serving_is_composition_invariant(paged_family, backend):
    fam, cfg, params = paged_family
    with Session(backend, os_threads=1) as sess:
        server = LMServer(cfg, params, session=sess, max_new=8)
        base = make_ragged_requests(cfg)
        rng = np.random.default_rng(3)
        # duplicates -> radix block sharing; one prompt far above
        # prompt_cap=8 -> chunked prefill (budget 8 forces multi-chunk),
        # which the slot arena could only serve via solo-wave fallback
        reqs = base + [Request(prompt=list(base[0].prompt), max_new=6),
                       Request(prompt=list(base[2].prompt), max_new=3),
                       Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                                        40)), max_new=3)]
        solo = solo_reference(server, reqs)

        async def go():
            async with ContinuousBatcher(server, max_batch=3, slots=1,
                                         max_wait_ms=5, quantum=4,
                                         prompt_cap=8, paged=True,
                                         block_size=4,
                                         prefill_budget=8) as b:
                sem = asyncio.Semaphore(3)

                async def one(r):
                    async with sem:
                        return await b.submit(r)

                comps = await asyncio.gather(*[one(r) for r in reqs])
                return comps, b.stats

        comps, stats = asyncio.run(go())
        assert [c.tokens for c in comps] == solo
        assert stats.mode == "iteration"
        if paged_supported(cfg) and cfg.family != "ssm":
            # true paged pool: the 40-token prompt chunk-prefills in
            # place of the slot arena's solo-wave fallback, and the
            # duplicate prompts share physical blocks
            assert stats.wave_fallbacks == 0
            assert stats.prefix_hits >= 1
            assert stats.shared_blocks_peak > 0
            assert stats.live_tokens_peak > 0
        else:
            # ssm: paged request demotes to the slot arena untouched
            assert stats.shared_blocks_peak == 0
        server.close(prune=False)


def test_paged_requires_unified_role():
    """A paged row is a table of shared refcounted blocks — it cannot
    migrate between pools, so disaggregated roles must refuse it."""
    from collections import deque

    from repro.serving.batcher import BatcherStats, EngineLoop

    with pytest.raises(ValueError, match="unified"):
        EngineLoop(object(), index=0, queue=deque(), arrived=None,
                   stats=BatcherStats(), cpu=None, is_closed=lambda: True,
                   handoff=lambda *a: None, role="prefill", paged=True)


# --------------------------------------------------- radix fleet routing --

def test_radix_fleet_routing_is_composition_invariant():
    from repro.configs import get_smoke
    from repro.fleet import FleetRouter, run_fleet
    from repro.models import build_model

    cfg = get_smoke("smollm-360m").replace(param_dtype="float32",
                                           compute_dtype="float32")
    params, _ = build_model(cfg).init(jax.random.PRNGKey(0))
    with Session("processes", os_threads=1) as sess:
        server = LMServer(cfg, params, session=sess, max_new=8)
        base = make_ragged_requests(cfg)
        reqs = base + [Request(prompt=list(base[0].prompt), max_new=6),
                       Request(prompt=list(base[2].prompt) + [5, 9],
                               max_new=3)]
        solo = solo_reference(server, reqs)
        comps, s = run_fleet(server, reqs, n_members=2, policy="radix",
                             max_batch=3, quantum=4, prompt_cap=16,
                             paged=True, block_size=4, return_stats=True)
        assert [c.tokens for c in comps] == solo
        # the duplicate and the extended prompt radix-route to the owner
        assert s["routing"]["prefix"] >= 1
        # block tables cannot migrate between pools
        with pytest.raises(ValueError, match="disaggregate"):
            FleetRouter(server, paged=True, disaggregate=True)
        server.close(prune=False)
