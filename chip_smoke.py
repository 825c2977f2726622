"""Serve smollm-360m at its published widths on one TPU chip and check it.

  python chip_smoke.py [--seed 0]

Random weights and prompts from ``--seed``.  Everything runs in this one
process on the ``threads`` backend: the process holds the chip, so no
worker process could get it.  Phases:

1. kernels vs ref: on the contiguous path (flash prefill, decode kernel)
   and the paged path (chunk prefill, paged decode kernel), prefill logits
   and ``DECODE_STEPS`` decode steps of the Pallas build match an
   ``attn_impl="ref"`` build of the same weights within ``LOGIT_RTOL``.
2. serving: ``N_REQUESTS`` prompts of 64-256 tokens go through the
   iteration-level ``ContinuousBatcher`` (``run_continuous``), once on the
   slot arena and once on the paged arena (block size ``BLOCK_SIZE``).
   Each completion must equal the same prompt served alone through the
   same arena — the batch-invariance contract.  A staggered pass (at most
   ``STAGGER`` rows in flight) must do the same on the paged arena; on the
   slot arena it is reported, as is agreement with the solo wave path
   (``LMServer.serve_wave``).
3. deployment: every deployed AOT entry point compiled (``aot_xla``, no
   fallback to the eager worker), and its program and the decode step of
   each arena call the attention kernels (``tpu_custom_call``).

Set-up, compile and serve wall times go to earlier lines; they are not
metrics.  The last line is one JSON object, ``"ok": true`` only when every
phase passed; the exit code is non-zero otherwise, and when JAX finds no
TPU nothing is served at all.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "smollm-360m"
N_REQUESTS = 8
MAX_NEW = 16
BLOCK_SIZE = 16
STAGGER = 3             # rows in flight in the staggered-arrival pass
DECODE_STEPS = 4
# Relative L2 error per logits row allowed between the Pallas and the ref
# build.  Both round every activation to bf16 alike except the attention
# output: the kernel feeds unnormalised probabilities to the MXU tile by
# tile, the reference normalised ones in one pass, so each layer's output
# lands one or two bf16 steps apart (2**-9..2**-8 relative; 1e-3..2.8e-3
# per call measured on a TPU v5e).  Over 32 layers such steps add up about
# like a random walk, sqrt(32) * 2**-8.5 ~ 1.6e-2; the bound leaves twice
# that.
LOGIT_RTOL = 2 ** -5


def _rel_l2(a, b):
    """Per-row ||a - b|| / ||b|| over the last axis."""
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


def contiguous_logits(cfg, params, impl, prompts, feed):
    """Left-padded batch prefill, then teacher-forced decode."""
    import jax
    from repro.models import build_model
    from repro.models.api import grow_cache
    from repro.runtime.server import pack_prompts

    tokens, lengths = pack_prompts(prompts, pad=cfg.pad_id)
    model = build_model(cfg.replace(attn_impl=impl))
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
    logits, cache = prefill(params, {"tokens": tokens, "lengths": lengths})
    cache = grow_cache(cfg, cache, tokens.shape[1] + DECODE_STEPS)
    steps = [logits]
    for tok in feed:
        logits, cache = decode(params, cache, tok.astype(np.int32))
        steps.append(logits)
    return np.stack([np.asarray(s, np.float32) for s in steps])


def paged_logits(cfg, params, impl, prompts, feed):
    """The paged arena's model calls: each prompt prefilled as one chunk
    into its own blocks of a shared pool, then teacher-forced decode
    through the block tables."""
    import jax
    from repro.models import transformer
    from repro.models.api import paged_init_pool

    width = 1 << (max(map(len, prompts)) + DECODE_STEPS - 1).bit_length()
    n_tab = width // BLOCK_SIZE
    table = 1 + np.arange(len(prompts) * n_tab,       # block 0 is trash
                          dtype=np.int32).reshape(len(prompts), n_tab)
    pool = paged_init_pool(cfg, 1 + table.size, BLOCK_SIZE)
    pk, pv = pool["k"], pool["v"]
    chunk = jax.jit(lambda p, t, pk, pv, tab, m, n:
                    transformer.lm_prefill_paged_chunk(
                        p, cfg, t, pk, pv, tab, m, n, attn_impl=impl))
    decode = jax.jit(lambda p, pk, pv, tab, lens, live, t:
                     transformer.lm_decode_paged(
                         p, cfg, pk, pv, tab, lens, live, t,
                         attn_impl=impl))
    first = []
    for b, prompt in enumerate(prompts):
        toks = np.full((1, width), cfg.pad_id, np.int32)
        toks[0, :len(prompt)] = prompt
        logits, pk, pv = chunk(params, toks, pk, pv, table[b:b + 1],
                               np.int32(0), np.int32(len(prompt)))
        first.append(logits[0])
    steps = [jax.numpy.stack(first)]
    lens = np.array([len(p) for p in prompts], np.int32)
    live = np.ones(len(prompts), bool)
    for tok in feed:
        logits, pk, pv = decode(params, pk, pv, table, lens, live,
                                tok.astype(np.int32))
        steps.append(logits)
        lens = lens + 1
    return np.stack([np.asarray(s, np.float32) for s in steps])


def kernels_vs_ref(cfg, params, rng):
    """Both arenas' model paths, Pallas against an ``attn_impl="ref"``
    build: prefill logits and teacher-forced decode steps."""
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in (200, 77)]
    feed = rng.integers(1, cfg.vocab_size, (DECODE_STEPS, len(prompts), 1))
    fails = []
    for name, run in (("contiguous", contiguous_logits),
                      ("paged", paged_logits)):
        pal, ref = (run(cfg, params, impl, prompts, feed)
                    for impl in ("pallas", "ref"))
        rel = _rel_l2(pal, ref)
        agree = int((pal.argmax(-1) == ref.argmax(-1)).sum())
        print(f"kernels_vs_ref {name}: logits {pal.shape} max_rel_l2 "
              f"{rel.max():.3e} (bound {LOGIT_RTOL:.3e}) "
              f"argmax_agree {agree}/{rel.size}")
        if pal.shape != (DECODE_STEPS + 1, len(prompts), cfg.vocab_size):
            fails.append(f"kernels_vs_ref {name}: logits shape {pal.shape}")
        if not (np.isfinite(pal).all() and np.isfinite(ref).all()):
            fails.append(f"kernels_vs_ref {name}: non-finite logits")
        if not rel.max() <= LOGIT_RTOL:
            fails.append(f"kernels_vs_ref {name}: max relative L2 "
                         f"{rel.max():.3e} > {LOGIT_RTOL:.3e}")
    return fails


def _first_diff(a, b):
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


def compare(name, got, want):
    """Token lists per request must be equal; report where they part."""
    bad = [(i, _first_diff(g, w)) for i, (g, w) in enumerate(zip(got, want))
           if g != w]
    print(f"{name}: {len(got) - len(bad)}/{len(got)} equal"
          + (f", first differing token per request {bad}" if bad else ""))
    return [f"{name}: requests {[i for i, _ in bad]} differ"] if bad else []


def serve(cfg, params, reqs):
    """Both arenas through the batcher, each against its own solo runs."""
    from repro.cloud import Session
    from repro.runtime.server import LMServer
    from repro.serving import run_continuous

    fails = []
    arena = dict(max_batch=8, slots=1, iteration_level=True, quantum=8,
                 prompt_cap=256, block_size=BLOCK_SIZE)
    with Session("threads") as sess:
        server = LMServer(cfg, params, session=sess,
                          max_new=max(r.max_new for r in reqs))
        try:
            t0 = time.perf_counter()
            wave = [server.serve_wave([r])[0].tokens for r in reqs]
            print(f"solo_wave_s {time.perf_counter() - t0:.3f}")
            for paged in (False, True):
                name = "paged" if paged else "slot"
                t0 = time.perf_counter()
                comps = run_continuous(server, reqs, concurrency=len(reqs),
                                       paged=paged, **arena)
                print(f"serve_{name}_s {time.perf_counter() - t0:.3f} "
                      f"tokens {sum(len(c.tokens) for c in comps)}")
                t0 = time.perf_counter()
                solo = [run_continuous(server, [r], concurrency=1,
                                       paged=paged, **arena)[0].tokens
                        for r in reqs]
                print(f"solo_{name}_s {time.perf_counter() - t0:.3f}")
                got = [c.tokens for c in comps]
                if not all(got):
                    fails.append(f"{name}: a request was served no tokens")
                fails += compare(f"{name}_vs_solo", got, solo)
                compare(f"{name}_vs_solo_wave", got, wave)
                # Staggered arrivals: at most STAGGER rows in flight, so
                # later rows are admitted at other cursors than their solo
                # runs.  The paged arena is invariant by construction; the
                # slot arena is not on the chip (ROADMAP 2.15), so its
                # agreement is only reported.
                t0 = time.perf_counter()
                late = run_continuous(server, reqs, concurrency=STAGGER,
                                      paged=paged, **arena)
                print(f"staggered_{name}_s {time.perf_counter() - t0:.3f}")
                late_fails = compare(f"{name}_staggered_vs_solo",
                                     [c.tokens for c in late], solo)
                if paged:
                    fails += late_fails
            fails += check_deployment(sess.deployment)
            fails += check_arena_decode(server, params, arena)
        finally:
            server.close()
    return fails


def check_arena_decode(server, params, arena):
    """The decode step each arena ran above, compiled again at the same
    shapes (the compile caches hold it), must call the decode kernel."""
    import jax
    import jax.numpy as jnp
    from repro.models.api import arena_init_cache, paged_init_pool
    from repro.runtime import engine

    cfg, rows = server.cfg, arena["max_batch"]

    def sds(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    calls = {}
    for paged in (False, True):
        eng = engine.EngineClient(server, rows=rows, paged=paged,
                                  prompt_cap=arena["prompt_cap"],
                                  quantum=arena["quantum"],
                                  block_size=arena["block_size"])
        eng.close()
        if paged:
            pool = jax.eval_shape(lambda: paged_init_pool(
                cfg, eng.pool_blocks, eng.block_size))
            low = engine._paged_decode_fn(cfg, eng.quantum).lower(
                params, pool["k"], pool["v"], sds(rows, eng.table_width),
                sds(rows), sds(rows, dtype=bool), sds(rows))
        else:
            cache = jax.eval_shape(lambda: arena_init_cache(
                cfg, rows, eng.cap, eng.cursor0))
            low = engine._decode_fn(cfg, eng.quantum).lower(
                params, cache, sds(rows, 1), sds(rows, dtype=bool))
        name = "paged" if paged else "slot"
        calls[name] = low.compile().as_text().count("tpu_custom_call")
    print(f"arena decode programs: tpu_custom_calls {calls}")
    return [f"arena decode: no kernel in the {n} decode program"
            for n, c in calls.items() if c < 1]


def check_deployment(dep):
    aot = [d for d in dep.functions if d.remote_fn.jax_traceable]
    kinds = sorted({d.bridge.kind for d in aot})
    calls = [d.bridge.executor.compiled.as_text().count("tpu_custom_call")
             for d in aot if d.bridge.kind == "aot_xla"]
    print(f"deployment: {len(dep.functions)} functions, {len(aot)} AOT "
          f"kinds {kinds}, tpu_custom_calls per program {calls}")
    fails = []
    if not aot or kinds != ["aot_xla"]:
        fails.append(f"deployment: AOT entry points of kinds {kinds}")
    if not calls or min(calls) < 2:      # the prefill and decode kernels
        fails.append(f"deployment: kernels missing from the compiled "
                     f"decode programs ({calls} tpu_custom_calls)")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import build_model
        from repro.runtime.server import Request
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing: {e}",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device {device}")
    print(f"compile_cache {enable_compile_cache()}")
    cache = {"hits": 0, "misses": 0, "compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cache["compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    model = build_model(cfg)
    params = jax.jit(lambda k: model.init(k)[0])(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"setup_s {time.perf_counter() - t0:.3f} arch {cfg.name} "
          f"params {n_params} layers {cfg.n_layers} d_model {cfg.d_model}")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=[int(t) for t in
                            rng.integers(1, cfg.vocab_size, n)],
                    max_new=MAX_NEW)
            for n in rng.integers(64, 257, N_REQUESTS)]
    print(f"prompt_lengths {[len(r.prompt) for r in reqs]} "
          f"max_new {MAX_NEW}")

    failures = []
    for name, phase in (("kernels_vs_ref",
                         lambda: kernels_vs_ref(cfg, params, rng)),
                        ("serve", lambda: serve(cfg, params, reqs))):
        t0 = time.perf_counter()
        try:
            failures += phase()
        except Exception as e:              # report every phase, then fail
            traceback.print_exc()
            failures.append(f"{name}: {type(e).__name__}: {e}")
        print(f"{name}_s {time.perf_counter() - t0:.3f}")
    print(f"compile_s {cache['compile_s']:.3f} persistent_cache_hits "
          f"{cache['hits']} misses {cache['misses']}")
    for f in failures:
        print(f"FAIL {f}")
    doc = {"ok": not failures, "device": device}
    if failures:
        doc["failures"] = failures
    print(json.dumps(doc))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
