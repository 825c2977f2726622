"""Per-architecture smoke tests (assignment requirement): a REDUCED config
of each family runs one forward + one train step on CPU, asserting output
shapes and finiteness.  Also checks prefill→decode vs full-forward
consistency (the two entry points must agree on the next token)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke
from repro.models import build_model, make_train_step
from repro.optim import AdamW

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def _batch(cfg, with_labels=False):
    batch = {}
    kt, ke, kl = jax.random.split(KEY, 3)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            ke, (B, S, cfg.d_model)).astype(jnp.bfloat16)
        batch["tokens"] = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
    elif cfg.embeds_input:
        batch["embeds"] = jax.random.normal(
            ke, (B, S, cfg.d_model)).astype(jnp.bfloat16)
        if cfg.mrope_sections:
            batch["pos3d"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (3, B, S))
    else:
        batch["tokens"] = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
    if with_labels:
        batch["labels"] = jax.random.randint(kl, (B, S), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params, specs = model.init(KEY)
    # spec tree mirrors param tree
    assert set(jax.tree.structure(params).node_data()[1] or []) == \
        set(jax.tree.structure(specs).node_data()[1] or [])
    logits, _ = jax.jit(model.forward)(params, _batch(cfg))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params, _ = model.init(KEY)
    opt = AdamW(peak_lr=1e-3, warmup=2, total_steps=10)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt))
    batch = _batch(cfg, with_labels=True)
    params, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    leaves = jax.tree.leaves(params)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in leaves)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    """Greedy argmax from (prefill S-1 tokens, decode token S-1) must equal
    argmax of the full forward's last position."""
    cfg = get_smoke(arch)
    if cfg.family == "hybrid":
        # decode recomputes conv/ssd state by a different (sequential)
        # algorithm; run in f32 so the check proves algorithmic equality
        # rather than bf16 drift across 54 recurrent layers.
        cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(KEY)
    batch = _batch(cfg)
    logits_full, _ = jax.jit(model.forward)(params, batch)

    if cfg.family == "encdec":
        pre = {"frames": batch["frames"], "tokens": batch["tokens"][:, :-1]}
    elif cfg.embeds_input:
        pre = {k: (v[:, :-1] if k == "embeds" else v[..., :-1])
               for k, v in batch.items()}
    else:
        pre = {"tokens": batch["tokens"][:, :-1]}
    _, cache = jax.jit(model.prefill)(params, pre)
    from repro.models.api import grow_cache
    cache = grow_cache(cfg, cache, S + 1)

    if cfg.embeds_input and cfg.family != "encdec":
        pytest.skip("vlm decode consumes token ids, not embeds — "
                    "consistency is covered by token-input archs")
    last_tok = batch["tokens"][:, -1:]
    logits_dec, _ = jax.jit(model.decode)(params, cache, last_tok)
    np.testing.assert_array_equal(
        np.argmax(np.asarray(logits_dec), -1),
        np.argmax(np.asarray(logits_full[:, -1]), -1))


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_is_exact_assignment(arch):
    """The FULL configs carry the assigned dims verbatim (never run on CPU
    — exercised via the dry-run's ShapeDtypeStruct lowering only)."""
    cfg = get_config(arch)
    expected = {
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
        "smollm-360m": (32, 960, 15, 5, 2560, 49152),
        "gemma-2b": (18, 2048, 8, 1, 16384, 256000),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "rwkv6-1.6b": (24, 2048, 0, 0, 7168, 65536),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (arch, got, expected)
    if arch == "phi3.5-moe-42b-a6.6b":
        assert (cfg.moe.n_experts, cfg.moe.top_k) == (16, 2)
    if arch == "dbrx-132b":
        assert (cfg.moe.n_experts, cfg.moe.top_k) == (16, 4)
    if arch == "zamba2-2.7b":
        assert cfg.ssm.state_dim == 64
    if arch == "gemma-2b":
        assert cfg.head_dim == 256


def test_param_counts_plausible():
    """Analytic param counts should be in the advertised ballpark."""
    expect = {"qwen2-7b": (6e9, 9e9), "smollm-360m": (3e8, 4.5e8),
              "gemma-2b": (2e9, 3.5e9), "dbrx-132b": (1.1e11, 1.5e11),
              "zamba2-2.7b": (2.2e9, 3.2e9), "rwkv6-1.6b": (1.2e9, 2.2e9),
              "phi3.5-moe-42b-a6.6b": (3.7e10, 4.8e10)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, (arch, n)


def test_attention_init_scales_by_contracted_width():
    """q/k/v/o weights scale by the width they contract over (d_model in,
    Hq·D out).  Taking the heads axis as fan-in made scores ~8x too wide:
    near-hard attention that turns a rounding difference into another
    token a few layers later."""
    from repro.models.attention import attn_init
    d, hq, hkv, hd = 960, 15, 5, 64
    p, _ = attn_init(KEY, d, hq, hkv, hd, jnp.float32)
    tn_std = 0.8796          # std of a standard normal cut to [-2, 2]
    for name, fan_in in (("wq", d), ("wk", d), ("wv", d), ("wo", hq * hd)):
        std = float(jnp.std(p[name])) * fan_in ** 0.5 / tn_std
        assert abs(std - 1.0) < 0.05, (name, std)


def test_logits_come_from_the_f32_accumulator():
    """With bf16 params the fp32 logits are the matmul's f32 accumulator,
    not bf16 values cast up (those tie near-equal greedy candidates)."""
    cfg = get_smoke("smollm-360m")
    assert cfg.param_dtype == "bfloat16" and cfg.logits_fp32
    model = build_model(cfg)
    params, _ = model.init(KEY)
    logits, _ = jax.jit(model.forward)(params, _batch(cfg))
    assert logits.dtype == jnp.float32
    as_bf16 = logits.astype(jnp.bfloat16).astype(jnp.float32)
    assert not bool(jnp.all(as_bf16 == logits))
