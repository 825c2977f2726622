"""The dense family, described by its reference module, gives what the
harness gave when it held the dense rules itself.  Every expected value
below is a literal copy of what the harness read before the family moved
into ``references/dense_decoder.py``: the program's ``ModelConfig``, the
weight spec in its order, the weights' bytes, the counts of operations and
bytes, and each per-layer metric on the recorded chip trace excerpt."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

import costs
import harness
import test_metrics
import weights
from test_devtrace import _recorded

BENCH = Path(__file__).resolve().parents[1]


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _expected_config(name):
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    common = dict(
        family="dense", act="swiglu", mrope_sections=(), pad_id=0,
        moe=MoEConfig(n_experts=0, top_k=0, capacity_factor=1.25,
                      router_aux_weight=0.01, impl="einsum"),
        ssm=SSMConfig(state_dim=0, n_heads=0, head_dim=0, expand=2,
                      chunk=128, conv_width=4),
        shared_attn_every=0, shared_attn_lora_rank=0, encoder_layers=0,
        decoder_layers=0, window=0, param_dtype="bfloat16",
        compute_dtype="bfloat16", kv_quant="none", logits_fp32=True,
        attn_impl="auto", scan_layers=True, embeds_input=False)
    if name == "smollm-360m":
        return ModelConfig(
            name="smollm-360m", n_layers=32, d_model=960, n_heads=15,
            n_kv_heads=5, head_dim=64, d_ff=2560, vocab_size=49152,
            qkv_bias=False, rope_theta=10000.0, rms_eps=1e-05,
            tie_embeddings=True, remat="none", **common)
    return ModelConfig(
        name="qwen1.5-4b", n_layers=40, d_model=2560, n_heads=20,
        n_kv_heads=20, head_dim=128, d_ff=6912, vocab_size=151936,
        qkv_bias=True, rope_theta=5000000.0, rms_eps=1e-06,
        tie_embeddings=False, remat="dots_saveable", **common)


SPEC = {
    "smollm-360m": [
        ("embed", (49152, 960), 0.09682458365518543),
        ("final_norm", (960,), 0.1),
        ("layers/ln1", (32, 960), 0.1),
        ("layers/ln2", (32, 960), 0.1),
        ("layers/attn/wq", (32, 960, 15, 64), 0.032274861218395144),
        ("layers/attn/wk", (32, 960, 5, 64), 0.032274861218395144),
        ("layers/attn/wv", (32, 960, 5, 64), 0.032274861218395144),
        ("layers/attn/wo", (32, 15, 64, 960), 0.032274861218395144),
        ("layers/mlp/wi", (32, 960, 2560), 0.032274861218395144),
        ("layers/mlp/wg", (32, 960, 2560), 0.032274861218395144),
        ("layers/mlp/wo", (32, 2560, 960), 0.01976423537605237)],
    "qwen1.5-4b": [
        ("embed", (151936, 2560), 0.05929270612815711),
        ("final_norm", (2560,), 0.1),
        ("layers/ln1", (40, 2560), 0.1),
        ("layers/ln2", (40, 2560), 0.1),
        ("layers/attn/wq", (40, 2560, 20, 128), 0.01976423537605237),
        ("layers/attn/wk", (40, 2560, 20, 128), 0.01976423537605237),
        ("layers/attn/wv", (40, 2560, 20, 128), 0.01976423537605237),
        ("layers/attn/wo", (40, 20, 128, 2560), 0.01976423537605237),
        ("layers/mlp/wi", (40, 2560, 6912), 0.01976423537605237),
        ("layers/mlp/wg", (40, 2560, 6912), 0.01976423537605237),
        ("layers/mlp/wo", (40, 6912, 2560), 0.012028130608117204),
        ("layers/attn/bq", (40, 20, 128), 0.1),
        ("layers/attn/bk", (40, 20, 128), 0.1),
        ("layers/attn/bv", (40, 20, 128), 0.1),
        ("unembed", (151936, 2560), 0.05929270612815711)],
}

# matmul_params, kv_bytes_per_token, the paged kernel's (flops, bytes) at
# 1000 and at 123457 live keys, decode_work's tokens / kv_tokens / flops
COUNTS = {
    "smollm-360m": (361758720, 40960, (122880000, 40960000),
                    (15170396160, 5056798720), (23, 30696, 20412825600)),
    "qwen1.5-4b": (3560898560, 409600, (409600000, 409600000),
                   (50567987200, 50567987200), (23, 30696, 176374415360)),
}

READINGS = {
    "smollm-360m": {
        "decode_step_ms.tpot": 1846.7117824000002,
        "step_mfu_pct.tpot": 0.00133787738646689,
        "step_hbm_pct.tpot": 0.05305425613031488,
        "paged_decode_roofline.tpot": 1.2613895538599333},
    "qwen1.5-4b": {
        "decode_step_ms.tpot": 2308.389728,
        "step_mfu_pct.tpot": 0.012745539283300288,
        "step_hbm_pct.tpot": 0.42887112502074853,
        "paged_decode_roofline.tpot": 12.61389553859933},
}
# the same for both configurations; the five span readers read nothing
# from an excerpt without host events
READINGS_ANY = {
    "window_compiles.tok_s": 0, "decode_rows_mean.tok_s": 2.5,
    "prefix_hit_rows_pct.ttft": 25.0,
    "prefill_ms_per_ktok.ttft": 31.098844000000003,
    "device_idle_pct.tok_s": 0.0010009332832905393,
    "dispatch_ms.tpot": None, "engine_host_ms.tpot": None,
    "schedule_ms.tok_s": None, "idle_dispatch_pct.tok_s": None,
    "gc_pause_pct.tok_s": None}

# sha256 (first 16 hex digits) of each leaf of bench/tests/data/tiny.json's
# weights, by seed
TINY_WEIGHTS = {
    7: {"embed": "f0d41efca468ff66", "final_norm": "2498f4c557b722d7",
        "layers/attn/bk": "a27227865b3455ce",
        "layers/attn/bq": "79b23895509faa42",
        "layers/attn/bv": "70a3cf80bbfaa767",
        "layers/attn/wk": "2e59702827c49ddc",
        "layers/attn/wo": "0e366911227c4c91",
        "layers/attn/wq": "21fa0e0f613dddd6",
        "layers/attn/wv": "cb2512870c9a002e",
        "layers/ln1": "e783c68d014dfe2c", "layers/ln2": "f819f90922248efe",
        "layers/mlp/wg": "292d7bf258ba21e5",
        "layers/mlp/wi": "1e680b99a69ddd04",
        "layers/mlp/wo": "6d442d7b38366fbe"},
    2**33 + 5: {"embed": "8f96c288e700361d", "final_norm": "f7cd2f2fa7352b70",
                "layers/attn/bk": "f62379ea2a10d326",
                "layers/attn/bq": "6089d366d0c4f1b0",
                "layers/attn/bv": "f034dea99396f4de",
                "layers/attn/wk": "2987c824d5a9297d",
                "layers/attn/wo": "d3d6d177504a57f5",
                "layers/attn/wq": "c79da5e76c5fd5b4",
                "layers/attn/wv": "47d7e803e216cfdc",
                "layers/ln1": "c6c76e9640e898e6",
                "layers/ln2": "cec4c1bdaa10c107",
                "layers/mlp/wg": "58a9e301b5dd4cf9",
                "layers/mlp/wi": "fff26032f00dd3d7",
                "layers/mlp/wo": "6191441b8f57a176"},
}

NAMES = ["smollm-360m", "qwen1.5-4b"]


class _Rec:
    def __init__(self, prompt, stamps):
        self.prompt, self.stamps = [1] * prompt, stamps


@pytest.mark.parametrize("name", NAMES)
def test_program_config_is_the_parents(name):
    assert harness.program_config(_cfg(name)) == _expected_config(name)


@pytest.mark.parametrize("name", NAMES)
def test_weight_spec_is_the_parents_in_its_order(name):
    got = [(p, tuple(s), std) for p, (s, std) in weights.shapes(
        _cfg(name)).items()]
    assert got == SPEC[name]


@pytest.mark.parametrize("seed", sorted(TINY_WEIGHTS))
def test_tiny_weights_are_the_parents_bit_for_bit(seed):
    cfg = json.loads((BENCH / "tests" / "data" / "tiny.json").read_text())
    got = {p: hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for p, x in weights.flatten(weights.make(cfg, seed)).items()}
    assert got == TINY_WEIGHTS[seed]


@pytest.mark.parametrize("name", NAMES)
def test_counts_are_the_parents(name):
    cfg = _cfg(name)
    params, kv_bytes, at_1000, at_123457, work = COUNTS[name]
    assert costs.matmul_params(cfg) == params
    assert costs.kv_bytes_per_token(cfg) == kv_bytes
    assert costs.paged_kernel_work(cfg, {1000: 1}) == at_1000
    # a dense layer reads every key, so the work of many query tokens is
    # that of their live lengths summed
    assert costs.paged_kernel_work(
        cfg, {1000: 100, 2000: 10, 3457: 1}) == at_123457
    recs = [_Rec(100, [0.5, 1.5, 1.5, 2.5, 3.5]),
            _Rec(1500, [0.1 * i for i in range(40)])]
    w = costs.decode_work(cfg, recs, 1.0, 3.0)
    assert (w["tokens"], w["kv_tokens"], w["flops"]) == work


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_what_the_parent_read(name):
    win = test_metrics._window(_recorded())
    win.config = _cfg(name)
    want = {**READINGS_ANY, **READINGS[name]}
    for metric, expected in want.items():
        got = harness.load_reader(metric)(win)
        if expected is None:
            assert got is None, metric
        else:
            assert got == pytest.approx(expected, rel=1e-9, abs=0), metric
