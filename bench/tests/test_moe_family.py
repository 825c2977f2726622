"""A second family joins the benchmark by new files alone.

Everything the family needs lies under ``bench/tests`` and no file of the
harness is edited for it: the configuration ``data/tiny_moe.json`` (the
program's ``moe`` family: 4 layers, d 128, 8 experts, top-2, every layer
sparse), its plain reference ``moe_decoder.py``, loaded as
``references.moe_decoder`` as a module of ``bench/references`` would be,
and its limit ``data/tiny_moe_limits.json``.  Through ``harness.run_cell``
the program serves it from the paged arena and comes out correct, and the
float8 control in its place does not.
"""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

import check
import costs
import harness
import weights
from test_faults import _broken

HERE = Path(__file__).resolve().parent
DATA = HERE / "data"


@pytest.fixture(autouse=True)
def _family(tmp_path, monkeypatch):
    """``references.moe_decoder`` from this directory, for this test only;
    the test's CPU programs kept out of the checkout's cache."""
    name = "references.moe_decoder"
    spec = importlib.util.spec_from_file_location(name,
                                                  HERE / "moe_decoder.py")
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, name, mod)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")
    return mod


def _config():
    return json.loads((DATA / "tiny_moe.json").read_text())


def tiny_moe_cell():
    return harness.Cell(
        "tiny-moe", _config(),
        json.loads((DATA / "tiny_mix.json").read_text()),
        json.loads((DATA / "tiny_moe_limits.json").read_text()), 1, {},
        ["ttft_p90_ms", "tpot_p90_ms", "output_tok_s"])


def _run(seed, **kw):
    return harness.run_cell(tiny_moe_cell(), seed, 2.0, False,
                            allow_cpu=True, log=lambda *a: None, **kw)


def test_the_program_config_comes_from_the_family():
    cfg = harness.program_config(_config())
    assert cfg.family == "moe"
    assert (cfg.moe.n_experts, cfg.moe.top_k) == (8, 2)
    # the experts' width, not the unused dense intermediate_size
    assert cfg.d_ff == 64 and cfg.rope_theta == 10000.0
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.tie_embeddings) == (4, 128, 4, 2, 32, False)


def test_counts_take_the_active_and_the_touched_experts():
    c = _config()
    spec = weights.shapes(c)

    def size(path):
        n = 1
        for s in spec[path][0]:
            n *= s
        return n
    experts = sum(size(f"layers/moe/{w}") for w in ("wi", "wg", "wo"))
    rest = sum(size(p) for p in spec if p.rsplit("/", 1)[-1] in (
        "wq", "wk", "wv", "wo", "router") and "moe/wo" not in p) \
        + size("unembed")
    assert costs.matmul_params(c) == rest + experts * 2 // 8
    # one row reads its own two experts a layer; many rows read them all
    assert costs.step_weight_bytes(c, 1) == pytest.approx(
        2 * (rest + experts * 2 / 8))
    assert costs.step_weight_bytes(c, 10_000) == pytest.approx(
        2 * (rest + experts))


def test_list_and_dict_keys_reach_forward(monkeypatch, _family):
    seen = []
    real = _family.forward

    def spy(params, config, tokens, *, fp8=False):
        seen.append(config)
        return real(params, config, tokens, fp8=fp8)
    monkeypatch.setattr(_family, "forward", spy)
    c = _config()
    params = weights.make(c, 5)

    class Rec:
        prompt, tokens = [3, 4, 5], [6, 7]
    check.gaps(params, {**c, "probe": [1, {"a": 2}]}, 16, [Rec()], False)
    assert seen and seen[0]["layer_types"] == c["layer_types"]
    assert seen[0]["rope_parameters"] == c["rope_parameters"]
    assert seen[0]["probe"] == [1, {"a": 2}]


def test_sound_run_is_correct():
    doc = _run(2**33 + 21)
    assert doc["correct"], doc["checks"]
    assert doc["failed"] == 0 and doc["checks"]["checked_tokens_min"]["ok"]
    assert "greedy_gap_p95" in doc["checks"]


@pytest.mark.parametrize("seed", [2**31 + 22, 2**40 + 23])
def test_float8_control_fails_where_the_program_passes(seed):
    doc = _run(seed, control=True)
    assert doc["correct"] is False, doc["checks"]
    gap = doc["checks"]["greedy_gap_p95"]
    assert not gap["ok"] and gap["value"] > gap["limit"]
    assert doc["served_gap"] <= gap["limit"]


@pytest.mark.parametrize("kind", ["state_unchanged", "token_altered",
                                  "half_the_rows"])
def test_broken_decode_is_not_correct(monkeypatch, kind):
    from repro.runtime import engine
    monkeypatch.setattr(engine, "_paged_decode_fn", _broken(kind))
    doc = _run(2**33 + 24)
    assert not doc["correct"]
    assert not doc["checks"]["greedy_gap_p95"]["ok"]
