import json
from pathlib import Path

import pytest

import costs
import weights

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,params", [("smollm-360m", 361.8e6),
                                         ("qwen1.5-4b", 3.95e9)])
def test_matmul_params_match_the_published_size(name, params):
    cfg = _cfg(name)
    # every weight but the input embedding (and the tiny norms / biases)
    total = costs.matmul_params(cfg)
    if cfg["tie_word_embeddings"]:
        assert total == pytest.approx(params, rel=0.01)
    else:
        emb = cfg["vocab_size"] * cfg["hidden_size"]
        assert total + emb == pytest.approx(params, rel=0.01)


def test_matmul_params_count_the_weight_tree():
    cfg = _cfg("qwen1.5-4b")
    n = 0
    for path, (shape, _) in weights.shapes(cfg).items():
        if path.rsplit("/", 1)[-1] in ("wq", "wk", "wv", "wo", "wi", "wg") \
                or path == "unembed":
            size = 1
            for s in shape:
                size *= s
            n += size
    assert n == costs.matmul_params(cfg)


def test_kv_bytes_per_token():
    assert costs.kv_bytes_per_token(_cfg("smollm-360m")) == 40 * 1024
    assert costs.kv_bytes_per_token(_cfg("qwen1.5-4b")) == 400 * 1024


def test_decode_work_counts_tokens_inside_the_window():
    class R:
        prompt = [1] * 100
        stamps = [0.5, 1.5, 1.5, 2.5, 3.5]    # index 0 came from prefill
    w = costs.decode_work(_cfg("smollm-360m"), [R()], 1.0, 3.0)
    assert w["tokens"] == 3 and w["kv_tokens"] == 101 + 102 + 103


def test_roofline_names_its_bound():
    peaks = costs.peaks_for("TPU v5 lite")
    t, bound = costs.roofline_s(0, 819e9, peaks)
    assert bound == "memory" and t == pytest.approx(1.0)
    t, bound = costs.roofline_s(197e12, 1, peaks)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks_for("TPU v9 imaginary")
