import pytest

import devtrace

MS = 1_000_000


def _trace():
    # two decode programs (each running the kernel twice) and one other
    ops = [("fusion.1", 0, 2 * MS),
           ("flash_decode_paged_bhgd.3", 2 * MS, MS),
           ("flash_decode_paged_bhgd.3", 3 * MS, MS),
           ("copy.7", 10 * MS, 5 * MS),
           ("fusion.1", 20 * MS, MS),
           ("flash_decode_paged_bhgd.3", 21 * MS, MS),
           ("flash_decode_paged_bhgd.3", 22 * MS, MS)]
    modules = [("jit_run", 0, 4 * MS), ("jit_run", 10 * MS, 4 * MS),
               ("jit_run", 20 * MS, 3 * MS)]
    host = [("np.asarray(jax.Array)", 4 * MS, 6 * MS),
            ("PjitFunction(run)", 14 * MS, 3 * MS),
            ("Session.submit", 13 * MS, 9 * MS)]
    return devtrace.Reduced(30e-3, ops, modules, host)


def test_busy_is_the_union_of_operations():
    r = _trace()
    assert r.busy_s == pytest.approx(12e-3)


def test_programs_that_ran_the_kernel():
    r = _trace()
    assert [m[1] for m in r.modules_with("flash_decode_paged")] == [0, 20 * MS]
    assert r.op_count("flash_decode_paged") == 4
    assert r.op_time_s("flash_decode_paged") == pytest.approx(4e-3)


def test_idle_gaps_are_named_by_the_host():
    gaps = _trace().gaps()
    assert gaps[0] == ("np.asarray(jax.Array)", pytest.approx(6e-3))
    # the 15-20 ms gap: the submit covers all of it, the dispatch a part
    assert gaps[1] == ("Session.submit", pytest.approx(5e-3))


def test_breakdown_lists_the_heaviest_operations_first():
    b = _trace().breakdown()
    assert b["device_ops"][:2] == [["copy.7", pytest.approx(5e-3)],
                                   ["flash_decode_paged_bhgd.3",
                                    pytest.approx(4e-3)]]
    assert len(b["idle_gaps"]) == 2
    assert all(len(x) == 2 for x in b["device_ops"] + b["idle_gaps"])


def test_names_from_the_trace_text():
    assert devtrace.op_name("%copy.115 = bf16[32,449]{1,0} copy(bf16[32]"
                            " %flash_decode_paged_bhgd.12)") == "copy.115"
    assert devtrace.module_name("jit_run(15345879429575535474)") == "jit_run"


def _recorded():
    import json
    from pathlib import Path
    doc = json.loads((Path(__file__).parent / "data" /
                      "trace_excerpt.json").read_text())
    ops = [(devtrace.op_name(n), s, d) for n, s, d in doc["ops"]]
    mods = [(devtrace.module_name(n), s, d) for n, s, d in doc["modules"]]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    return devtrace.Reduced((hi - lo) / 1e9, ops, mods)


def test_recorded_trace_from_the_chip():
    r = _recorded()
    # the excerpt holds one chunk-prefill program, then a decode program
    # whose first five layers ran the paged kernel
    assert [m[0] for m in r.modules] == ["jit_run"] * 2
    assert [m[1] for m in r.modules_with("flash_decode_paged")] \
        == [99346061]
    assert r.op_count("flash_decode_paged") == 5
    # nested operations count once in the busy time
    assert 0 < r.busy_s <= r.window_s
    top = [n for n, _ in r.breakdown()["device_ops"]]
    assert top and not any(n.startswith("while") for n in top)
