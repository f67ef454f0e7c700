"""``expert_matmul_call_ms.*``: the reader on a made trace (no chip)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402


def trace_of(names_and_ms):
    events, at = [], 0
    for name, ms in names_and_ms:
        events.append([name, at, int(ms * 1e6)])
        at += int(ms * 1e6) + 1000
    return {"devices": {"/device:TPU:0": {"XLA Ops": events}}, "host": []}


@pytest.mark.parametrize("metric,whole", [
    ("expert_matmul_call_ms.longgen", 64 * 2048 * 1408 * 2),
    ("expert_matmul_call_ms.serve", 8 * 4096 * 14336 * 2)])
def test_the_matched_calls_time_over_their_number(metric, whole):
    bench = spec.Benchmark(ROOT)
    read, file = bench.reader(metric), bench.layer_metric(metric)
    run = {"facts": {}, "trace": trace_of([
        ("ragged-dot-none.1 custom-call f32[768,1408]", 1.2),                   # XLA's
        ("ragged-dot-none.4 custom-call (tuple)", 0.002),                       # and its set-up call
        ("gmm_ragged_dot.7 custom-call bf16[2816,2048]", 0.4),                  # the kernel
        ("gmm_ragged_dot.7 custom-call bf16[2816,2048]", 0.5),
        ("fusion.356 fusion bf16[128,163840]", 5.0),                            # not a call
        ("paged_mla_decode_attention.21 custom-call bf16[128,16,512]", 3.0)])}
    assert read(run, file) == pytest.approx(0.7)
    facts = run["facts"]["expert_matmul"]
    assert facts["calls"] == 3 and facts["whole_stack_bytes"] == whole
    assert facts["by_result_shape"]["bf16[2816,2048]"] == {"calls": 2,
                                                           "ms_a_call": pytest.approx(0.45)}
    assert facts["gb_s_if_whole_stack"] == pytest.approx(whole / 0.7e-3 / 1e9)


def test_nothing_to_read_leaves_the_metric_out():
    bench = spec.Benchmark(ROOT)
    read = bench.reader("expert_matmul_call_ms.serve")
    file = bench.layer_metric("expert_matmul_call_ms.serve")
    assert read({"trace": None}, file) is None
    # a program with no such call: a dense model
    assert read({"trace": trace_of([("fusion.1 fusion bf16[64,4096]", 1.0)])}, file) is None
