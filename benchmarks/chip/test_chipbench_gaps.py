"""Gap kinds, percentiles and the metric readers, on hand-built reports."""

import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import gaps, spec, work  # noqa: E402


def _emits():
    # request 1 (a whole prefill) emits its first token at 2.5, inside 0's
    # gap (2, 3); request 2 (a tail prefill) at 4.2, inside 0's gap (4, 5)
    # and 1's gap (3.6, 4.6)
    return {0: [1.0, 2.0, 3.0, 4.0, 5.0],
            1: [2.5, 3.6, 4.6],
            2: [4.2, 5.2]}


def test_gap_kinds_follow_the_first_tokens_inside_them():
    g = gaps.classify(_emits(), {0: "full", 1: "full", 2: "tail"})
    assert len(g.values) == 4 + 2 + 1
    assert g.kinds == ["plain", "full", "plain", "tail",    # request 0
                       "plain", "tail",                      # request 1
                       "plain"]                              # request 2
    assert np.allclose(g.values, [1, 1, 1, 1, 1.1, 1, 1])
    assert g.share("full") == pytest.approx(100 / 7)


def test_a_gap_holding_both_kinds_is_full():
    g = gaps.classify({0: [0.0, 1.0], 1: [0.3], 2: [0.6]},
                      {0: "full", 1: "full", 2: "tail"})
    assert g.kinds == ["full"]


def test_summary_places_the_95th_percentile_rank():
    kinds = ["plain"] * 90 + ["tail"] * 8 + ["full"] * 2
    g = gaps.Gaps(np.arange(100, dtype=float), kinds)
    s = g.summary()
    assert s["share_pct"] == {"plain": 90.0, "tail": 8.0, "full": 2.0}
    assert s["p95_edge_margin_pct"] == pytest.approx(3.0)
    assert s["median_s"]["full"] == pytest.approx(98.5)


def test_percentile_interpolates_between_ranks():
    assert gaps.percentile([1, 2, 3, 4], 50) == 2.5
    assert gaps.percentile(list(range(101)), 95) == 95.0
    with pytest.raises(ValueError):
        gaps.percentile([], 90)


def _result(rid, prompt_len, n, admitted_at, shared=0):
    return types.SimpleNamespace(rid=rid, prompt_len=prompt_len,
                                 tokens=np.zeros(prompt_len + n, np.int32),
                                 admitted_at=float(admitted_at),
                                 shared_prefix=shared)


def _run(**kw):
    report = types.SimpleNamespace(
        results=[_result(0, 10, 3, 0), _result(1, 20, 2, 1, shared=16)],
        steps=3, slots=2, prefill_tokens=14, shared_prefill_tokens=16)
    base = dict(config={}, traffic={}, report=report,
                due={0: 0.0, 1: 1.0}, emits={0: [0.5, 1.5, 2.5],
                                             1: [1.2, 2.7]},
                gaps=gaps.classify({0: [0.5, 1.5, 2.5], 1: [1.2, 2.7]},
                                   {0: "full", 1: "tail"}),
                window_s=2.5, setup_s=30.0, trace=None, peaks=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_decode_lanes_and_prefilled_positions():
    run = _run()
    # request 0 decodes at clocks 0, 1 over 11, 12 positions; request 1 at
    # clock 1 over 21
    assert work.decode_contexts(run.report) == {0: [11], 1: [12, 21]}
    assert work.prefilled(run.report) == [(0, 10), (16, 20)]


def test_end_to_end_readers():
    run = _run()
    read = lambda name: spec.load_metric(name).read(run)  # noqa: E731
    assert read("tok_s") == pytest.approx(5 / 2.5)
    assert read("ttft_p90_s") == pytest.approx(
        np.percentile([0.5, 0.2], 90))
    assert read("tbt_p95_s") == pytest.approx(np.percentile([1, 1, 1.5], 95))
    assert read("setup_s") == 30.0


def test_scheduler_readers():
    run = _run()
    read = lambda name: spec.load_metric(name).read(run)  # noqa: E731
    assert read("sched.occupancy_pct") == pytest.approx(100 * 3 / 6)
    assert read("sched.prefix_hit_pct") == pytest.approx(100 * 16 / 30)
    # 0's gap (1.5, 2.5) holds nothing; (0.5, 1.5) holds 1's first token
    assert read("sched.stall_gap_pct") == pytest.approx(100 / 3)


def test_trace_readers_read_nothing_without_a_trace():
    run = _run()
    for name in ("step.decode_ms", "step.prefill_ms_per_ktok",
                 "device.idle_pct", "device.mfu_pct",
                 "kernel.paged_decode_roofline",
                 "kernel.paged_mla_decode_roofline"):
        assert spec.load_metric(name).read(run) is None


def test_trace_readers():
    trace = {"window_s": 2.0, "busy_s": 1.5,
             "modules": {"jit_serve_step": [0.3, 3], "jit_prefill": [0.028, 1]},
             "ops": {"jit_serve_step/paged_decode_dense.9": 0.2}}
    run = _run(trace=trace, peaks={"bf16_flop_s": 1e12, "hbm_byte_s": 1e9})
    read = lambda name: spec.load_metric(name).read(run)  # noqa: E731
    assert read("step.decode_ms") == pytest.approx(100.0)
    assert read("step.prefill_ms_per_ktok") == pytest.approx(28 / 0.014)
    assert read("device.idle_pct") == pytest.approx(25.0)
    assert read("kernel.paged_mla_decode_roofline") is None   # no such op
