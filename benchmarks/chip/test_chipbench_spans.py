"""The readers of the serve loop's own spans and counters: the in-program
stall share against the harness's clock on CPU serves, and the host readers
on hand-built reports and traces."""

import math
import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from chipbench import gaps, harness, observe, spec, traffic  # noqa: E402

SMALL = dict(
    name="small", family="dense", attention="gqa", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, vocab=256, max_seq=4096,
    norm="layernorm_np", act="silu", rope_theta=10000.0, tie_embeddings=True,
    softmax={"kind": "int", "M": 6, "N": 16, "T_C": -7.0})
SHARED = {"why": "small doc-QA", "sessions_per_s": 1.0,
          "initial_sessions": 2, "session_every_steps": 4.0,
          "asks_per_session": {"values": [2, 3]}, "ask_every_steps": 2.0,
          "prefix_len": {"values": [32]}, "prompt_len": {"values": [8, 16]},
          "answer_len": {"min": 3, "max": 8},
          "serve": {"slots": 3, "pool_blocks": 24, "kernel": "jnp"},
          "shape_seed": 5}
UNSHARED = dict(SHARED, why="small chat", sessions_per_s=4.0,
                initial_sessions=3, session_every_steps=3.0,
                asks_per_session={"values": [1]}, prefix_len={"values": [0]},
                prompt_len={"values": [8, 16, 32]})


def _read(name, run):
    return spec.load_metric(name).read(run)


@pytest.fixture(scope="module", params=["shared", "unshared"])
def served(request):
    """(mix name, a reader's view of one CPU serve, the Recorder's gaps)."""
    mix = SHARED if request.param == "shared" else UNSHARED
    devs = harness.devices(1, require_tpu=False)
    eng, cfg, options = harness.build(SMALL, mix, 7, devs)
    reqs = harness.requests(traffic.shape(mix, 2), cfg.vocab, 2 ** 31 + 77)
    rec = observe.Recorder()
    with rec.attached():
        report = eng.serve(reqs, options=options)
    by_rid = report.by_rid()
    kind = {r.rid: "tail" if by_rid[r.rid].shared_prefix > 0 else "full"
            for r in reqs}
    g = gaps.classify(rec.emits, kind)
    return request.param, types.SimpleNamespace(report=report, gaps=g,
                                                trace=None)


def test_admit_stall_share_equals_the_harness_clock_reading(served):
    name, run = served
    assert _read("sched.admit_stall_gap_pct", run) == \
        _read("sched.stall_gap_pct", run)
    # both kinds the program counts agree with the harness's, gap by gap
    assert run.report.gap_kinds == {k: run.gaps.kinds.count(k)
                                    for k in gaps.KINDS}
    assert run.report.gap_kinds["full"] > 0
    if name == "shared":
        assert run.report.gap_kinds["tail"] > 0
    else:
        assert run.report.gap_kinds["tail"] == 0


def test_loop_ms_per_step_is_positive_and_finite(served):
    _, run = served
    v = _read("host.loop_ms_per_step", run)
    assert math.isfinite(v) and v > 0


def _hand_run():
    host_s = {p: [0.001, 4, 0.0005] for p in (
        "admission", "first_token", "step_sync", "emit", "swap_out",
        "resume")}
    host_s["loop"] = [0.003, 11, 0.001]
    rep = dict(steps=10, host_s=host_s, gap_kinds={"plain": 6, "tail": 3,
                                                   "full": 1})
    trace = {"window_s": 1.0, "busy_s": 0.9,
             "modules": {"jit_serve_step": [0.8, 10], "jit_prefill": [0.1, 2]},
             "idle_gaps": [["host:device_to_host", 0.04],
                           ["host:serve_loop", 0.03],
                           ["host:step_sync", 0.005],
                           ["host:emit", 0.002]]}
    return types.SimpleNamespace(report=types.SimpleNamespace(**rep),
                                 trace=trace)


def test_host_readers_on_a_hand_built_run():
    run = _hand_run()
    # (0.04 + 0.005) s of idle under the pulls over 10 decode steps
    assert _read("host.sync_idle_ms_per_step", run) == pytest.approx(4.5)
    # (emit 0.001 + loop 0.003) s over 10 steps
    assert _read("host.loop_ms_per_step", run) == pytest.approx(0.4)
    assert _read("sched.admit_stall_gap_pct", run) == pytest.approx(40.0)


def test_sync_idle_is_zero_when_no_idle_falls_under_the_pulls():
    run = _hand_run()
    run.trace["idle_gaps"] = [["host:serve_loop", 0.03]]
    assert _read("host.sync_idle_ms_per_step", run) == 0.0


@pytest.mark.parametrize("name", ["host.sync_idle_ms_per_step",
                                  "host.loop_ms_per_step",
                                  "sched.admit_stall_gap_pct"])
def test_a_program_without_the_counters_reads_nothing(name):
    """A report without ``host_s`` and ``gap_kinds`` (a program that keeps
    no such counters) gives no value, and raises nothing."""
    run = _hand_run()
    run.report = types.SimpleNamespace(steps=10)
    assert _read(name, run) is None


def test_sync_idle_reads_nothing_without_a_trace_or_a_decode_step():
    run = _hand_run()
    run.trace = None
    assert _read("host.sync_idle_ms_per_step", run) is None
    run = _hand_run()
    del run.trace["modules"]["jit_serve_step"]
    assert _read("host.sync_idle_ms_per_step", run) is None
