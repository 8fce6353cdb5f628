"""The trace reduction, on a trace recorded on one TPU v5e chip while the
serve loop ran olmo-1b at smoke size (``testdata/serve_trace.xplane.pb``)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pytest  # noqa: E402

from chipbench import tracing  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "testdata", "serve_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tracing.reduce(FIXTURE)


def test_window_is_the_harness_span(reduced):
    assert reduced["window_s"] == pytest.approx(0.113992691, rel=1e-6)


def test_busy_time_is_the_union_of_ops_and_their_self_times_add_up(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert sum(reduced["ops"].values()) == pytest.approx(reduced["busy_s"],
                                                         rel=1e-9)


def test_programs_counted_by_name(reduced):
    secs, n = reduced["modules"]["jit_serve_step"]
    assert n == 17 and secs == pytest.approx(0.000616846, rel=1e-6)
    assert reduced["modules"]["jit_prefill"][1] == 6
    assert reduced["modules"]["jit_paged_scatter"][1] == 6


def test_ops_belong_to_their_program_and_loops_keep_only_self_time(reduced):
    kernel = reduced["ops"]["jit_serve_step/paged_decode_dense.9"]
    assert kernel == pytest.approx(0.000241036, rel=1e-6)
    # the layer loop holds the kernel: its self time leaves the kernel out
    assert reduced["ops"]["jit_serve_step/while.6"] < 0.0004 - kernel + 1e-6


def test_breakdown_lists_are_sorted_and_short(reduced):
    for key in ("device_ops", "idle_gaps"):
        vals = [v for _, v in reduced[key]]
        assert 0 < len(vals) <= 10 and vals == sorted(vals, reverse=True)
    assert reduced["device_ops"][0][0] == "jit_serve_step/paged_decode_dense.9"


def test_idle_time_is_attributed_to_host_spans(reduced):
    idle = dict(reduced["idle_gaps"])
    assert set(idle) <= {"host:serve_loop", "host:device_to_host",
                         "host:dispatch", "host:prefill", "host:scatter",
                         "host:decode_step", "host:first_token",
                         "host:prefill_tail", "host:prefix_view",
                         "host:admission"}
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_nested_ops_self_time():
    ops = [(0, 10, "while"), (2, 5, "k"), (6, 8, "f"), (12, 13, "g")]
    got = {name: s for _, _, name, s in tracing._self_times(ops)}
    assert got == {"while": 5, "k": 3, "f": 2, "g": 1}


def test_a_trace_without_a_tpu_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    with pytest.raises(ValueError, match="no TPU device plane"):
        tracing.reduce(str(path))
