"""The serve loop's own spans and counters (``serving/spans.py``): phase
self times that add up to the wall clock, phase counts that match the work
done, gap kinds counted where the admissions happen, and the profiler spans
that carry them into a device trace."""

import glob

import jax
import numpy as np
import pytest

from repro.configs.registry import smoke_config
from repro.models import build_model
from repro.serving import ServeOptions
from repro.serving.engine import Engine
from repro.serving.scheduler import (
    Request, random_trace, shared_prefix_trace,
)
from repro.serving.spans import GAP_KINDS, PHASES, LoopSpans


@pytest.fixture(scope="module")
def engine():
    cfg = smoke_config("olmo-1b")
    m = build_model(cfg)
    params, _ = m.init_split(jax.random.PRNGKey(0))
    return cfg, Engine(m, params)


def _unshared(vocab):
    return random_trace(6, vocab, seed=3, prompt_lens=(4, 8, 12),
                        max_new_range=(2, 6), arrival_spacing=1.0)


def _shared(vocab):
    return shared_prefix_trace(6, vocab, prefix_len=16, seed=3,
                               max_new_range=(2, 6), arrival_spacing=1.0)


def _preempting(vocab):
    rng = np.random.default_rng(4)
    return [Request(rid=i, prompt=rng.integers(0, vocab, (12,), np.int32),
                    max_new=12, arrival=a, seed=300 + i, priority=p)
            for i, (a, p) in enumerate([(0.0, 1), (0.0, 1), (4.0, 0)])]


MODES = {
    "contiguous": (_unshared, dict(slots=2)),
    "paged_shared": (_shared, dict(slots=3, paged=True, prefix_share=True,
                                   block_size=4)),
    "chunked": (_unshared, dict(slots=2, paged=True, block_size=4,
                                prefill_chunk=4)),
    "speculative": (_unshared, dict(slots=2, speculative=True, draft_k=2)),
    "preemption": (_preempting, dict(slots=3, paged=True, block_size=4,
                                     num_blocks=16, preemption=True)),
}


@pytest.fixture(scope="module", params=sorted(MODES))
def served(request, engine):
    cfg, eng = engine
    trace, opts = MODES[request.param]
    reqs = trace(cfg.vocab)
    return request.param, reqs, eng.serve(reqs, options=ServeOptions(**opts))


def test_phase_self_times_and_the_loop_add_up_to_the_wall(served):
    _, _, rep = served
    assert set(rep.host_s) == set(PHASES) | {"loop"}
    for secs, n, longest in rep.host_s.values():
        assert secs >= 0 and n >= 0 and 0 <= longest <= secs
    assert sum(v[0] for v in rep.host_s.values()) == pytest.approx(
        rep.wall_s, rel=1e-9)


def test_phase_counts_follow_the_work(served):
    name, reqs, rep = served
    assert rep.host_s["step_sync"][1] == rep.steps
    assert rep.host_s["emit"][1] == rep.steps
    assert rep.host_s["first_token"][1] == len(reqs)
    assert rep.host_s["admission"][1] == len(reqs) + rep.resumes
    assert rep.host_s["swap_out"][1] == rep.preemptions
    assert rep.host_s["resume"][1] == rep.resumes
    if name == "preemption":
        assert rep.preemptions >= 1


def test_every_gap_between_tokens_has_one_kind(served):
    _, reqs, rep = served
    assert set(rep.gap_kinds) == set(GAP_KINDS)
    assert sum(rep.gap_kinds.values()) == sum(r.max_new - 1 for r in reqs)


def test_request_times_read_the_loop_clock(served):
    _, reqs, rep = served
    for req, res in zip(sorted(reqs, key=lambda r: r.rid), rep.results):
        assert len(res.tbt_s) == req.max_new - 1
        assert 0 < res.ttft_s <= res.latency_s <= rep.wall_s
        assert min(res.tbt_s, default=0.0) >= 0
        assert res.ttft_s + sum(res.tbt_s) <= res.latency_s


def test_prefix_hits_make_tail_gaps_and_unshared_prompts_none(engine):
    cfg, eng = engine
    opts = ServeOptions(slots=3, paged=True, prefix_share=True, block_size=4)
    shared = eng.serve(_shared(cfg.vocab), options=opts)
    unshared = eng.serve(_unshared(cfg.vocab), options=opts)
    assert shared.shared_prefill_tokens > 0 and shared.gap_kinds["tail"] > 0
    assert unshared.shared_prefill_tokens == 0
    assert unshared.gap_kinds["tail"] == 0 and unshared.gap_kinds["full"] > 0


def test_spans_land_on_the_host_plane_of_a_profiler_trace(engine, tmp_path):
    from jax.profiler import ProfileData

    cfg, eng = engine
    reqs = _unshared(cfg.vocab)
    opts = ServeOptions(slots=2)
    eng.serve(reqs, options=opts)           # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = eng.serve(reqs, options=opts)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("host:"):
                    seen.setdefault(e.name, []).append(dict(e.stats))
    rids = sorted(r.rid for r in reqs)
    assert sorted(s["rid"] for s in seen["host:admission"]) == rids
    assert sorted(s["rid"] for s in seen["host:first_token"]) == rids
    assert sorted(s["step"] for s in seen["host:step_sync"]) == \
        list(range(rep.steps))
    assert sorted(s["step"] for s in seen["host:emit"]) == \
        list(range(rep.steps))


class _Clock:
    """A clock that moves only when told to (nanoseconds)."""

    def __init__(self):
        self.t = 1000

    def __call__(self):
        return self.t


def test_a_phase_keeps_only_its_self_time():
    clock = _Clock()
    sp = LoopSpans(clock)
    sp.tick()
    clock.t += 10
    with sp.phase("admission", rid=0):
        clock.t += 5
        with sp.phase("first_token", rid=0):
            clock.t += 7
        clock.t += 3
    clock.t += 2
    wall, host = sp.close()
    assert host["admission"] == pytest.approx([8e-9, 1, 8e-9])
    assert host["first_token"] == pytest.approx([7e-9, 1, 7e-9])
    assert host["loop"] == pytest.approx([12e-9, 1, 12e-9])
    assert wall == pytest.approx(27e-9)


def test_gap_kinds_follow_the_activations_between_a_lanes_tokens():
    sp = LoopSpans(_Clock())
    sp.activated("full")
    sp.emitted(0)           # lane 0's first token: its own activation
    sp.emitted(0)           # plain
    sp.activated("tail")
    sp.emitted(1)
    sp.emitted(0)           # tail (lane 1 came in)
    sp.emitted(1)           # plain: lane 1's own activation is not counted
    sp.activated("tail")
    sp.activated("full")
    sp.emitted(0)           # full wins over tail
    assert sp.gap_kinds == {"plain": 2, "tail": 1, "full": 1}


def test_timings_measure_from_the_queue_entry():
    clock = _Clock()
    sp = LoopSpans(clock)
    sp.queued([5])
    clock.t += 2_000_000_000
    sp.queued([5])          # still queued: the entry time stays
    clock.t += 1_000_000_000
    sp.emitted(5)
    clock.t += 500_000_000
    sp.emitted(5, sp.now())
    clock.t += 250_000_000
    ttft, tbt, latency = sp.timings(5)
    assert (ttft, tbt, latency) == pytest.approx((3.0, [0.5], 3.75))
