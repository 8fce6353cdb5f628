"""The traffic generator and the serving geometry it derives, and the
benchmark file's own consistency: every name it uses finds its file."""

import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import spec, traffic  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
RUN_SECONDS = BENCH["run_seconds"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_request_of_the_window_fits_the_derived_cache(cell):
    """The scheduler accepts the whole window's trace at the geometry the
    mix derives (the minicpm3-4b doc-QA trace exceeded a cache sized from
    the drawn prompts before), and the pool holds the largest request."""
    from repro.serving.scheduler import BlockAllocator, SlotScheduler

    c = spec.load_cell(ROOT, cell)
    geo = traffic.geometry(c.traffic)
    asks = traffic.shape(c.traffic, RUN_SECONDS)
    prompts = traffic.tokens(asks, c.config["vocab"], 2 ** 31 + 11)
    from chipbench.harness import requests

    reqs = requests(asks, c.config["vocab"], 2 ** 31 + 11)
    assert [len(r.prompt) for r in reqs] == [len(p) for p in prompts]
    serve = traffic.serve_settings(c.traffic)
    SlotScheduler(reqs, serve["slots"], geo["cache_len"])
    alloc = BlockAllocator(geo["num_blocks"], serve["block_size"])
    assert max(alloc.blocks_needed(r.prompt_len, r.max_new)
               for r in reqs) <= geo["num_blocks"]
    assert geo["cache_len"] % serve["block_size"] == 0


def test_minicpm_doc_qa_geometry_by_hand():
    mix = spec.load_cell(ROOT, "minicpm3-4b.doc-qa").traffic
    # 4,096-token document + 192-token question + 128-token answer
    assert traffic.geometry(mix)["cache_len"] == 4096 + 192 + 128


def test_shape_is_fixed_by_the_mix_and_tokens_by_the_seed():
    mix = spec.load_cell(ROOT, "olmo-1b.doc-qa").traffic
    a, b = traffic.shape(mix, 45), traffic.shape(mix, 45)
    assert a == b
    ta = traffic.tokens(a, 50304, 2 ** 31 + 5)
    tb = traffic.tokens(a, 50304, 2 ** 31 + 6)
    assert all(np.array_equal(x, y) for x, y in
               zip(ta, traffic.tokens(a, 50304, 2 ** 31 + 5)))
    assert not all(np.array_equal(x, y) for x, y in zip(ta, tb))
    # the asks of one session share its document token for token
    by_session = {}
    for ask, toks in zip(a, ta):
        doc = toks[:ask.prefix_len]
        if ask.session in by_session:
            assert np.array_equal(by_session[ask.session], doc)
        by_session[ask.session] = doc
    assert [x.arrival for x in a] == sorted(x.arrival for x in a)


def test_warm_up_covers_every_prompt_shape():
    mix = spec.load_cell(ROOT, "olmo-1b.doc-qa").traffic
    first = traffic.shape(mix, 45)[0]
    warm = traffic.warm_asks(mix, first)
    # opens with the window's first shape, into a fresh pool
    assert (warm[0].prefix_len, warm[0].prompt_len) == (
        first.prefix_len, first.prompt_len)
    whole = [(a.prefix_len, a.prompt_len) for a in warm
             if a.session == a.rid]
    assert sorted(set(whole)) == [(3072, q) for q in (64, 128, 192)]
    # a resident document asked every question length again
    tails = [a for a in warm if a.session != a.rid]
    assert [a.prompt_len for a in tails] == [64, 128, 192]
    lead = warm[tails[0].session]
    assert lead.prompt_len == 64 and lead.rid < tails[0].rid
    assert [a.arrival for a in warm] == sorted(a.arrival for a in warm)
    assert [a.rid for a in warm] == list(range(len(warm)))
    chat = spec.load_cell(ROOT, "olmo-1b.chat").traffic
    warm = traffic.warm_asks(chat, traffic.shape(chat, 45)[0])
    assert sorted({a.prompt_len for a in warm}) == [64, 128, 256, 512,
                                                    1024, 2048]
    assert len(warm) == 7


def test_a_pool_smaller_than_two_requests_is_refused():
    mix = dict(spec.load_cell(ROOT, "olmo-1b.doc-qa").traffic)
    mix["serve"] = dict(mix["serve"], pool_blocks=100)
    with pytest.raises(ValueError, match="pool_blocks"):
        traffic.geometry(mix)


def test_a_serve_block_sets_serve_options_but_not_what_is_derived():
    from repro.serving import ServeOptions

    mix = dict(spec.load_cell(ROOT, "olmo-1b.doc-qa").traffic)
    assert traffic.serve_settings(mix) == {
        "slots": 8, "block_size": 16, "prefix_share": True,
        "kernel": "pallas"}
    # a later mix sets any other ServeOptions field as data
    mix["serve"] = dict(mix["serve"], kernel="jnp", prefill_chunk=256)
    got = traffic.serve_settings(mix)
    assert got["kernel"] == "jnp" and got["prefill_chunk"] == 256
    ServeOptions(**got, paged=True)
    for derived in ("cache_len", "num_blocks", "paged", "mesh", "shards"):
        bad = dict(mix, serve=dict(mix["serve"], **{derived: 1}))
        with pytest.raises(ValueError, match="derived"):
            traffic.serve_settings(bad)
    with pytest.raises(ValueError, match="missing"):
        traffic.validate(dict(mix, serve={"slots": 8}))


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_file_finds_every_file_it_names():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.exists(os.path.join(
            HERE, "references", cfg["reference"] + ".py"))
        assert "mean_logit_gap" in cfg["limits"]
        # every key changed from the source is listed, with how it differs
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg.get("departures", {})) == set(c["reduced"])
        assert set(cfg["limits"]) <= {"logit_gap", "mean_logit_gap"}
    cells = set(CELLS)
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        traffic.validate(spec.load_cell(ROOT, w["name"]).traffic)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert {"setup_s"} <= e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
        assert callable(spec.load_metric(m["name"]).read)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
