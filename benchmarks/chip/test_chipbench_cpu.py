"""Whole runs of the harness on the CPU at a small size.

A run without a TPU fails instead of falling back to the CPU. Past the
look for a chip, a run drives the same set-up, window, readers and check as
on the chip: it comes out correct on the program as it is, and not correct
with the timed path broken underneath in each way a serving cell can be
(the decode step leaves the cache unchanged, half the batch is left out, a
token is altered where it is sampled). The control, the reference in fp8,
reads gaps the program's runs do not reach.
"""

import importlib.util
import io
import json
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from chipbench import harness, spec  # noqa: E402

SMALL = dict(
    name="small", family="dense", attention="gqa", n_layers=2, d_model=128,
    n_heads=4, n_kv_heads=2, d_head=32, d_ff=256, vocab=512, max_seq=4096,
    norm="layernorm_np", act="silu", rope_theta=10000.0, tie_embeddings=True,
    softmax={"kind": "int", "M": 6, "N": 16, "T_C": -7.0}, reference="decoder",
    limits={"logit_gap": 0.5, "mean_logit_gap": 0.025})
MIX = {"why": "small doc-QA", "sessions_per_s": 1.0, "initial_sessions": 1,
       "session_every_steps": 3.0, "asks_per_session": {"values": [2, 3]},
       "ask_every_steps": 2.0, "prefix_len": {"values": [32]},
       "prompt_len": {"values": [8, 16]}, "answer_len": {"min": 3, "max": 8},
       "serve": {"slots": 3, "pool_blocks": 16},
       "shape_seed": 5}
E2E = [("tok_s", "tokens/s"), ("ttft_p90_s", "s"), ("tbt_p95_s", "s"),
       ("setup_s", "s")]


def _run(seed=2 ** 31 + 12345):
    cell = spec.Cell("small", 1, SMALL, MIX, E2E, [])
    return harness.run_cell(cell, seed, 4, False, time.perf_counter(),
                            require_tpu=False, err=io.StringIO())


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "olmo-1b.chat", "--seed", str(2 ** 31 + 1), "--seconds", "5",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_the_compile_cache_stays_inside_the_checkout():
    code = ("import sys; sys.path[:0] = %r\n"
            "from chipbench import harness\n"
            "import jax\n"
            "print(harness.use_compile_cache(), "
            "jax.config.jax_compilation_cache_dir)"
            % [HERE, os.path.join(ROOT, "src")])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, "elsewhere"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    inside = os.path.join(ROOT, ".jax_cache")
    assert p.stdout.split() == [inside, inside]


def test_a_sound_run_is_correct_and_reports_its_metrics():
    r = _run()
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {n for n, _ in E2E}
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert all(set(c) == {"value", "limit"} for c in r["checks"].values())
    json.dumps(r)


def test_a_cell_on_more_chips_than_jax_sees_is_refused():
    cell = spec.Cell("small", 4, SMALL, MIX, E2E, [])
    with pytest.raises(harness.NoChip):
        harness.run_cell(cell, 7, 4, False, time.perf_counter(),
                         require_tpu=False, err=io.StringIO())


FOUR_CHIPS = """
import io, json, sys, time
sys.path[:0] = {paths!r}
import jax
from chipbench import harness, spec
import test_chipbench_cpu as t
cfg = dict(t.SMALL, n_kv_heads=4)
cell = spec.Cell("small", 4, cfg, t.MIX, t.E2E, [])
devs = harness.devices(4, False)
eng, _, options = harness.build(cfg, t.MIX, 3, devs)
print(json.dumps({{"mesh": int(options.mesh.devices.size),
                  "serving": len(harness.serving_devices(eng, options))}}))
r = harness.run_cell(cell, 2 ** 31 + 99, 4, False, time.perf_counter(),
                     require_tpu=False, err=io.StringIO())
print(json.dumps(r))
"""


def test_a_four_chip_cell_serves_on_a_mesh_of_its_four_chips():
    """A cell's ``chips`` alone puts it on a serving mesh of that many
    devices (four simulated CPU devices here), with its weights made in
    the serving placement, and the run reports the devices it used."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(paths=[HERE, os.path.join(ROOT, "src")])
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    placed, r = [json.loads(x) for x in p.stdout.strip().splitlines()[-2:]]
    assert placed == {"mesh": 4, "serving": 4}
    assert r["device"]["count"] == 4
    assert r["correct"] is True, r["checks"]


def _drop_cache_writes(monkeypatch):
    import repro.models.attention as attention
    import repro.models.mla as mla

    def unchanged(pool, table, new, cache_pos):
        return pool

    monkeypatch.setattr(attention, "paged_write", unchanged)
    monkeypatch.setattr(mla, "paged_write", unchanged)


def _drop_half_the_batch(monkeypatch):
    import jax.numpy as jnp

    import repro.serving.engine as engine

    orig = engine.make_serve_step_fn

    def broken(*a, **k):
        step = orig(*a, **k)

        def half(params, cache, tok, pos, keys, done):
            cache, toks, keys, done = step(params, cache, tok, pos, keys,
                                           done)
            odd = jnp.arange(toks.shape[0]) % 2 == 1
            return cache, jnp.where(odd, tok[:, 0], toks), keys, done
        return half

    monkeypatch.setattr(engine, "make_serve_step_fn", broken)


def _alter_tokens(monkeypatch):
    import repro.serving.engine as engine

    orig = engine.make_sampler

    def broken(*a, **k):
        sample = orig(*a, **k)

        def altered(logits, key):
            tok = sample(logits, key)
            return (tok + (tok % 5 == 0)) % SMALL["vocab"]
        return altered

    monkeypatch.setattr(engine, "make_sampler", broken)


@pytest.mark.parametrize("fault", [_drop_cache_writes, _drop_half_the_batch,
                                   _alter_tokens],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = _run()
    assert r["correct"] is False, r["checks"]


def test_the_control_is_not_correct_by_the_limits_a_run_holds():
    """At a size a test can hold (d_model 512, vocab 8,192) the fp8 control,
    put in the program's place and judged by the configuration's limits as
    a run is judged, comes out not correct on every seed, while the program
    comes out correct; at the cells' own size the chip readings in PERF.md
    set the limits."""
    spec_ = importlib.util.spec_from_file_location(
        "chipbench_calibrate", os.path.join(HERE, "calibrate.py"))
    cal = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(cal)
    cfg = dict(SMALL, d_model=512, n_heads=4, n_kv_heads=4, d_head=128,
               d_ff=1024, vocab=8192)
    mix = dict(MIX, prefix_len={"values": [48]}, prompt_len={"values": [16]},
               answer_len={"min": 8, "max": 16},
               serve=dict(MIX["serve"], pool_blocks=64))
    cell = spec.Cell("small", 1, cfg, mix, [], [])
    s = cal.calibrate(cell, [11, 12, 13], 3, 4, require_tpu=False,
                      out=io.StringIO())
    assert s["correct"] == [True] * 3, s
    assert s["control_correct"] == [False] * 3, s
