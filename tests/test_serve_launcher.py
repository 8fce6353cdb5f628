"""The serving launcher's set-up and the compile-cache helper, in-process.

``repro.launch.serve.parse_args`` + ``build_engine`` are the one set-up that
``python -m repro.launch.serve`` and ``chip_smoke.py`` share; here they run
on the smoke config with ``--warm-steps 0`` (seeded params, no optimizer
state) and serve a 2-request trace.
"""

import dataclasses
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.launch.serve import build_engine, parse_args
from repro.serving.scheduler import random_trace

ROOT = Path(__file__).resolve().parents[1]


def test_warm_steps_default_follows_smoke():
    """Quick training only at smoke size: at published widths the AdamW
    state would not fit one chip, so the default there is seeded params."""
    smoke, _ = parse_args(["--arch", "olmo-1b", "--smoke"])
    full, _ = parse_args(["--arch", "olmo-1b"])
    assert (smoke.smoke, smoke.warm_steps) == (True, 120)
    assert (full.smoke, full.warm_steps) == (False, 0)


def test_build_engine_warm_steps_0_serves_trace(capsys):
    args, options = parse_args([
        "--arch", "olmo-1b", "--smoke", "--softmax", "int",
        "--warm-steps", "0", "--max-new", "4", "--continuous", "--paged",
        "--slots", "2"])
    engine = build_engine(args)
    assert "seeded random params" in capsys.readouterr().out
    cfg = engine.model.cfg
    assert cfg.name == "olmo-1b-smoke" and cfg.softmax.kind == "int"
    # the single-device path sits on one device, whatever the host has
    assert engine.model.ctx.mesh.devices.size == 1
    assert all(len(x.devices()) == 1 for x in jax.tree.leaves(engine.params))
    reqs = random_trace(2, cfg.vocab, seed=0, prompt_lens=(4, 8),
                        max_new_range=(4, 4))
    rep = engine.serve(reqs, options=options)
    assert [r.rid for r in rep.results] == [0, 1]
    for req, res in zip(reqs, rep.results):
        assert res.tokens.shape == (req.prompt_len + 4,)
        np.testing.assert_array_equal(res.tokens[:req.prompt_len],
                                      req.prompt)
    assert rep.leaked_blocks == 0


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets nothing: JAX reads
    the variable itself."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_ignored_dir(monkeypatch,
                                                    restore_cache_dir):
    """Unset: a fixed ``.jax_cache`` at the checkout root (no temporary
    name, pid or time in the path — the path is part of the cache key),
    which git ignores."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(ROOT / ".jax_cache") == compile_cache.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == got
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_lower_serve_step_and_decode_executor():
    """The hooks ``chip_smoke.py`` reads the served programs through: the
    lowered paged decode step of each kernel, and the (model, params) each
    kernel decodes with."""
    args, options = parse_args([
        "--arch", "olmo-1b", "--smoke", "--warm-steps", "0", "--max-new",
        "4", "--continuous", "--paged", "--slots", "2"])
    engine = build_engine(args)
    fused_opts = dataclasses.replace(options, kernel="pallas")
    reqs = random_trace(2, engine.model.cfg.vocab, seed=1, prompt_lens=(8,),
                        max_new_range=(4, 4))
    rep = engine.serve(reqs, options=fused_opts)
    fused = engine.lower_serve_step(fused_opts, rep.cache_len).as_text()
    gather = engine.lower_serve_step(options, rep.cache_len).as_text()
    assert fused != gather
    with pytest.raises(ValueError, match="paged"):
        engine.lower_serve_step(dataclasses.replace(options, paged=False),
                                rep.cache_len)
    model, params = engine.decode_executor("pallas")
    assert model.cfg.softmax.kind == "int_pallas_paged"
    assert params is engine.params
    assert engine.decode_executor()[0] is engine.model


def test_continuous_prints_the_host_loop_table(capsys):
    """``--continuous`` reports where the serve loop's host time went: each
    phase with its self time, count and longest span, the rest as ``loop``,
    and the gaps between tokens by kind."""
    from repro.launch.serve import main

    main(["--arch", "olmo-1b", "--smoke", "--softmax", "int",
          "--warm-steps", "0", "--max-new", "4", "--continuous", "--paged",
          "--requests", "3", "--slots", "2", "--prompt-len", "8"])
    out = capsys.readouterr().out
    table = out[out.index("host loop ("):].splitlines()
    phases = [row.split()[0] for row in table[1:8]]
    assert phases == ["admission", "first_token", "step_sync", "emit",
                      "swap_out", "resume", "loop"]
    assert int(table[1].split()[2]) == 3         # one admission per request
    assert table[8].startswith("gaps between tokens: plain ")
