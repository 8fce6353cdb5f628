"""Tensor-parallel sharded serving: head/latent-sharded decode across a
device mesh must be GREEDY BIT-IDENTICAL to single-device serving.

The correctness bar (deterministic TP): every serving contraction is either
column-parallel (bitwise per shard) or runs full-width on replicated/gathered
operands — see the ``tp_collect`` rule in ``distributed/sharding.py`` — so
``Engine.serve(shards=N)`` emits the EXACT token stream of ``serve()`` for
dense / GQA / MLA across paged, contiguous, prefix-shared, speculative, and
pallas-kernel modes. The pool partitions on heads (MLA: the latent rank), so
per-device pool bytes drop to ~partitioned/N + replicated.

Multi-device cases need simulated devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before the first jax
import (the CI ``shard-smoke`` job sets it); without it they skip and only
the host-side validation/accounting tests run.
"""

import dataclasses
import functools

import numpy as np
import jax
import pytest

from repro.configs.registry import smoke_config
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.models import kv_cache
from repro.serving.engine import Engine
from repro.serving.scheduler import random_trace, shared_prefix_trace
from repro.serving.sharded import (
    check_sharded_consistency, pool_report, validate_serving_mesh,
    validate_serving_shards,
)

NDEV = len(jax.devices())

needs4 = pytest.mark.skipif(
    NDEV < 4,
    reason="needs 4 simulated devices: run with XLA_FLAGS="
           "--xla_force_host_platform_device_count=4 (set before the first "
           "jax import; see README 'Multi-device serving')")


@functools.lru_cache(maxsize=None)
def _setup(arch, **cfg_over):
    cfg = smoke_config(arch)
    if cfg_over:
        cfg = dataclasses.replace(cfg, **cfg_over)
    m = build_model(cfg)
    params, _ = m.init_split(jax.random.PRNGKey(0))
    return cfg, m, Engine(m, params, max_new=6)


def _trace(vocab, n=5, seed=0):
    return random_trace(n, vocab, seed=seed, prompt_lens=(4, 8),
                        max_new_range=(4, 6), arrival_spacing=1.0)


# ---------------------------------------------------------------- validation

def test_shard_validation_dense_heads():
    cfg = smoke_config("olmo-1b")                   # n_heads = 4
    validate_serving_shards(cfg, 1)
    validate_serving_shards(cfg, 2)
    validate_serving_shards(cfg, 4)
    with pytest.raises(ValueError, match="n_heads=4 is not divisible"):
        validate_serving_shards(cfg, 3)


def test_shard_validation_gqa_kv_heads():
    cfg = smoke_config("qwen2.5-32b")               # n_heads=4, n_kv_heads=1
    with pytest.raises(ValueError, match="n_kv_heads=1 is not divisible"):
        validate_serving_shards(cfg, 2)
    validate_serving_shards(dataclasses.replace(cfg, n_kv_heads=2), 2)


def test_shard_validation_mla_latent_rank():
    cfg = smoke_config("minicpm3-4b")               # mla, kv_lora_rank=64
    validate_serving_shards(cfg, 4)
    bad = dataclasses.replace(cfg, kv_lora_rank=6)
    with pytest.raises(ValueError, match="kv_lora_rank=6 is not divisible"):
        validate_serving_shards(bad, 4)


@pytest.mark.parametrize("arch", ["mamba2-780m", "hymba-1.5b"])
def test_shard_validation_rejects_headless_families(arch):
    with pytest.raises(ValueError, match="no head axis"):
        validate_serving_shards(smoke_config(arch), 2)


def test_serving_mesh_needs_model_axis():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="model"):
        validate_serving_mesh(smoke_config("olmo-1b"), mesh)


def test_make_serving_mesh_too_few_devices_names_the_recipe():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_serving_mesh(NDEV + 1)


def test_serve_shards_validates_before_placement():
    """Engine.serve(shards=N) must fail loudly on a non-dividing shard count
    without ever touching devices."""
    cfg, m, eng = _setup("olmo-1b")
    reqs = _trace(cfg.vocab, n=2)
    with pytest.raises(ValueError):
        eng.serve(reqs, paged=True, shards=NDEV + 1)
    if NDEV >= 4:
        with pytest.raises(ValueError, match="n_heads=4 is not divisible"):
            eng.serve(reqs, paged=True, shards=3)


# ------------------------------------------------------------ pool accounting

def test_consistency_check_needs_shards():
    """The check compares one device with a NAMED shard count: no default
    that quietly spans however many devices this host has."""
    with pytest.raises(ValueError, match="shards=N or a mesh"):
        check_sharded_consistency(None, [])


def test_pool_report_partitions_pool_bytes():
    """Analytic accounting over the REAL pool builders: partitioned bytes
    divide by shards, replicated bytes are paid per device, and one shard
    degenerates to the single-device total."""
    cfg = smoke_config("olmo-1b")
    geom = dict(slots=4, cache_len=64, block_size=16, num_blocks=20)
    one = pool_report(cfg, n_shards=1, **geom)
    four = pool_report(cfg, n_shards=4, **geom)
    assert one["per_device_bytes"] == one["total_bytes"]
    assert four["total_bytes"] == one["total_bytes"]
    assert four["per_device_bytes"] == \
        four["partitioned_bytes"] / 4 + four["replicated_bytes"]
    assert four["per_device_bytes"] < one["per_device_bytes"]
    # the K/V pools dominate the block tables: most bytes must partition
    assert four["partitioned_bytes"] > four["replicated_bytes"]
    assert four["capacity_ratio"] > 2.0


def test_pool_report_mla_latent_partitions():
    """The MLA latent pool partitions on the rank dim; its per-token rope
    keys replicate (every shard scores against full rope)."""
    cfg = smoke_config("minicpm3-4b")
    rep = pool_report(cfg, slots=4, cache_len=64, block_size=16,
                      num_blocks=20, n_shards=4)
    assert rep["partitioned_bytes"] > 0
    assert rep["replicated_bytes"] > 0
    assert rep["per_device_bytes"] < rep["total_bytes"]


def test_pool_report_rejects_bad_shards():
    with pytest.raises(ValueError, match="not divisible"):
        pool_report(smoke_config("olmo-1b"), slots=4, cache_len=64,
                    block_size=16, num_blocks=20, n_shards=3)


# ----------------------------------------------------------- bitwise parity

@needs4
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_paged_parity_dense(shards):
    cfg, m, eng = _setup("olmo-1b")
    rep = check_sharded_consistency(eng, _trace(cfg.vocab), shards=shards,
                                    paged=True)
    assert rep, rep


@needs4
def test_sharded_paged_parity_gqa():
    """Grouped-query KV (fewer KV heads than Q heads) shards on the KV-head
    dim — 2 shards × 2 KV heads."""
    cfg, m, eng = _setup("qwen2.5-32b", n_kv_heads=2)
    rep = check_sharded_consistency(eng, _trace(cfg.vocab, seed=1), shards=2,
                                    paged=True)
    assert rep, rep


@needs4
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_paged_parity_mla(shards):
    """The MLA latent POOL is rank-sharded (the capacity win); the attend
    view gathers the rank so scores stay bitwise per head."""
    cfg, m, eng = _setup("minicpm3-4b")
    rep = check_sharded_consistency(eng, _trace(cfg.vocab, seed=2),
                                    shards=shards, paged=True)
    assert rep, rep


@needs4
def test_sharded_contiguous_parity():
    cfg, m, eng = _setup("olmo-1b")
    rep = check_sharded_consistency(eng, _trace(cfg.vocab, seed=3), shards=4,
                                    paged=False)
    assert rep, rep


@needs4
def test_sharded_composes_with_prefix_share():
    """CoW/refcounting is host-side and shard-agnostic: prefix-shared paged
    serving under a mesh emits the single-device stream, and the shared-token
    accounting matches too."""
    cfg, m, eng = _setup("olmo-1b")
    reqs = shared_prefix_trace(5, cfg.vocab, prefix_len=16, seed=4,
                               suffix_lens=(2, 4), max_new_range=(4, 6))
    kw = dict(paged=True, prefix_share=True)
    base = eng.serve(reqs, **kw)
    shrd = eng.serve(reqs, shards=4, **kw)
    for a, b in zip(base.results, shrd.results):
        assert a.rid == b.rid and np.array_equal(a.tokens, b.tokens)
        assert a.shared_prefix == b.shared_prefix
    assert sum(r.shared_prefix for r in shrd.results) > 0


@needs4
def test_sharded_composes_with_speculative():
    """Draft-verify under the mesh: accepted-token counts and the emitted
    streams match the single-device speculative run exactly."""
    cfg, m, eng = _setup("olmo-1b")
    reqs = _trace(cfg.vocab, seed=5)
    kw = dict(paged=True, speculative=True, draft_k=3)
    base = eng.serve(reqs, **kw)
    shrd = eng.serve(reqs, shards=4, **kw)
    for a, b in zip(base.results, shrd.results):
        assert a.rid == b.rid and np.array_equal(a.tokens, b.tokens)
        assert a.accepted == b.accepted


@needs4
def test_sharded_composes_with_pallas_kernel():
    """The fused paged-decode kernel partitions under the mesh like the jnp
    path (same grid per shard, fewer heads each)."""
    from repro.core.softmax_variants import SoftmaxSpec
    cfg = smoke_config("olmo-1b").with_softmax(SoftmaxSpec("int"))
    m = build_model(cfg)
    params, _ = m.init_split(jax.random.PRNGKey(0))
    eng = Engine(m, params, max_new=6)
    rep = check_sharded_consistency(eng, _trace(cfg.vocab, seed=6), shards=4,
                                    paged=True, kernel="pallas")
    assert rep, rep


# -------------------------------------------------- compiled-step contract

@needs4
def test_sharded_serve_zero_retraces():
    """The one-compiled-step contract survives the mesh: serving two traces
    through the same geometry keeps a single executable in the jit cache.
    Needs its own engine — the module-shared one has served other
    geometries through the same compiled step."""
    cfg = smoke_config("olmo-1b")
    m = build_model(cfg)
    params, _ = m.init_split(jax.random.PRNGKey(0))
    eng = Engine(m, params, max_new=6)
    mesh = make_serving_mesh(4)
    eng.serve(_trace(cfg.vocab, seed=7), paged=True, mesh=mesh,
              cache_len=32, slots=4)
    eng.serve(_trace(cfg.vocab, seed=8), paged=True, mesh=mesh,
              cache_len=32, slots=4)
    assert eng._get_serve_step("jnp", mesh)._cache_size() == 1


@needs4
def test_sharded_cache_donation_reuses_buffers():
    """donate_argnums on a NamedSharding carry must be a true in-place
    donation: the stepped cache's per-shard buffers live at the SAME device
    addresses as the input's — no relayout, no copy."""
    cfg, m, eng = _setup("olmo-1b")
    mesh = make_serving_mesh(4)
    ex = eng._mesh_exec(mesh)
    slots, C = 4, 32
    from repro.serving.sharded import place_cache
    cache = place_cache(kv_cache.cache_zeros(cfg, slots, C),
                        kv_cache.serve_cache_axes(cfg, slots, C),
                        ex["rules"], mesh)

    def ptrs(tree):
        out = set()
        for leaf in jax.tree.leaves(tree):
            for s in leaf.addressable_shards:
                out.add(s.data.unsafe_buffer_pointer())
        return out

    step = eng._get_serve_step("jnp", mesh)
    tok = np.zeros((slots, 1), np.int32)
    pos = np.full((slots,), C, np.int32)          # parked: no write lands
    keys = np.zeros((slots, 2), np.uint32)
    done = np.ones((slots,), bool)
    # warm up the executable so the measured step is a pure donate-and-run
    cache, *_ = step(ex["params"], cache, tok, pos, keys, done)
    before = ptrs(cache)
    cache, *_ = step(ex["params"], cache, tok, pos, keys, done)
    assert ptrs(cache) == before
