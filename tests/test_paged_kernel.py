"""Fused paged-decode attention kernel: bit-exactness and contracts.

The oracles, in increasing integration order:

  * kernel-level: ``paged_attend_dense`` / ``paged_attend_mla`` (interpret
    mode) are bit-identical to gather-then-attend with the ``int_jax``
    integer softmax, across dense/GQA/MLA layouts, block sizes {8, 16, 64},
    sliding windows, int8-quantized pools, multi-token (verify) rows, f32
    compute, and a 4k-token context;
  * ``paged_gather``'s sentinel contract: entries outside [0, NB) yield
    all-zero blocks (the regression this PR fixes — clipped indices used to
    read a resident block silently);
  * the tile autotuner: picks a pages-per-step dividing the table length
    that fits the roofline VMEM model, and fails LOUDLY when nothing fits;
  * model-level: ``decode_step`` / ``verify_step`` on a paged cache under
    ``int_pallas_paged`` are bit-identical to ``int`` (gather reference),
    including cache leaves, for dense / GQA / MLA / int8-KV smokes.

Engine-level parity (serve tokens, speculative composition) lives in
``test_speculative.py``.
"""

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import smoke_config
from repro.core.int_softmax import int_softmax
from repro.core.precision import BEST
from repro.core.softmax_variants import SoftmaxSpec
from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.kernel import aligned_pages
from repro.launch.roofline import paged_tile_vmem_bytes
from repro.models import build_model, kv_cache
from repro.models.attention import paged_gather


# ------------------------------------------------------- reference (gather)


def _gather(pool, table):
    nb = pool.shape[0]
    b, nlog = table.shape
    pages = jnp.take(pool, jnp.clip(table, 0, nb - 1), axis=0)
    dead = ((table < 0) | (table >= nb)).reshape(
        b, nlog, *([1] * (pages.ndim - 2)))
    pages = jnp.where(dead, jnp.zeros((), pool.dtype), pages)
    return pages.reshape((b, nlog * pool.shape[1]) + pool.shape[2:])


def _ref_dense(q, k_pool, v_pool, table, positions, *, scale, window=0,
               k_scale=None, v_scale=None):
    k, v = _gather(k_pool, table), _gather(v_pool, table)
    if k_scale is not None:
        k = (k.astype(jnp.float32)
             * _gather(k_scale, table)[..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32)
             * _gather(v_scale, table)[..., None]).astype(q.dtype)
    b, t, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, t, kvh, h // kvh, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    l = k.shape[1]
    kv_pos = jnp.arange(l, dtype=jnp.int32)[None, None, :]
    valid = kv_pos <= positions[:, :, None]
    if window:
        valid &= kv_pos > positions[:, :, None] - window
    m = valid[:, None, None, :, :]
    w = int_softmax(scores, cfg=BEST, mask=m, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(b, t, h, v.shape[-1])


# jitted: the score sum's rounding must match the compiled model path
# (XLA's "semi" semantics — each dot rounded to bf16, the add in f32 —
# which the fused kernel reproduces; an eager add would round differently)
@jax.jit
def _ref_mla(q_lat, q_rope, c_pool, kr_pool, table, positions, scale):
    c_kv, k_rope = _gather(c_pool, table), _gather(kr_pool, table)
    scores = jnp.einsum("bqhr,blr->bhql", q_lat, c_kv)
    scores = scores + jnp.einsum("bqhd,bld->bhql", q_rope, k_rope)
    scores = scores.astype(jnp.float32) * scale
    l = c_kv.shape[1]
    kv = jnp.arange(l, dtype=jnp.int32)[None, None, :]
    valid = kv <= positions[:, :, None]
    mask = jnp.broadcast_to(valid[:, None, :, :], scores.shape)
    w = int_softmax(scores, cfg=BEST, mask=mask, axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhql,blr->bqhr", w, c_kv)


def _merged(pool):
    """[NB, BS, KV, D] -> the kernel's [NB, BS, KV * D] pool layout."""
    return pool.reshape(pool.shape[:2] + (-1,))


def _mixed_table(rng, B, NLOG, NB, BS, T):
    """Per-row tables with a random live prefix and NB sentinels after it;
    positions inside the live region."""
    table = np.full((B, NLOG), NB, np.int32)
    perm = rng.permutation(NB)
    pi = 0
    positions = np.zeros((B, T), np.int32)
    for b in range(B):
        npages = int(rng.integers(1, NLOG + 1))
        table[b, :npages] = perm[pi:pi + npages]
        pi += npages
        positions[b] = int(rng.integers(0, npages * BS)) + np.arange(T)
    return jnp.asarray(table), jnp.asarray(positions)


# --------------------------------------------------------- kernel-level


def _check_dense(bs, nlog, t, kvh, window, quant, *, H, D, seed):
    B = 3
    NB = B * nlog + 2
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(B, t, H, D)), jnp.bfloat16)
    if quant:
        k_pool = jnp.asarray(r.integers(-127, 128, (NB, bs, kvh, D)), jnp.int8)
        v_pool = jnp.asarray(r.integers(-127, 128, (NB, bs, kvh, D)), jnp.int8)
        k_scale = jnp.asarray(r.random((NB, bs, kvh)), jnp.float32) * .1
        v_scale = jnp.asarray(r.random((NB, bs, kvh)), jnp.float32) * .1
    else:
        k_pool = jnp.asarray(r.normal(size=(NB, bs, kvh, D)), jnp.bfloat16)
        v_pool = jnp.asarray(r.normal(size=(NB, bs, kvh, D)), jnp.bfloat16)
        k_scale = v_scale = None
    table, positions = _mixed_table(r, B, nlog, NB, bs, t)
    scale = D ** -0.5
    want = _ref_dense(q, k_pool, v_pool, table, positions, scale=scale,
                      window=window, k_scale=k_scale, v_scale=v_scale)
    got = ops.paged_attend_dense(q, _merged(k_pool), _merged(v_pool), table,
                                 positions, BEST,
                                 scale=scale, window=window, k_scale=k_scale,
                                 v_scale=v_scale, interpret=True)
    assert jnp.array_equal(want.astype(jnp.float32),
                           got.astype(jnp.float32))


@pytest.mark.parametrize("bs,nlog", [(8, 4), (16, 4), (64, 2)])
@pytest.mark.parametrize("t,kvh,window,quant", [
    (1, 2, 0, False),    # decode, MHA-ish
    (1, 1, 0, False),    # decode, GQA group=4
    (3, 2, 0, False),    # verify rows
    (1, 2, 12, False),   # sliding window
    (1, 2, 0, True),     # int8 pools, fused dequant
])
def test_dense_kernel_bitexact(bs, nlog, t, kvh, window, quant):
    _check_dense(bs, nlog, t, kvh, window, quant, H=4, D=32,
                 seed=hash((bs, nlog, t, kvh, window, quant)) % 2**31)


@pytest.mark.parametrize("hpp", [1, 2, 4])
@pytest.mark.parametrize("t,window,quant", [
    (1, 0, False),       # decode
    (3, 0, False),       # verify rows
    (1, 12, False),      # sliding window
    (1, 0, True),        # int8 pools, each head dequantized with its scale
], ids=["decode", "verify", "window", "int8"])
def test_dense_kernel_bitexact_head_blocks(monkeypatch, hpp, t, window,
                                           quant):
    """Programs over a block of ``hpp`` of the 4 kv heads (GQA group 2, 128
    lanes per head): every row of the shared [hpp * ROWS, L] score block
    still matches the reference bit for bit. A smaller ``hpp`` is forced
    through a VMEM budget that fits it at the smallest step and no more."""
    bs, nlog, kvh, d = 16, 4, 4, 128
    rows = t * 2
    base = aligned_pages(bs)
    if hpp < kvh:
        l_full = -(-nlog // base) * base * bs
        budget = paged_tile_vmem_bytes(rows, l_full, bs, d, d, base, 2,
                                       quant, hpp)
        monkeypatch.setattr(ops, "choose_tiles", functools.partial(
            ops.choose_tiles, vmem_budget=budget))
    assert ops.choose_tiles(rows, nlog, bs, d, d, 2, quant,
                            kv_heads=kvh)[1] == hpp
    _check_dense(bs, nlog, t, kvh, window, quant, H=8, D=d,
                 seed=hash((hpp, t, window, quant)) % 2**31)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_dense_kernel_bitexact_dtype(dtype):
    B, t, H, D, NB, bs, nlog = 2, 1, 4, 32, 8, 8, 3
    r = np.random.default_rng(7)
    q = jnp.asarray(r.normal(size=(B, t, H, D)), dtype)
    k_pool = jnp.asarray(r.normal(size=(NB, bs, 2, D)), dtype)
    v_pool = jnp.asarray(r.normal(size=(NB, bs, 2, D)), dtype)
    table, positions = _mixed_table(r, B, nlog, NB, bs, t)
    scale = D ** -0.5
    want = _ref_dense(q, k_pool, v_pool, table, positions, scale=scale)
    got = ops.paged_attend_dense(q, _merged(k_pool), _merged(v_pool), table,
                                 positions, BEST, scale=scale, interpret=True)
    if dtype == jnp.bfloat16:
        assert jnp.array_equal(want.astype(jnp.float32),
                               got.astype(jnp.float32))
    else:
        # float32 dots have no bf16 rounding step to hide the order of the
        # f32 accumulation, and XLA:CPU sums a [ROWS, L] x [L, D] dot in a
        # different order than the reference's batched einsum: the outputs
        # (|x| < 4) then differ by a few f32 ulps (2.4e-7 at |x| ~ 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-6)


def test_dense_kernel_bitexact_4k():
    """One long-context case: 4k logical tokens walked 8 pages per step."""
    B, t, H, kvh, D, bs = 2, 1, 4, 2, 32, 16
    nlog = 4096 // bs
    NB = nlog + 8
    r = np.random.default_rng(11)
    q = jnp.asarray(r.normal(size=(B, t, H, D)), jnp.bfloat16)
    k_pool = jnp.asarray(r.normal(size=(NB, bs, kvh, D)), jnp.bfloat16)
    v_pool = jnp.asarray(r.normal(size=(NB, bs, kvh, D)), jnp.bfloat16)
    table = np.full((B, nlog), NB, np.int32)
    table[0] = r.permutation(NB)[:nlog]
    table[1, :nlog // 2] = r.permutation(NB)[:nlog // 2]
    positions = jnp.asarray([[4095], [nlog // 2 * bs - 1]], jnp.int32)
    table = jnp.asarray(table)
    scale = D ** -0.5
    want = _ref_dense(q, k_pool, v_pool, table, positions, scale=scale)
    got = ops.paged_attend_dense(q, _merged(k_pool), _merged(v_pool), table,
                                 positions, BEST, scale=scale, interpret=True)
    assert jnp.array_equal(want.astype(jnp.float32),
                           got.astype(jnp.float32))


@pytest.mark.parametrize("bs,nlog,t", [(8, 4, 1), (16, 4, 3), (64, 2, 1)])
def test_mla_kernel_bitexact(bs, nlog, t):
    B, H, R, DR = 3, 4, 64, 16
    NB = B * nlog + 2
    r = np.random.default_rng(hash((bs, nlog, t)) % 2**31)
    q_lat = jnp.asarray(r.normal(size=(B, t, H, R)), jnp.bfloat16)
    q_rope = jnp.asarray(r.normal(size=(B, t, H, DR)), jnp.bfloat16)
    c_pool = jnp.asarray(r.normal(size=(NB, bs, R)), jnp.bfloat16)
    kr_pool = jnp.asarray(r.normal(size=(NB, bs, DR)), jnp.bfloat16)
    table, positions = _mixed_table(r, B, nlog, NB, bs, t)
    scale = (R // 2 + DR) ** -0.5
    want = _ref_mla(q_lat, q_rope, c_pool, kr_pool, table, positions, scale)
    got = ops.paged_attend_mla(q_lat, q_rope, c_pool, kr_pool, table,
                               positions, BEST, scale=scale, interpret=True)
    assert jnp.array_equal(want.astype(jnp.float32),
                           got.astype(jnp.float32))


# ------------------------------------------------- sentinel + autotune


def test_paged_gather_zeros_sentinels():
    """Entries outside [0, NB) gather ZERO blocks — not block 0 / NB-1."""
    pool = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4) + 1.0
    table = jnp.asarray([[0, 2, -1], [1, -7, 5]], jnp.int32)  # 2,5,-7: dead
    out = paged_gather(pool, table)
    out = out.reshape(2, 3, 3, 4)
    assert np.array_equal(out[0, 0], pool[0])
    assert np.array_equal(out[1, 0], pool[1])
    for b, n in [(0, 1), (0, 2), (1, 1), (1, 2)]:
        assert np.all(np.asarray(out[b, n]) == 0.0), (b, n)


def test_interpret_only_on_cpu(monkeypatch):
    """Kernels run in the Pallas interpreter on the CPU (how this suite
    runs), compile on a TPU, and refuse any other platform instead of
    silently ceasing to be a kernel there; an explicit flag always wins."""
    import repro.kernels as kernels

    assert kernels.resolve_interpret(None) is True     # suite runs on cpu
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "tpu")
    assert kernels.resolve_interpret(None) is False
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' is neither"):
        kernels.resolve_interpret(None)
    assert kernels.resolve_interpret(True) is True
    assert kernels.resolve_interpret(False) is False


def test_choose_tiles_divides_and_fits():
    """pps * block_size fills whole 128-lane tiles (the score slab's store
    offset must be lane-aligned on the chip) and divides the table; hpp
    divides the kv heads, and exceeds 1 only with 128-lane head slices."""
    for bs, nlog in [(16, 256), (8, 64), (64, 20), (256, 3)]:
        for d, kv in [(64, 1), (64, 4), (128, 4), (128, 16), (128, 6)]:
            pps, hpp = ops.choose_tiles(4, nlog, bs, d, d, 2, False,
                                        kv_heads=kv)
            base = aligned_pages(bs)
            assert pps * bs % 128 == 0 and pps % base == 0
            assert (-(-nlog // base) * base) % pps == 0
            assert kv % hpp == 0 and (hpp == 1 or d % 128 == 0)
            l_full = -(-nlog // base) * base * bs
            assert paged_tile_vmem_bytes(4, l_full, bs, d, d, pps, 2, False,
                                         hpp) <= ops.VMEM_LIMIT_BYTES
    # a table that is not a whole number of aligned steps is padded to the
    # next one, and the step then divides the padded length
    assert ops.choose_tiles(4, 12, 16, 64, 64, 2, False) == (16, 1)
    assert ops.choose_tiles(4, 34, 16, 64, 64, 2, False) == (8, 1)


@pytest.mark.parametrize("n_logical,pps", [(144, 16), (212, 8)],
                         ids=["chat", "doc-qa"])
def test_choose_tiles_all_heads_at_olmo_1b_cells(n_logical, pps):
    """olmo-1b's benchmark geometries (one decode row per head, 16 kv heads
    of 128): every head in one program, so each page is one whole-row
    fetch."""
    assert ops.choose_tiles(1, n_logical, 16, 128, 128, 2, False,
                            kv_heads=16) == (pps, 16)


@pytest.mark.parametrize("quant", [False, True])
def test_choose_tiles_fewer_heads_at_32k(quant):
    """A 32k olmo-1b table cannot hold all 16 heads' V rows in VMEM (128
    MiB): the choice falls back to a smaller divisor that fits."""
    pps, hpp = ops.choose_tiles(1, 2048, 16, 128, 128, 2, quant,
                                kv_heads=16)
    assert 1 < hpp < 16 and 16 % hpp == 0
    assert paged_tile_vmem_bytes(1, 2048 * 16, 16, 128, 128,
                                 aligned_pages(16), 2, quant,
                                 2 * hpp) > ops.VMEM_LIMIT_BYTES


def test_choose_tiles_mla_unchanged_at_minicpm3_4b():
    """The head-shared MLA kernel keeps its step: 40 rows over 276 pages of
    the 256-wide latent and 32 rope dims, 8 pages per step, one program."""
    assert ops.choose_tiles(40, 276, 16, 32, 256, 2, False) == (8, 1)


def test_choose_tiles_rejects_loudly():
    with pytest.raises(ValueError, match="rejected by roofline"):
        ops.choose_tiles(4, 4096, 64, 128, 128, 2, False, vmem_budget=1024)


# ----------------------------------------------------------- model-level


@pytest.mark.parametrize("arch,kv_quant", [
    ("olmo-1b", False), ("qwen2.5-32b", False), ("minicpm3-4b", False),
    ("olmo-1b", True),
])
def test_model_paged_decode_fused_bitexact(arch, kv_quant):
    """decode_step and verify_step under ``int_pallas_paged`` reproduce the
    gather reference (``int``) bit-for-bit — logits AND cache leaves."""
    bs, C, B, T, P = 8, 64, 3, 4, 9
    cfg_ref = smoke_config(arch, softmax=SoftmaxSpec("int"))
    cfg_fused = smoke_config(arch, softmax=SoftmaxSpec("int_pallas_paged"))
    if kv_quant:
        cfg_ref = dataclasses.replace(cfg_ref, kv_quant=True)
        cfg_fused = dataclasses.replace(cfg_fused, kv_quant=True)
    m_ref, m_fused = build_model(cfg_ref), build_model(cfg_fused)
    params, _ = m_ref.init_split(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg_ref.vocab, (B, P)))}
    logits, cache = m_ref.prefill(params, batch, C)
    pcache = kv_cache.paged_cache_zeros(cfg_ref, B, C, bs, B * (C // bs))
    from test_speculative import _paged_install
    cache = _paged_install(cfg_ref, cache, pcache, B, C, bs)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    pos = jnp.full((B,), P, jnp.int32)

    cr, cf = cache, cache
    for i in range(2):
        lr, cr = m_ref.decode_step(params, cr, {"token": tok}, pos + i)
        lf, cf = m_fused.decode_step(params, cf, {"token": tok}, pos + i)
        assert jnp.array_equal(lr, lf), (arch, i)
        for a, b in zip(jax.tree.leaves(cr), jax.tree.leaves(cf)):
            assert np.array_equal(a, b), (arch, i)
        tok = jnp.argmax(lr[:, -1], -1).astype(jnp.int32)[:, None]

    block = jnp.asarray(rng.integers(0, cfg_ref.vocab, (B, T)))
    vr, _ = m_ref.verify_step(params, cache, {"token": block}, pos)
    vf, _ = m_fused.verify_step(params, cache, {"token": block}, pos)
    assert jnp.array_equal(vr, vf), arch
