"""The serve path's Pallas kernels compile for a TPU v5e at real widths.

Compile-only: the chip's compiler (installed with libtpu) compiles for a
DESCRIBED ``v5e:2x2`` topology — nothing runs, and no chip is attached.
Interpret-mode tests cannot see what this refuses: slices not aligned to the
(8, 128) tiling, and kernels that need more VMEM than their scoped limit.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file. Where it cannot be described the fixture skips.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.precision import PrecisionConfig
from repro.kernels.int_softmax.ops import int_softmax_pallas
from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.paged_attention import ops

CFG = PrecisionConfig(M=6, N=16)        # the paper's Alg.-1 operating point
BS = 16                                 # the serving default block size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _dense_shapes(slots, t, heads, kv, d, ctx, pool_dtype=jnp.bfloat16):
    nlog = ctx // BS
    return [((slots, t, heads, d), jnp.bfloat16),
            ((slots * nlog, BS, kv * d), pool_dtype),
            ((slots * nlog, BS, kv * d), pool_dtype),
            ((slots, nlog), jnp.int32), ((slots, t), jnp.int32)]


def _dense(q, k, v, table, pos, *scales):
    return ops.paged_attend_dense(
        q, k, v, table, pos, CFG, scale=128 ** -0.5,
        k_scale=scales[0] if scales else None,
        v_scale=scales[1] if scales else None, interpret=False)


@pytest.mark.parametrize("ctx,t,quant", [
    (544, 1, False),        # the chip smoke's cache: 512-token prompt + 32
    (544, 5, False),        # speculative verify rows (draft_k 4)
    (32768, 1, False),      # olmo-1b max_seq
    (32768, 1, True),       # int8 KV pool with per-position scales
], ids=["short", "verify5", "32k", "32k-int8"])
def test_paged_dense_compiles_olmo_1b(one_chip, ctx, t, quant):
    """olmo-1b widths: 16 heads = 16 KV heads of 128, 4 slots."""
    shapes = _dense_shapes(4, t, 16, 16, 128, ctx,
                           jnp.int8 if quant else jnp.bfloat16)
    if quant:
        nb = shapes[1][0][0]
        shapes += [((nb, BS, 16), jnp.float32)] * 2
    compiled = _compile(_dense, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_mla_compiles_minicpm3_4b(one_chip):
    """minicpm3-4b widths: 40 heads over a 256-wide latent + 32 rope dims."""
    slots, heads, r, dr, ctx = 4, 40, 256, 32, 4096
    nlog = ctx // BS

    def mla(ql, qr, c, kr, table, pos):
        return ops.paged_attend_mla(ql, qr, c, kr, table, pos, CFG,
                                    scale=0.1, interpret=False)

    compiled = _compile(mla, [
        ((slots, 1, heads, r), jnp.bfloat16),
        ((slots, 1, heads, dr), jnp.bfloat16),
        ((slots * nlog, BS, r), jnp.bfloat16),
        ((slots * nlog, BS, dr), jnp.bfloat16),
        ((slots, nlog), jnp.int32), ((slots, 1), jnp.int32)], one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("masked", [False, True])
def test_int_softmax_kernel_compiles_at_attn_chunk(one_chip, masked):
    """The standalone Alg.-1 kernel over rows of attn_chunk (2048) scores."""
    shape = (64, 2048)
    if masked:
        def fn(x, m):
            return int_softmax_pallas(x, cfg=CFG, mask=m, interpret=False)
        shapes = [(shape, jnp.float32), (shape, jnp.bool_)]
    else:
        def fn(x):
            return int_softmax_pallas(x, cfg=CFG, interpret=False)
        shapes = [(shape, jnp.float32)]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def _check_vmem_boundary(sharding, hpp):
    """The longest table for which ``choose_tiles`` still gives a program
    ``hpp`` kv heads within a 16 MiB budget compiles with that budget as
    the kernel's VMEM limit; one 30% longer is refused by both."""
    rows, d, budget = 64 // hpp, 128, 16 * 2 ** 20
    base = paged_kernel.aligned_pages(BS)

    def accepts(nlog):
        try:
            return ops.choose_tiles(rows, nlog, BS, d, d, 2, False, budget,
                                    hpp)[1] == hpp
        except ValueError:
            return False

    lo, hi = base, base * 1024
    assert accepts(lo) and not accepts(hi)
    while hi - lo > base:
        mid = (lo + hi) // 2 // base * base
        lo, hi = (mid, hi) if accepts(mid) else (lo, mid)

    def compile_at(nlog):
        pps = base   # the smallest aligned step: what choose_tiles tries last

        def fn(q, k, v, table, pos):
            return paged_kernel.paged_attention_dense(
                q, k, v, table, pos, CFG, scale=0.1, pps=pps, hpp=hpp,
                vmem_limit=budget, interpret=False)

        return _compile(fn, [((1, hpp, rows, d), jnp.bfloat16),
                             ((nlog, BS, hpp * d), jnp.bfloat16),
                             ((nlog, BS, hpp * d), jnp.bfloat16),
                             ((1, nlog), jnp.int32), ((1, rows), jnp.int32)],
                        sharding)

    assert "tpu_custom_call" in compile_at(lo).as_text()
    over = -(-int(lo * 1.3) // base) * base
    assert not accepts(over)
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        compile_at(over)


def test_choose_tiles_matches_compiler_vmem_limit(one_chip):
    """``choose_tiles`` and the compiler agree at the VMEM boundary, one kv
    head per program (64 rows). (The roofline model is an upper bound
    within 25% of what Mosaic allocates, so an accepted tile always
    compiles.) The budget is Mosaic's default 16 MiB scope: at serving's
    ``VMEM_LIMIT_BYTES`` the same check holds but compiles for over two
    minutes (Mosaic unrolls the full-row softmax, so compile time grows
    with the VMEM the rows fill)."""
    _check_vmem_boundary(one_chip, 1)


def test_choose_tiles_matches_compiler_vmem_limit_head_block(one_chip):
    """The same boundary for a program over a block of 4 kv heads (16 rows
    each, the same 64 score rows): each page is one [BS, 4 * 128] fetch and
    V is staged [L, 4 * 128]."""
    _check_vmem_boundary(one_chip, 4)
