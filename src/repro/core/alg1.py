"""SoftmAP Algorithm 1 — the ONE jnp-pure implementation of the integer body.

Pipeline (all integer once codes are formed; line numbers follow the paper):

  l.4   v_stable = v - max(v)                       (integer max-subtract)
  l.5   v_ln2    = floor(ln2 / S)                   (offline)
  l.6   mu       = floor(2^(2M) / v_ln2)            (offline, Barrett constant)
  l.7   q        = floor((-v_stable) * mu / 2^(2M)) (Barrett quotient, +1 correction)
        v_corr   = v_stable + q * v_ln2             in (-v_ln2, 0]
  l.8-10 a,b,c coefficients -> v_b = floor(b/S), v_c = floor(c/(a S^2))  (offline)
  l.11  v_approx = ((v_corr + v_b)^2 + v_c) >> q
  l.12  v_sm     = v_approx / sum(v_approx)         (fixed-point division, P_out frac bits)
  l.13  S_sm     = scale bookkeeping (the emitted codes carry scale 2^-P_out)

Every execution substrate imports this module rather than re-implementing the
body: ``core.int_softmax`` (reference + STE), both Pallas kernels
(``kernels/int_softmax``, ``kernels/int_attention`` — the functions here are
pure jnp, so they trace inside ``pl.pallas_call`` unchanged), and the backend
registry (``repro.backends``). The numpy AP dataflow
(``ap/dataflow.ap_softmax_vector``) is the hardware half of the co-design and
is asserted bit-identical to this body by tests.

Design notes (see DESIGN.md §3):

* The N-bit-truncated sum is realized as a **pairwise saturating reduction** —
  exactly what the 2D AP's log2(L/2)-stage row reduction does in hardware, and
  provably equal to ``min(true_sum, saturation)`` for non-negative addends.
* Masked positions contribute 0 to the sum (the AP's mask register); without
  this, clipping at T_C would leak ~e^T_C of probability mass per masked slot.
* All intermediates respect the Table-I column widths via saturation; for every
  paper configuration the saturations are provably inactive except the sum's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionConfig
from repro.core.quantization import dequantize_probs, quantize_stable_scores


def _sat(x, width: int):
    """Saturate non-negative int32 values to ``width`` bits."""
    return jnp.minimum(x, jnp.int32(min(2**width - 1, 2**31 - 1)))


def saturating_sum(x, saturation: int, axis: int = -1):
    """Pairwise saturating reduction of non-negative int32 values.

    Equals ``min(sum(x), saturation)`` exactly (proof: by induction each subtree
    yields min(subtree_sum, sat); a clipped parent of exact children is exact
    below sat and pinned at sat above it — for ANY binary tree). Mirrors the
    2D AP's log2-stage row-pair reduction, with the accumulator saturating at
    the Table-I width. Each level adds the two contiguous halves: a strided
    ``x[0::2] + x[1::2]`` pairing gives the same codes but does not lower in a
    TPU kernel (Mosaic has no lane-strided slice).
    ``saturation`` must be <= 2^30 - 1 so a pairwise add cannot overflow int32.
    """
    if saturation > 2**30 - 1:
        raise ValueError("saturation must be <= 2^30 - 1 to stay in int32")
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    # pad to a power of two with zeros (identity of +)
    size = 1 if n == 0 else 2 ** ((n - 1).bit_length())
    if size != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, size - n)]
        x = jnp.pad(x, pad)
    sat = jnp.int32(saturation)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = jnp.minimum(x[..., :h] + x[..., h:], sat)
    # final clip covers the single-element case (contract: min(sum, sat))
    return jnp.minimum(x[..., 0], sat)


def fixedpoint_div(num, den, frac_bits: int):
    """floor(num * 2^frac_bits / den) for int32 ``0 <= num < den <= 2^30``,
    without overflowing int32: restoring long division, one quotient bit per
    step — the same bit-serial division the AP's R column performs. ``den``
    broadcasts against ``num``."""
    num = num.astype(jnp.int32)
    den = jnp.broadcast_to(den.astype(jnp.int32), num.shape)

    def step(_, carry):
        rem, quo = carry
        rem = rem << 1
        ge = rem >= den
        rem = jnp.where(ge, rem - den, rem)
        quo = (quo << 1) | ge.astype(jnp.int32)
        return rem, quo

    _, quo = jax.lax.fori_loop(
        0, frac_bits, step, (num, jnp.zeros_like(num)))
    return quo


def int_exp_codes(v_stable, cfg: PrecisionConfig):
    """Integer exponential: codes v_stable (<=0, scale S) -> v_approx (scale aS^2).

    Implements Alg. 1 lines 5-11 with a single Barrett correction step so the
    remainder lands exactly in (-v_ln2, 0] (the polynomial's domain).
    """
    v_stable = v_stable.astype(jnp.int32)
    neg = -v_stable  # in [0, 2^(M-1)]
    # Barrett quotient: q_hat = floor(neg * mu / 2^(2M)), q_hat in {q, q-1}.
    q = (neg * jnp.int32(cfg.mu)) >> (2 * cfg.M)
    r = v_stable + q * jnp.int32(cfg.v_ln2)
    # correction: pull r into (-v_ln2, 0]
    need = r <= -jnp.int32(cfg.v_ln2)
    q = jnp.where(need, q + 1, q)
    r = jnp.where(need, r + jnp.int32(cfg.v_ln2), r)
    # v_corr column width clamp (Table I; inactive for all paper configs)
    r = jnp.maximum(r, -jnp.int32(2 ** (cfg.w_vcorr - 1)))
    poly = (r + jnp.int32(cfg.v_b)) ** 2 + jnp.int32(cfg.v_c)
    poly = _sat(poly, cfg.w_poly)
    # Fixed-point exponential: poly << (F - q)  (right shift once q > F).
    # F = cfg.exp_shift positions the q=0 code at the top of the Table-I
    # v_approx width, exactly I-BERT's poly * 2^(n-q) scheme.
    sh = jnp.int32(cfg.exp_shift) - jnp.minimum(q, 31 + jnp.int32(cfg.exp_shift))
    v_approx = jnp.where(
        sh >= 0, poly << jnp.maximum(sh, 0), poly >> jnp.minimum(-sh, 31)
    )
    return _sat(v_approx, cfg.w_vapprox)


def int_softmax_from_codes(v, cfg: PrecisionConfig, mask=None, axis: int = -1,
                           assume_stable: bool = False, div: str = "auto"):
    """Alg. 1 on integer codes ``v`` (scale S). Returns fixed-point probability
    codes with ``cfg.P_out`` fractional bits (scale 2^-P_out).

    ``assume_stable``: True when codes are already max-subtracted (<= 0), as
    produced by ``quantize_stable_scores``; the integer max-subtract (l.4) then
    reduces to the identity but is still applied, matching the AP dataflow.

    ``div``: "auto" uses the single-op ``<< P_out // total`` fast path when the
    quotient provably fits int32; "bitserial" always runs the restoring long
    division. Both are exact floor division, so the codes are bit-identical —
    the Pallas kernels pass "bitserial" to keep their trace on shift/compare/
    subtract ops only (Mosaic-safe; vector int32 floor-division lowering is
    not exercised on TPU).
    """
    v = v.astype(jnp.int32)
    if mask is not None:
        floor_code = jnp.int32(-(2 ** (cfg.M - 1)))
        v = jnp.where(mask, v, floor_code)
    # l.4 integer max-subtract (numerical stability)
    v_max = jnp.max(v, axis=axis, keepdims=True)
    v_stable = v - v_max
    if not assume_stable:
        v_stable = jnp.clip(v_stable, -(2 ** (cfg.M - 1)), 0)
    v_approx = int_exp_codes(v_stable, cfg)
    if mask is not None:
        v_approx = jnp.where(mask, v_approx, 0)
    total = saturating_sum(v_approx, cfg.sum_saturation, axis=axis)
    total = jnp.maximum(total, 1)
    total = jnp.expand_dims(total, axis if axis >= 0 else v.ndim + axis)
    # l.12 fixed-point division into the R column (P_out = 2M+12 fractional
    # bits). v_approx <= total always, so the quotient fits P_out bits (a lone
    # max element yields the all-ones code ~= 1.0).
    if div == "auto" and cfg.w_vapprox + cfg.P_out <= 31:
        return (v_approx << cfg.P_out) // total  # fast path, exact
    return fixedpoint_div(v_approx, total, cfg.P_out)


def int_softmax_block(x, mask, cfg: PrecisionConfig):
    """Float scores -> float32 probabilities over the LAST axis.

    The block-level entry point shared by the standalone Pallas softmax kernel
    and the fused attention kernel (everything here is pure jnp, so it traces
    inside ``pl.pallas_call``). Bit-identical to ``core.int_softmax.int_softmax``
    at ``axis=-1`` by construction: same quantizer, same code body, same
    dequantization.
    """
    v = quantize_stable_scores(x, cfg, mask=mask, axis=-1)
    codes = int_softmax_from_codes(v, cfg, mask=mask, axis=-1,
                                   assume_stable=True, div="bitserial")
    return dequantize_probs(codes, cfg)
