"""GQA attention with a pluggable softmax — where SoftmAP enters the model.

Supports: grouped KV heads (GQA/MQA), RoPE / M-RoPE / none, causal or
sliding-window or full (encoder / cross) masking, query-chunked execution
(bounded score memory for 32k prefill), and split-KV decode against a cache.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.backends import telemetry
from repro.core.softmax_variants import spec_backend
from repro.models.layers import (
    Ctx, Param, apply_mrope, apply_rope, dense_apply, dense_init,
)


def attn_init(key, cfg, cross: bool = False):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * dh, ("embed", "heads"), bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d, kv * dh, ("embed", "kv_heads"), bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d, kv * dh, ("embed", "kv_heads"), bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], h * dh, d, ("heads", "embed")),
    }
    backend = spec_backend(cfg.softmax)
    if getattr(backend, "learnable", False):
        # learnable softmax params (ConSmax beta/gamma): one scalar per query
        # head, initialized from the backend cfg's operating point. Tiny and
        # replicated — every device applies the same elementwise map.
        c = backend.cfg
        p["smx"] = {
            "beta": Param(jnp.full((h,), c.beta, jnp.float32), (None,)),
            "gamma": Param(jnp.full((h,), c.gamma, jnp.float32), (None,)),
        }
    return p


def _rope(x, positions, cfg):
    if cfg.rope_type == "none" or positions is None:
        return x
    if cfg.rope_type == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


def project_qkv(p, x, cfg, ctx: Ctx, positions):
    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = dense_apply(p["wq"], x, ctx).reshape(b, s, h, dh)
    k = dense_apply(p["wk"], x, ctx).reshape(b, s, kv, dh)
    v = dense_apply(p["wv"], x, ctx).reshape(b, s, kv, dh)
    q = _rope(q, positions, cfg)
    k = _rope(k, positions, cfg)
    q = ctx.shard(q, ("batch", None, "heads", None))
    k = ctx.shard(k, ("batch", None, "kv_heads", None))
    v = ctx.shard(v, ("batch", None, "kv_heads", None))
    return q, k, v


def _mask(q_pos, kv_pos, kind: str, window: int):
    """[..., Sq, Skv] boolean mask. q_pos/kv_pos: int32 position vectors."""
    if kind == "none":
        return None
    rel = q_pos[..., :, None] - kv_pos[..., None, :]
    m = rel >= 0
    if kind == "window":
        m &= rel < window
    return m


def attend(q, k, v, mask, cfg, ctx: Ctx, scale: Optional[float] = None,
           smx=None):
    """q [B,Sq,H,D], k/v [B,Skv,KV,D] -> [B,Sq,H,D]. mask [B?,Sq,Skv] or None.
    ``smx``: learned softmax params ({"beta","gamma"} [H]) when the configured
    backend is learnable (ConSmax); None falls back to the backend cfg."""
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    group = h // kvh
    scale = scale if scale is not None else dh ** -0.5
    qg = q.reshape(b, sq, kvh, group, dh)
    # scores: [B, KV, G, Sq, Skv]
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(
        jnp.dtype(cfg.scores_dtype)) * scale
    scores = ctx.shard(scores, ("batch", "kv_heads", None, None, None))
    backend = spec_backend(cfg.softmax)
    # one AP per attention head (KV*G of them); shapes are static at trace
    # time, so metering rides along with jax.eval_shape cost passes for free
    telemetry.record_softmax(backend, scores.shape, heads=kvh * group)
    m = None if mask is None else mask[:, None, None, :, :]
    if smx is not None and getattr(backend, "learnable", False):
        # head h = kv_head * group + g — the same order qg unpacked above
        w = backend.apply(scores, mask=m, params={
            "beta": smx["beta"].reshape(kvh, group, 1, 1),
            "gamma": smx["gamma"].reshape(kvh, group, 1, 1),
        }).astype(ctx.dtype)
    else:
        w = backend.apply(scores, mask=m).astype(ctx.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(b, sq, h, v.shape[-1])  # v dim may differ (MLA)


def attend_chunked(q, k, v, q_pos, kv_pos, kind, cfg, ctx: Ctx,
                   scale: Optional[float] = None, smx=None):
    """Query-chunked attention: bounds live score memory to
    [B, H, chunk, Skv] (the 32k-prefill enabler). Exact (full rows per chunk)."""
    b, sq, h, dh = q.shape
    chunk = cfg.attn_chunk
    if chunk <= 0 or sq <= chunk or sq % chunk != 0:
        mask = _mask(q_pos, kv_pos, kind, cfg.window)
        return attend(q, k, v, mask, cfg, ctx, scale, smx=smx)
    n = sq // chunk
    qc = q.reshape(b, n, chunk, h, dh).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(q_pos.shape[0], n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        qi, pi = xs
        mask = _mask(pi, kv_pos, kind, cfg.window)
        return carry, attend(qi, k, v, mask, cfg, ctx, scale, smx=smx)

    with telemetry.repeat(n):  # scan body traces once, executes n times
        _, out = jax.lax.scan(body, None, (qc, pc))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, out.shape[-1])


def _collect_heads(out, ctx: Ctx):
    """Pin the attend output's head layout before the output projection.
    Under the default rules "tp_collect" IS the model axis — the layout the
    attend einsum already produced, so this is a no-op. The serving rules map
    it to None: heads all-gather before ``wo``, whose weight the serve path
    keeps replicated, so the contraction runs in full on every device —
    sharded greedy decode emits the exact single-device token stream instead
    of drifting on row-parallel psum rounding order."""
    return ctx.shard(out, ("batch", None, "tp_collect", None))


def attn_apply(p, x, cfg, ctx: Ctx, positions, kind: str = "causal"):
    """Training / prefill self-attention. kind: causal | window | none."""
    b, s, _ = x.shape
    q, k, v = project_qkv(p, x, cfg, ctx, positions)
    pos = positions[0] if cfg.rope_type == "mrope" else positions
    out = attend_chunked(q, k, v, pos, pos, kind, cfg, ctx, smx=p.get("smx"))
    out = _collect_heads(out, ctx)
    return dense_apply(p["wo"], out.reshape(b, s, -1), ctx)


def kv_quantize(x, scheme: str = "absmax"):
    """bf16 [B, S, KV, D] -> (int8 codes, per-(position, head) f32 scale).
    Symmetric absmax over the head dim — the integer theme of the paper
    carried into the serving cache (int8 KV halves decode HBM traffic, the
    dominant roofline term of every decode cell). ``scheme="exaq"`` rounds
    the scale up to a power of two (core/quantization.exaq_scale), so
    dequant is an exponent add on integer hardware. Either way the scale is
    a function of this position's amax alone (position-local): requantizing
    a position always reproduces its stored bytes, which is what lets
    chunked prefill and prefix sharing stay bit-identical on int8 pools.
    ``scheme="exaq_clamped"`` additionally clamps the power-of-two exponent
    to a signed 5-bit field (core/quantization.exaq_scale_clamped) — the
    scale word a real exponent-add datapath would carry; still position-local,
    so the same bit-identity contract holds."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    if scheme == "exaq":
        from repro.core.quantization import exaq_scale
        scale = exaq_scale(amax)
    elif scheme == "exaq_clamped":
        from repro.core.quantization import exaq_scale_clamped
        scale = exaq_scale_clamped(amax, 5)
    else:
        scale = jnp.maximum(amax / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return codes.astype(jnp.int8), scale[..., 0]


def kv_dequantize(codes, scale, dtype):
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


def kv_fake_quant(x, scheme: str = "absmax"):
    """Quantize-then-dequantize: returns (codes, scale, dequantized-as-x.dtype).

    The prefill path attends the DEQUANTIZED values while committing the
    codes+scales to the cache, so the int8 pool is the single source of
    truth — decode/verify gathers (which only ever see codes) reproduce the
    exact tensor prefill attended. This is the bit-identity contract that
    lets shared/chunked/swapped int8 blocks replay byte-for-byte."""
    codes, scale = kv_quantize(x, scheme)
    return codes, scale, kv_dequantize(codes, scale, x.dtype)


def cache_write(buf, new, cache_pos, axis: int = 1):
    """Write ``new`` (one entry per batch row) into ``buf`` at ``cache_pos``.

    ``cache_pos`` scalar: one ``dynamic_update_slice`` shared by the whole
    batch (the static-batch fast path, unchanged lowering). ``cache_pos``
    per-row ``[B]``: a one-hot where-write so every slot lands at its own
    position — the continuous-batching path (serving/scheduler.py). A row
    whose position is out of range (the scheduler parks free slots at
    ``cache_len``) writes nothing. Both paths store identical values, so
    downstream attention is bit-identical across them."""
    if jnp.ndim(cache_pos) == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), cache_pos, axis)
    assert axis == 1, "per-row writes index the [B, L, ...] layout"
    l_max = buf.shape[1]
    hit = jnp.arange(l_max, dtype=jnp.int32)[None, :] == cache_pos[:, None]
    hit = hit.reshape(hit.shape + (1,) * (buf.ndim - 2))
    return jnp.where(hit, new.astype(buf.dtype), buf)


def cache_write_block(buf, new, cache_pos):
    """Write a BLOCK of T entries per batch row into ``buf`` [B, L, ...] at
    positions ``cache_pos + i`` (i < T) — the multi-token counterpart of
    :func:`cache_write` used by the speculative verify step. ``new``
    [B, T, ...]; ``cache_pos`` scalar or per-row [B]. Positions past the
    buffer (parked slots at ``cache_len``, over-draft tails near the end of
    a request's budget) drop instead of writing."""
    b, t = new.shape[0], new.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    cols = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    return buf.at[rows, cols].set(new.astype(buf.dtype), mode="drop")


def paged_write_block(pool, table, new, cache_pos):
    """Multi-token :func:`paged_write`: scatter ``new`` [B, T, ...] through
    the block table at positions ``cache_pos + i``. Rows/positions beyond
    the table (sentinel entries, parked slots, over-draft tails) drop."""
    nb, bs = pool.shape[:2]
    b, n_log = table.shape
    t = new.shape[1]
    pos = (jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))[:, None]
           + jnp.arange(t, dtype=jnp.int32)[None, :])            # [B, T]
    lb, off = pos // bs, pos % bs
    pb = jnp.take_along_axis(table, jnp.clip(lb, 0, n_log - 1), axis=1)
    pb = jnp.where(lb >= n_log, nb, pb)
    new = new.reshape((b, t) + pool.shape[2:])
    return pool.at[pb, off].set(new.astype(pool.dtype), mode="drop")


def verify_mask(l_max: int, q_pos, window: int = 0):
    """[B, T, l_max] validity for a multi-token verify step: query ``i`` of
    row ``b`` sits at absolute position ``q_pos[b, i]`` and may attend every
    cache position ``<= q_pos[b, i]`` (within the trailing ``window`` when
    set) — exactly the masks T successive single-token decode steps would
    apply, so verify attention rows match the autoregressive ones."""
    kv = jnp.arange(l_max, dtype=jnp.int32)[None, None, :]
    q = q_pos[:, :, None]
    valid = kv <= q
    if window:
        valid &= kv > q - window
    return valid


def valid_upto(l_max: int, cache_pos, window: int = 0):
    """[B?, l_max] validity mask: positions <= cache_pos (and, when ``window``
    is set, within the trailing window). Supports scalar or per-row [B]
    ``cache_pos``; the scalar result broadcasts over the batch."""
    kv_pos = jnp.arange(l_max, dtype=jnp.int32)[None, :]
    pos = cache_pos if jnp.ndim(cache_pos) == 0 else cache_pos[:, None]
    valid = kv_pos <= pos
    if window:
        valid &= kv_pos > pos - window
    return valid


def paged_gather(pool, table):
    """Materialize a contiguous per-row view of a paged pool.

    ``pool`` [NB, BS, ...] (physical blocks), ``table`` [B, n_logical]
    (physical block id per logical block) -> [B, n_logical * BS, ...].

    Sentinel contract: any table entry outside ``[0, NB)`` (the allocator's
    ``NB`` marker, or anything stale/negative) yields an ALL-ZERO block —
    not whatever resident block a clipped index happens to hit. Downstream
    consumers mask by position validity anyway, but the explicit zeros make
    the gathered view bit-identical to what the fused paged kernel streams
    (it zeroes sentinel tiles the same way), including on parked rows whose
    validity mask covers the whole (empty) cache."""
    nb, bs = pool.shape[:2]
    pages = jnp.take(pool, jnp.clip(table, 0, nb - 1), axis=0)
    b, n = table.shape
    dead = (table < 0) | (table >= nb)
    pages = jnp.where(dead.reshape(b, n, *([1] * (pages.ndim - 2))),
                      jnp.zeros((), pool.dtype), pages)
    return pages.reshape((b, n * bs) + pool.shape[2:])


def paged_gather_heads(pool, table, kv_heads: int):
    """:func:`paged_gather` of a K/V head pool ``[NB, BS, KV * D]`` (the
    heads of one position side by side, the layout the fused kernel reads
    one head of as an aligned tile) as the contiguous ``[B, L, KV, D]``
    view the attention math takes."""
    g = paged_gather(pool, table)
    return g.reshape(g.shape[:2] + (kv_heads, -1))


def paged_write(pool, table, new, cache_pos):
    """Write one entry per batch row into the pool at ``cache_pos`` through
    the block table. ``new`` [B, ...]; ``cache_pos`` scalar or [B]. Rows
    whose position is out of range (parked slots at cache_len) or whose
    table entry is the NB sentinel scatter out of bounds and are dropped.
    Each entry is reshaped to the pool's row, so a K/V head pool
    [NB, BS, KV * D] takes ``new`` as [B, KV, D]."""
    nb, bs = pool.shape[:2]
    b, n_log = table.shape
    pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
    lb, off = pos // bs, pos % bs
    pb = jnp.take_along_axis(table, jnp.clip(lb, 0, n_log - 1)[:, None], 1)[:, 0]
    pb = jnp.where(lb >= n_log, nb, pb)
    new = new.reshape((b,) + pool.shape[2:])
    return pool.at[pb, off].set(new.astype(pool.dtype), mode="drop")


def _attend_paged_fused(p, q, new_cache, positions, cfg, ctx: Ctx, kind,
                        backend):
    """Attend straight against the paged pools via the block-table-walking
    Pallas kernel (``kernels/paged_attention``) — no dense gather. Bit-exact
    vs gather + ``backend.apply`` in interpret mode; see the kernel module
    docstring for the rounding contract (compiled on a TPU it agrees within
    bf16 rounding). ``positions`` [B, T] absolute query positions."""
    from repro.kernels.paged_attention import ops as paged_ops

    b, t, h, dh = q.shape
    table = new_cache["table"]
    kvh = cfg.n_kv_heads
    l_max = table.shape[1] * new_cache["k"].shape[1]
    # same score shape/heads the gather path records — metering is invariant
    # to the execution substrate
    telemetry.record_softmax(backend, (b, kvh, h // kvh, t, l_max),
                             heads=kvh * (h // kvh))
    quant = "k_scale" in new_cache
    out = paged_ops.paged_attend_dense(
        q,
        new_cache["k"] if quant else new_cache["k"].astype(q.dtype),
        new_cache["v"] if quant else new_cache["v"].astype(q.dtype),
        table, positions, backend.cfg,
        scale=dh ** -0.5,
        window=cfg.window if kind == "window" else 0,
        k_scale=new_cache.get("k_scale"), v_scale=new_cache.get("v_scale"),
        scores_dtype=jnp.dtype(cfg.scores_dtype))
    return dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, t, -1),
                       ctx)


def _shard_paged(new_cache, ctx: Ctx):
    """Pin the paged pool carry's sharding: pools partition by kv-heads
    (under the serving rules each device owns its heads' pages; under the
    default rules "kv_heads" dedups against the already-used model axis, so
    nothing changes for the dry-run path), the block table stays replicated.
    Constraining the CARRY — not just the attended view — keeps one stable
    NamedSharding across every donated decode/verify step (no relayout, no
    retrace)."""
    out = dict(new_cache)
    out["k"] = ctx.shard(out["k"], (None, None, "kv_heads"))
    out["v"] = ctx.shard(out["v"], (None, None, "kv_heads"))
    if "k_scale" in out:
        out["k_scale"] = ctx.shard(out["k_scale"], (None, None, "kv_heads"))
        out["v_scale"] = ctx.shard(out["v_scale"], (None, None, "kv_heads"))
    return out


def _attn_decode_paged(p, x, cache, cache_pos, cfg, ctx: Ctx, positions, kind):
    """Paged single-token decode: scatter the new K/V through the block
    table, then attend. The reference path gathers the whole logical cache
    back ([B, C, KV, D] holds exactly the values the contiguous path holds
    at every valid position, so scores — and outputs — are bit-identical);
    backends advertising ``fused_paged_decode`` skip the gather and walk the
    block table in a fused kernel instead, bit-identical to the reference."""
    b, s, _ = x.shape  # s == 1
    q, k_new, v_new = project_qkv(p, x, cfg, ctx, positions)
    table = cache["table"]
    if "k_scale" in cache:
        scheme = getattr(cfg, "kv_quant_scheme", "absmax")
        kq, ks = kv_quantize(k_new, scheme)
        vq, vs = kv_quantize(v_new, scheme)
        new_cache = {
            "k": paged_write(cache["k"], table, kq[:, 0], cache_pos),
            "v": paged_write(cache["v"], table, vq[:, 0], cache_pos),
            "k_scale": paged_write(cache["k_scale"], table, ks[:, 0],
                                   cache_pos),
            "v_scale": paged_write(cache["v_scale"], table, vs[:, 0],
                                   cache_pos),
            "table": table}
    else:
        new_cache = {
            "k": paged_write(cache["k"], table, k_new[:, 0], cache_pos),
            "v": paged_write(cache["v"], table, v_new[:, 0], cache_pos),
            "table": table}
    new_cache = _shard_paged(new_cache, ctx)
    backend = spec_backend(cfg.softmax)
    if getattr(backend, "fused_paged_decode", False):
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32),
                               (b,))[:, None]
        return _attend_paged_fused(p, q, new_cache, pos, cfg, ctx, kind,
                                   backend), new_cache
    kvh = cfg.n_kv_heads
    if "k_scale" in cache:
        k = kv_dequantize(paged_gather_heads(new_cache["k"], table, kvh),
                          paged_gather(new_cache["k_scale"], table),
                          ctx.dtype)
        v = kv_dequantize(paged_gather_heads(new_cache["v"], table, kvh),
                          paged_gather(new_cache["v_scale"], table),
                          ctx.dtype)
    else:
        k = paged_gather_heads(new_cache["k"], table, kvh)
        v = paged_gather_heads(new_cache["v"], table, kvh)
    k = ctx.shard(k, ("batch", None, "kv_heads", None))
    v = ctx.shard(v, ("batch", None, "kv_heads", None))
    l_max = k.shape[1]
    valid = valid_upto(l_max, cache_pos,
                       cfg.window if kind == "window" else 0)
    mask = jnp.broadcast_to(valid[:, None, :], (b, 1, l_max))
    out = attend(q, ctx.cast(k), ctx.cast(v), mask, cfg, ctx, smx=p.get("smx"))
    y = dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, s, -1), ctx)
    return y, new_cache


def attn_prefill_tail(p, x, prefix_k, prefix_v, cfg, ctx: Ctx, positions,
                      prefix_len: int, prefix_k_scale=None,
                      prefix_v_scale=None):
    """Prefill the unshared prompt tail against a shared-prefix cache.

    ``x`` embeds tokens[prefix_len:]; ``prefix_k``/``prefix_v`` [B, s, KV, D]
    (or [B, s, KV * D] straight from the pool) are the prefix K/V gathered
    from shared pool blocks (the exact bf16
    values a full prefill would have computed and cached for those
    positions, so the tail's attention rows — and its own K/V — match the
    full prefill bit for bit). Returns (y, {"k","v"} tail cache [B, T, ...]).

    Under ``cfg.kv_quant`` the prefix arrives as int8 codes plus per-position
    scales (``prefix_k_scale``/``prefix_v_scale`` [B, s, KV]); both the
    prefix and the tail attend through the same quantize->dequantize round
    trip a whole fake-quant prefill applies, and the returned tail cache
    carries codes+scales, so shared/chunked int8 execution stays
    bit-identical to the private whole-prefill path."""
    b, t, _ = x.shape
    q, k_t, v_t = project_qkv(p, x, cfg, ctx, positions)
    # a prefix read back from pool blocks has its heads side by side
    # ([B, s, KV * D]); a contiguous chunk prefix is already [B, s, KV, D]
    prefix_k = prefix_k.reshape(prefix_k.shape[:2] + (cfg.n_kv_heads, -1))
    prefix_v = prefix_v.reshape(prefix_v.shape[:2] + (cfg.n_kv_heads, -1))
    if getattr(cfg, "kv_quant", False):
        scheme = getattr(cfg, "kv_quant_scheme", "absmax")
        kq, ks, k_t = kv_fake_quant(k_t, scheme)
        vq, vs, v_t = kv_fake_quant(v_t, scheme)
        pk = kv_dequantize(prefix_k, prefix_k_scale, k_t.dtype)
        pv = kv_dequantize(prefix_v, prefix_v_scale, v_t.dtype)
        tail = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        pk, pv = ctx.cast(prefix_k), ctx.cast(prefix_v)
        tail = {"k": k_t, "v": v_t}
    k = jnp.concatenate([pk, k_t], axis=1)
    v = jnp.concatenate([pv, v_t], axis=1)
    pos = positions[0] if cfg.rope_type == "mrope" else positions
    kv_pos = jnp.arange(prefix_len + t, dtype=jnp.int32)[None, :]
    out = attend_chunked(q, k, v, pos, kv_pos, "causal", cfg, ctx,
                         smx=p.get("smx"))
    y = dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, t, -1), ctx)
    return y, tail


def attn_decode(p, x, cache, cache_pos, cfg, ctx: Ctx, positions,
                kind: str = "causal"):
    """Single-token decode. cache: {"k","v"} [B, L, KV, D] (kv_seq-sharded:
    split-KV / flash-decoding style), optionally int8-quantized with
    per-(position, head) scales ({"k_scale","v_scale"} present), or the
    paged layout ({"table" present}: pool [NB, BS, KV * D] + block table).
    cache_pos: int32 current length — scalar (uniform batch) or [B]
    (per-slot positions, continuous batching)."""
    if "table" in cache:
        return _attn_decode_paged(p, x, cache, cache_pos, cfg, ctx, positions,
                                  kind)
    b, s, _ = x.shape  # s == 1
    q, k_new, v_new = project_qkv(p, x, cfg, ctx, positions)
    quant = "k_scale" in cache
    if quant:
        scheme = getattr(cfg, "kv_quant_scheme", "absmax")
        kq, ks = kv_quantize(k_new, scheme)
        vq, vs = kv_quantize(v_new, scheme)
        k_codes = cache_write(cache["k"], kq, cache_pos)
        v_codes = cache_write(cache["v"], vq, cache_pos)
        k_sc = cache_write(cache["k_scale"], ks, cache_pos)
        v_sc = cache_write(cache["v_scale"], vs, cache_pos)
        k = ctx.shard(kv_dequantize(k_codes, k_sc, ctx.dtype),
                      ("batch", "kv_seq", "kv_heads", None))
        v = ctx.shard(kv_dequantize(v_codes, v_sc, ctx.dtype),
                      ("batch", "kv_seq", "kv_heads", None))
        new_cache = {"k": k_codes, "v": v_codes, "k_scale": k_sc, "v_scale": v_sc}
    else:
        # the constraint lands on the carry itself: default rules give the
        # split-KV layout (kv_heads dedups against the used model axis),
        # serving rules unmap kv_seq so the donated carry stays head-sharded
        # with ONE stable NamedSharding across every compiled step
        k = ctx.shard(cache_write(cache["k"], k_new, cache_pos),
                      ("batch", "kv_seq", "kv_heads", None))
        v = ctx.shard(cache_write(cache["v"], v_new, cache_pos),
                      ("batch", "kv_seq", "kv_heads", None))
        new_cache = {"k": k, "v": v}
    l_max = k.shape[1]
    valid = valid_upto(l_max, cache_pos,
                       cfg.window if kind == "window" else 0)
    mask = jnp.broadcast_to(valid[:, None, :], (b, 1, l_max))
    out = attend(q, ctx.cast(k), ctx.cast(v), mask, cfg, ctx, smx=p.get("smx"))
    y = dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, s, -1), ctx)
    return y, new_cache


def attn_verify(p, x, cache, cache_pos, cfg, ctx: Ctx, positions,
                kind: str = "causal"):
    """Multi-token decode for speculative verification: write K/V for all T
    tokens at positions ``cache_pos .. cache_pos + T-1`` and attend the T
    queries in one pass with per-query causal masking. Each query row sees
    exactly the keys the corresponding single-token decode step would see,
    so logits — and the written entries — match the autoregressive stream;
    rejected tail entries are cleared afterwards by ``Model.verify_commit``.
    ``positions`` [B, T] are the absolute positions (also the rope inputs).
    Covers the contiguous, int8-quantized, and paged cache layouts."""
    b, t, _ = x.shape
    q, k_new, v_new = project_qkv(p, x, cfg, ctx, positions)
    if "table" in cache:
        table = cache["table"]
        if "k_scale" in cache:
            scheme = getattr(cfg, "kv_quant_scheme", "absmax")
            kq, ks = kv_quantize(k_new, scheme)
            vq, vs = kv_quantize(v_new, scheme)
            kp = paged_write_block(cache["k"], table, kq, cache_pos)
            vp = paged_write_block(cache["v"], table, vq, cache_pos)
            ksp = paged_write_block(cache["k_scale"], table, ks, cache_pos)
            vsp = paged_write_block(cache["v_scale"], table, vs, cache_pos)
            new_cache = {"k": kp, "v": vp, "k_scale": ksp, "v_scale": vsp,
                         "table": table}
        else:
            kp = paged_write_block(cache["k"], table, k_new, cache_pos)
            vp = paged_write_block(cache["v"], table, v_new, cache_pos)
            new_cache = {"k": kp, "v": vp, "table": table}
        new_cache = _shard_paged(new_cache, ctx)
        kp, vp = new_cache["k"], new_cache["v"]
        if "k_scale" in cache:
            ksp, vsp = new_cache["k_scale"], new_cache["v_scale"]
        backend = spec_backend(cfg.softmax)
        if getattr(backend, "fused_paged_decode", False):
            # verify rows are just decode rows at T positions: the same
            # fused kernel covers the K+1 block with per-row masking
            return _attend_paged_fused(p, q, new_cache,
                                       positions.astype(jnp.int32), cfg,
                                       ctx, kind, backend), new_cache
        kvh = cfg.n_kv_heads
        if "k_scale" in cache:
            k = kv_dequantize(paged_gather_heads(kp, table, kvh),
                              paged_gather(ksp, table), ctx.dtype)
            v = kv_dequantize(paged_gather_heads(vp, table, kvh),
                              paged_gather(vsp, table), ctx.dtype)
        else:
            k = paged_gather_heads(kp, table, kvh)
            v = paged_gather_heads(vp, table, kvh)
        k = ctx.shard(k, ("batch", None, "kv_heads", None))
        v = ctx.shard(v, ("batch", None, "kv_heads", None))
    elif "k_scale" in cache:
        scheme = getattr(cfg, "kv_quant_scheme", "absmax")
        kq, ks = kv_quantize(k_new, scheme)
        vq, vs = kv_quantize(v_new, scheme)
        k_codes = cache_write_block(cache["k"], kq, cache_pos)
        v_codes = cache_write_block(cache["v"], vq, cache_pos)
        k_sc = cache_write_block(cache["k_scale"], ks, cache_pos)
        v_sc = cache_write_block(cache["v_scale"], vs, cache_pos)
        k = kv_dequantize(k_codes, k_sc, ctx.dtype)
        v = kv_dequantize(v_codes, v_sc, ctx.dtype)
        new_cache = {"k": k_codes, "v": v_codes, "k_scale": k_sc,
                     "v_scale": v_sc}
    else:
        k = cache_write_block(cache["k"], k_new, cache_pos)
        v = cache_write_block(cache["v"], v_new, cache_pos)
        k = ctx.shard(k, ("batch", "kv_seq", "kv_heads", None))
        v = ctx.shard(v, ("batch", "kv_seq", "kv_heads", None))
        new_cache = {"k": k, "v": v}
    l_max = k.shape[1]
    mask = verify_mask(l_max, positions,
                       cfg.window if kind == "window" else 0)
    out = attend(q, ctx.cast(k), ctx.cast(v), mask, cfg, ctx, smx=p.get("smx"))
    y = dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, t, -1), ctx)
    return y, new_cache


def attn_verify_ring(p, x, cache, cache_pos, cfg, ctx: Ctx, positions,
                     window: int):
    """Multi-token ring decode with per-step cache snapshots (speculative
    verify). A ring write at position q clobbers the entry from position
    q - W, which is still inside the window of earlier positions — so a
    rejected draft cannot be masked away like in the positional caches.
    Instead the T tokens run through the exact single-token ring update in
    an inner scan, emitting the cache after EVERY token; ``verify_commit``
    restores the snapshot at the accepted depth. Returns
    (y [B, T, d], staged {"k","v": [T, B, W, ...], "pos": [T, B, W]})."""
    b, t, _ = x.shape
    xs = jnp.moveaxis(x, 1, 0)[:, :, None, :]           # [T, B, 1, d]
    ps = jnp.moveaxis(positions, 1, 0)                  # [T, B]

    def step(c, xi_pi):
        xi, pi = xi_pi
        y, nc = attn_decode_ring(p, xi, c, pi, cfg, ctx, pi[:, None], window)
        return nc, (y, nc)

    with telemetry.repeat(t):    # body traces once, runs t times
        _, (ys, snaps) = jax.lax.scan(step, cache, (xs, ps))
    y = jnp.moveaxis(ys[:, :, 0, :], 0, 1)              # [B, T, d]
    return y, snaps


def attn_decode_ring(p, x, cache, cache_pos, cfg, ctx: Ctx, positions,
                     window: int):
    """Ring-buffer decode for sliding-window layers (and full layers when the
    ring capacity >= max_seq): cache {"k","v":[B,W,KV,D], "pos":[B,W]}; the
    write slot is cache_pos % W and validity is derived from stored absolute
    positions (per batch row — rows at different positions, as under the
    continuous-batching scheduler, wrap independently). RoPE is applied at
    write time (absolute), so relative geometry is preserved across wraps."""
    b, s, _ = x.shape  # s == 1
    q, k_new, v_new = project_qkv(p, x, cfg, ctx, positions)
    w_cap = cache["k"].shape[1]
    slot = jax.lax.rem(cache_pos, w_cap)
    if jnp.ndim(cache_pos) == 0:
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
        pos_buf = jax.lax.dynamic_update_slice(
            cache["pos"],
            jnp.full((b, 1), cache_pos, cache["pos"].dtype), (0, slot))
        pos_col = cache_pos
    else:
        k = cache_write(cache["k"], k_new, slot)
        v = cache_write(cache["v"], v_new, slot)
        hit = jnp.arange(w_cap, dtype=jnp.int32)[None, :] == slot[:, None]
        pos_buf = jnp.where(hit, cache_pos[:, None].astype(cache["pos"].dtype),
                            cache["pos"])
        pos_col = cache_pos[:, None]
    valid = (pos_buf >= 0) & (pos_buf <= pos_col) & (pos_buf > pos_col - window)
    mask = jnp.broadcast_to(valid[:, None, :], (b, 1, w_cap))
    out = attend(q, ctx.cast(k), ctx.cast(v), mask, cfg, ctx, smx=p.get("smx"))
    y = dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, s, -1), ctx)
    return y, {"k": k, "v": v, "pos": pos_buf}


def attn_cross(p, x, enc_k, enc_v, cfg, ctx: Ctx):
    """Cross-attention (Whisper decoder): K/V precomputed from encoder."""
    b, s, _ = x.shape
    h, dh = cfg.n_heads, cfg.d_head
    q = dense_apply(p["wq"], x, ctx).reshape(b, s, h, dh)
    q = ctx.shard(q, ("batch", None, "heads", None))
    out = attend(q, enc_k, enc_v, None, cfg, ctx, smx=p.get("smx"))
    return dense_apply(p["wo"], out.reshape(b, s, -1), ctx)


def cross_kv(p, enc_out, cfg, ctx: Ctx):
    b, s, _ = enc_out.shape
    kv, dh = cfg.n_kv_heads, cfg.d_head
    k = dense_apply(p["wk"], enc_out, ctx).reshape(b, s, kv, dh)
    v = dense_apply(p["wv"], enc_out, ctx).reshape(b, s, kv, dh)
    return k, v
