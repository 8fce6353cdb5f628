"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3).

Prefill/train: latents are up-projected to full per-head K/V and attention runs
like MHA (group=1), reusing the pluggable-softmax ``attend_chunked``.

Decode: the **absorbed** formulation — W_uk folds into the query and W_uv into
the output, so attention runs directly against the cached latent c_kv
[B, L, r] plus the shared rope key [B, L, dr]. The cache is r+dr per token
instead of 2*H*dh (the whole point of MLA), and the decode einsums contract
over the latent rank.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.backends import telemetry
from repro.core.softmax_variants import spec_backend
from repro.models.attention import (
    _collect_heads, attend_chunked, cache_write, cache_write_block,
    paged_gather, paged_write, paged_write_block, valid_upto, verify_mask,
)
from repro.models.layers import Ctx, apply_rope, dense_apply, dense_init, norm_init, norm_apply


def mla_init(key, cfg):
    d, h = cfg.d_model, cfg.n_heads
    r, dr, dn, dv = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {}
    if cfg.q_lora_rank:
        p["wdq"] = dense_init(ks[0], d, cfg.q_lora_rank, ("embed", "kv_lora"))
        p["q_norm"] = norm_init(cfg.q_lora_rank, "rmsnorm")
        p["wuq"] = dense_init(ks[1], cfg.q_lora_rank, h * (dn + dr), ("kv_lora", "heads"))
    else:
        p["wq"] = dense_init(ks[1], d, h * (dn + dr), ("embed", "heads"))
    p["wdkv"] = dense_init(ks[2], d, r, ("embed", "kv_lora"))
    p["kv_norm"] = norm_init(r, "rmsnorm")
    p["wkr"] = dense_init(ks[3], d, dr, ("embed", None))
    p["wuk"] = dense_init(ks[4], r, h * dn, ("kv_lora", "heads"))
    p["wuv"] = dense_init(ks[5], r, h * dv, ("kv_lora", "heads"))
    p["wo"] = dense_init(ks[6], h * dv, d, ("heads", "embed"))
    return p


def _queries(p, x, cfg, ctx, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        ql = norm_apply(p["q_norm"], dense_apply(p["wdq"], x, ctx), "rmsnorm", ctx)
        q = dense_apply(p["wuq"], ql, ctx)
    else:
        q = dense_apply(p["wq"], x, ctx)
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return ctx.shard(q_nope, ("batch", None, "heads", None)), \
        ctx.shard(q_rope, ("batch", None, "heads", None))


def _latents(p, x, cfg, ctx, positions):
    c_kv = norm_apply(p["kv_norm"], dense_apply(p["wdkv"], x, ctx), "rmsnorm", ctx)
    k_rope = dense_apply(p["wkr"], x, ctx)[:, :, None, :]      # [B,S,1,dr]
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return c_kv, k_rope


def mla_apply(p, x, cfg, ctx: Ctx, positions, kind: str = "causal"):
    """Train / prefill path: up-project latents, run full attention."""
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _queries(p, x, cfg, ctx, positions)
    c_kv, k_rope = _latents(p, x, cfg, ctx, positions)
    k_nope = dense_apply(p["wuk"], c_kv, ctx).reshape(b, s, h, dn)
    v = dense_apply(p["wuv"], c_kv, ctx).reshape(b, s, h, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = ctx.shard(k, ("batch", None, "heads", None))
    v = ctx.shard(v, ("batch", None, "heads", None))
    scale = (dn + dr) ** -0.5
    out = attend_chunked(q, k, v, positions, positions, kind, cfg, ctx, scale)
    return dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, s, -1),
                       ctx)


def mla_prefill_tail(p, x, prefix_c, prefix_kr, cfg, ctx: Ctx, positions,
                     prefix_len: int):
    """Prefill the unshared prompt tail against shared-prefix latents.

    ``prefix_c`` [B, s, r] / ``prefix_kr`` [B, s, dr] are the cached latent /
    rope-key values gathered from shared pool blocks — bit-identical to what
    a full prefill computes for those positions, so up-projecting
    [prefix ++ tail] latents reproduces the full-prefill K/V exactly.
    Returns (y, {"c_kv" [B,T,r], "k_rope" [B,T,dr]} tail cache)."""
    b, t, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _queries(p, x, cfg, ctx, positions)
    c_t, kr_t = _latents(p, x, cfg, ctx, positions)
    c_all = jnp.concatenate([ctx.cast(prefix_c), c_t], axis=1)
    kr_all = jnp.concatenate([ctx.cast(prefix_kr)[:, :, None, :], kr_t], axis=1)
    s_all = prefix_len + t
    k_nope = dense_apply(p["wuk"], c_all, ctx).reshape(b, s_all, h, dn)
    v = dense_apply(p["wuv"], c_all, ctx).reshape(b, s_all, h, dv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all, (b, s_all, h, dr))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    kv_pos = jnp.arange(s_all, dtype=jnp.int32)[None, :]
    out = attend_chunked(q, k, v, positions, kv_pos, "causal", cfg, ctx,
                         (dn + dr) ** -0.5)
    y = dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, t, -1), ctx)
    return y, {"c_kv": c_t, "k_rope": kr_t[:, :, 0]}


def mla_decode(p, x, cache, cache_pos, cfg, ctx: Ctx, positions):
    """Absorbed decode against the latent cache {"c_kv":[B,L,r], "k_rope":[B,L,dr]}
    — or, when a block table is present, the paged pool
    {"c_kv":[NB,BS,r], "k_rope":[NB,BS,dr], "table":[B,n_logical]}."""
    b, s, _ = x.shape  # s == 1
    q_nope, q_rope = _queries(p, x, cfg, ctx, positions)
    c_new, kr_new = _latents(p, x, cfg, ctx, positions)
    if "table" in cache:
        table = cache["table"]
        # latent pool partitions on r under the serving rules (each device
        # holds a slice of every page); rope keys + table stay replicated —
        # carry constraints keep the donated layout stable step to step
        c_pool = ctx.shard(
            paged_write(cache["c_kv"], table, c_new[:, 0], cache_pos),
            (None, None, "latent"))
        kr_pool = paged_write(cache["k_rope"], table, kr_new[:, 0, 0], cache_pos)
        new_cache = {"c_kv": c_pool, "k_rope": kr_pool, "table": table}
        backend = spec_backend(cfg.softmax)
        if getattr(backend, "fused_paged_decode", False):
            pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32),
                                   (b,))[:, None]
            return _mla_attend_paged_fused(p, q_nope, q_rope, new_cache,
                                           pos, cfg, ctx, backend, b,
                                           s), new_cache
        c_kv = ctx.shard(paged_gather(c_pool, table),
                         ("batch", None, "latent"))
        k_rope = paged_gather(kr_pool, table)
        mask = valid_upto(c_kv.shape[1], cache_pos)[:, None, :]
        return _mla_attend(p, q_nope, q_rope, c_kv, k_rope, mask, cfg,
                           ctx, b, s), new_cache
    c_kv = cache_write(cache["c_kv"], c_new, cache_pos)
    k_rope = cache_write(cache["k_rope"], kr_new[:, :, 0], cache_pos)
    # "latent" is None under default rules (split-KV layout unchanged) and the
    # model axis under serving rules (r-sharded carry for head-TP serving)
    c_kv = ctx.shard(c_kv, ("batch", "kv_seq", "latent"))
    k_rope = ctx.shard(k_rope, ("batch", "kv_seq", None))
    mask = valid_upto(c_kv.shape[1], cache_pos)[:, None, :]
    return _mla_attend(p, q_nope, q_rope, c_kv, k_rope, mask, cfg, ctx,
                       b, s), {"c_kv": c_kv, "k_rope": k_rope}


def mla_verify(p, x, cache, cache_pos, cfg, ctx: Ctx, positions):
    """Multi-token absorbed decode for speculative verification: write the T
    latents at positions ``cache_pos .. cache_pos + T-1`` (contiguous or
    through the block table) and attend all T queries with per-query causal
    masking — each query row reproduces the single-token decode step at its
    position. ``positions`` [B, T] absolute. Rejected tail entries are
    cleared by ``Model.verify_commit``."""
    b, t, _ = x.shape
    q_nope, q_rope = _queries(p, x, cfg, ctx, positions)
    c_new, kr_new = _latents(p, x, cfg, ctx, positions)
    if "table" in cache:
        table = cache["table"]
        c_pool = ctx.shard(
            paged_write_block(cache["c_kv"], table, c_new, cache_pos),
            (None, None, "latent"))
        kr_pool = paged_write_block(cache["k_rope"], table, kr_new[:, :, 0],
                                    cache_pos)
        new_cache = {"c_kv": c_pool, "k_rope": kr_pool, "table": table}
        backend = spec_backend(cfg.softmax)
        if getattr(backend, "fused_paged_decode", False):
            return _mla_attend_paged_fused(p, q_nope, q_rope, new_cache,
                                           positions, cfg, ctx, backend, b,
                                           t), new_cache
        c_kv = ctx.shard(paged_gather(c_pool, table),
                         ("batch", None, "latent"))
        k_rope = paged_gather(kr_pool, table)
    else:
        c_kv = cache_write_block(cache["c_kv"], c_new, cache_pos)
        k_rope = cache_write_block(cache["k_rope"], kr_new[:, :, 0], cache_pos)
        c_kv = ctx.shard(c_kv, ("batch", "kv_seq", "latent"))
        k_rope = ctx.shard(k_rope, ("batch", "kv_seq", None))
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}
    mask = verify_mask(c_kv.shape[1], positions)
    return _mla_attend(p, q_nope, q_rope, c_kv, k_rope, mask, cfg, ctx,
                       b, t), new_cache


def _absorb_queries(p, q_nope, cfg, ctx: Ctx):
    """Fold W_uk into the query: q_lat [B,Sq,H,r]. Shared by the reference
    (post-gather) and fused paged attends — same einsum, same rounding."""
    h, dn = cfg.n_heads, cfg.qk_nope_dim
    wuk = ctx.cast(p["wuk"]["w"]).reshape(cfg.kv_lora_rank, h, dn)
    return jnp.einsum("bqhd,rhd->bqhr", q_nope, wuk)


def _mla_output(p, o_lat, cfg, ctx: Ctx, b, s):
    """Up-project the latent attention output through W_uv and the output
    projection — shared tail of the reference and fused paths."""
    h, dv = cfg.n_heads, cfg.v_head_dim
    # serving rules: gather the latent rank (sharded via the c_kv pool) so
    # the wuv contraction over r is full-width per head, then gather heads
    # before wo — both no-ops under the default rules
    o_lat = ctx.shard(o_lat, ("batch", None, "heads", "tp_collect"))
    wuv = ctx.cast(p["wuv"]["w"]).reshape(cfg.kv_lora_rank, h, dv)
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat, wuv)
    return dense_apply(p["wo"], _collect_heads(out, ctx).reshape(b, s, -1),
                       ctx)


def _mla_attend_paged_fused(p, q_nope, q_rope, new_cache, positions, cfg,
                            ctx: Ctx, backend, b, s):
    """Absorbed attention straight against the paged latent pools via the
    block-table-walking Pallas kernel — no dense gather. Bit-exact vs
    gather + ``_mla_attend`` in interpret mode (the kernel reproduces the
    two-dot "semi" rounding of the score sum; see its module docstring)."""
    from repro.kernels.paged_attention import ops as paged_ops

    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    table = new_cache["table"]
    l_max = table.shape[1] * new_cache["c_kv"].shape[1]
    q_lat = _absorb_queries(p, q_nope, cfg, ctx)
    telemetry.record_softmax(backend, (b, h, s, l_max), heads=h)
    o_lat = paged_ops.paged_attend_mla(
        q_lat, q_rope, ctx.cast(new_cache["c_kv"]),
        ctx.cast(new_cache["k_rope"]), table, positions.astype(jnp.int32),
        backend.cfg, scale=(dn + dr) ** -0.5)
    return _mla_output(p, o_lat, cfg, ctx, b, s)


def _mla_attend(p, q_nope, q_rope, c_kv, k_rope, mask, cfg, ctx: Ctx,
                b, s):
    """Absorbed attention over a contiguous latent view [B, L, r] — shared by
    the contiguous and paged (post-gather) decode paths, so both lower the
    same einsums and stay bit-identical. ``mask`` [B?, Sq, L] (broadcast over
    heads)."""
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q_lat = _absorb_queries(p, q_nope, cfg, ctx)
    # serving rules: the latent POOL is rank-sharded (the capacity win), but
    # the attend view gathers the rank per device so the score contraction
    # over r is full-width — bitwise per head, and still head-parallel
    # (q_lat/scores shard on heads). Under the default rules this is the
    # split-KV layout the carry already has.
    c_kv = ctx.shard(ctx.cast(c_kv), ("batch", "kv_seq", "tp_collect"))
    scores = jnp.einsum("bqhr,blr->bhql", q_lat, c_kv)
    scores = scores + jnp.einsum("bqhd,bld->bhql", q_rope, ctx.cast(k_rope))
    scores = scores.astype(jnp.float32) * ((dn + dr) ** -0.5)
    scores = ctx.shard(scores, ("batch", "heads", None, "kv_seq"))
    mask = jnp.broadcast_to(mask[:, None, :, :], scores.shape)
    backend = spec_backend(cfg.softmax)
    telemetry.record_softmax(backend, scores.shape, heads=h)
    w = backend.apply(scores, mask=mask).astype(ctx.dtype)
    o_lat = jnp.einsum("bhql,blr->bqhr", w, c_kv)
    return _mla_output(p, o_lat, cfg, ctx, b, s)
