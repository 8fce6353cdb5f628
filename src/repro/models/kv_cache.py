"""Decode-cache structure per architecture family.

``cache_struct`` returns a ShapeDtypeStruct pytree (dry-run inputs, no
allocation); ``cache_axes`` returns the matching logical-axes pytree (sharding
derivation); ``cache_zeros`` materializes zeros (serving engine / tests).

Layouts:
  dense/moe/vlm : {"k","v": [L, B, C, KV, Dh]}            split-KV over "kv_seq"
  mla           : {"c_kv": [L,B,C,r], "k_rope": [L,B,C,dr]}  latent cache
  ssm           : {"state": [L,B,H,P,N], "conv": [L,B,k-1,Cd]}  O(1) in context
  hybrid        : full/win segments of {attn: ring-or-full, ssm: state}
  encdec        : {"self": ..., "cross": [L,B,S_enc,KV,Dh]}
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.hybrid import full_attn_layer_ids

KV_DTYPE = jnp.bfloat16


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _attn_cache(make, L, b, c, cfg):
    if getattr(cfg, "kv_quant", False) and cfg.family != "encdec":
        import jax.numpy as _jnp
        return {"k": make((L, b, c, cfg.n_kv_heads, cfg.d_head), _jnp.int8),
                "v": make((L, b, c, cfg.n_kv_heads, cfg.d_head), _jnp.int8),
                "k_scale": make((L, b, c, cfg.n_kv_heads), _jnp.float32),
                "v_scale": make((L, b, c, cfg.n_kv_heads), _jnp.float32)}
    return {"k": make((L, b, c, cfg.n_kv_heads, cfg.d_head), KV_DTYPE),
            "v": make((L, b, c, cfg.n_kv_heads, cfg.d_head), KV_DTYPE)}


def _ring_cache(make, L, b, w, cfg):
    d = _attn_cache(make, L, b, w, cfg)
    # absolute positions per batch row: rows decode at independent positions
    # under the continuous-batching scheduler, so each row's ring wraps on
    # its own clock
    d["pos"] = make((L, b, w), jnp.int32)
    return d


def _ssm_cache(make, L, b, cfg):
    conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {
        "state": make((L, b, cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state),
                      jnp.float32),
        "conv": make((L, b, cfg.ssm_conv - 1, conv_dim), KV_DTYPE),
    }


def hybrid_segments(cfg):
    """(n_full, len_win_a, len_win_b) for the unroll/scan/unroll/scan/unroll split."""
    first, mid, last = full_attn_layer_ids(cfg)
    return (mid - first - 1, last - mid - 1)


def _build(cfg, batch: int, cache_len: int, enc_len: int, make):
    L, b, c = cfg.n_layers, batch, cache_len
    if cfg.family == "ssm":
        return _ssm_cache(make, L, b, cfg)
    if cfg.family == "hybrid":
        wa, wb = hybrid_segments(cfg)
        w = min(cfg.window, c)
        seg = lambda n, full: {
            "attn": (_attn_cache(make, n, b, c, cfg) if full
                     else _ring_cache(make, n, b, w, cfg)),
            "ssm": _ssm_cache(make, n, b, cfg)}
        return {"full": seg(3, True), "win_a": seg(wa, False),
                "win_b": seg(wb, False)}
    if cfg.family == "encdec":
        return {"self": _attn_cache(make, L, b, c, cfg),
                "cross": _attn_cache(make, L, b, enc_len, cfg)}
    if cfg.attention == "mla":
        return {"c_kv": make((L, b, c, cfg.kv_lora_rank), KV_DTYPE),
                "k_rope": make((L, b, c, cfg.qk_rope_dim), KV_DTYPE)}
    return _attn_cache(make, L, b, c, cfg)


def cache_struct(cfg, batch: int, cache_len: int, enc_len: int = 0):
    return _build(cfg, batch, cache_len, enc_len, _sds)


def cache_zeros(cfg, batch: int, cache_len: int, enc_len: int = 0):
    def mk(shape, dtype):
        if dtype == jnp.int32:  # ring position buffers start at -1 (empty)
            return jnp.full(shape, -1, dtype)
        return jnp.zeros(shape, dtype)
    return _build(cfg, batch, cache_len, enc_len, mk)


# ------------------------------------------------------------- paged layouts
#
# The paged cache replaces each per-slot contiguous attention buffer
# [L, B, C, ...] with a global pool of fixed-size KV blocks
# [L, num_blocks, block_size, ...] plus a per-slot block table
# [L, B, C // block_size] mapping logical block index -> physical block id.
# The table rides INSIDE the cache pytree (one identical copy per stacked
# layer, int32 — a few KB) so the decode path keeps the exact
# ``decode_step(params, cache, batch, pos)`` signature and the scan-carry /
# donation contract of the contiguous path. ``num_blocks`` is the INVALID
# table sentinel: gathers clip it, scatters drop it (out-of-bounds-high).
#
# Position-free state (SSM conv/ssd state) and the window-bounded hybrid
# rings stay slot-resident: there is nothing to page (O(1) / O(window) per
# slot) and nothing shareable (the SSM state is a whole-prefix summary, not
# positional storage). The hybrid family pages its full-attention segments,
# where the O(context) memory actually lives.


def _paged_attn_cache(make, L, nb, bs, slots, n_logical, cfg):
    # K/V pools keep one position's heads side by side ([..., KV * D]), so
    # the fused paged-decode kernel fetches a block of hpp heads of a page
    # as one [BS, hpp * D] block — the page's whole contiguous row when
    # hpp = KV (see kernels/paged_attention/kernel.py)
    row = cfg.n_kv_heads * cfg.d_head
    if getattr(cfg, "kv_quant", False):
        d = {"k": make((L, nb, bs, row), jnp.int8),
             "v": make((L, nb, bs, row), jnp.int8),
             "k_scale": make((L, nb, bs, cfg.n_kv_heads), jnp.float32),
             "v_scale": make((L, nb, bs, cfg.n_kv_heads), jnp.float32)}
    else:
        d = {"k": make((L, nb, bs, row), KV_DTYPE),
             "v": make((L, nb, bs, row), KV_DTYPE)}
    d["table"] = make((L, slots, n_logical), jnp.int32, fill=nb)
    return d


def _build_paged(cfg, slots: int, cache_len: int, block_size: int,
                 num_blocks: int, make):
    if cache_len % block_size != 0:
        raise ValueError(f"cache_len {cache_len} not a multiple of "
                         f"block_size {block_size}")
    L, nb, bs = cfg.n_layers, num_blocks, block_size
    n_log = cache_len // block_size
    if cfg.family == "encdec":
        raise NotImplementedError("paged caches cover the decoder-only "
                                  "serving families")
    if cfg.family == "ssm":
        return _ssm_cache(make, L, slots, cfg)
    if cfg.family == "hybrid":
        wa, wb = hybrid_segments(cfg)
        w = min(cfg.window, cache_len)
        seg = lambda n, full: {
            "attn": (_paged_attn_cache(make, n, nb, bs, slots, n_log, cfg)
                     if full else _ring_cache(make, n, slots, w, cfg)),
            "ssm": _ssm_cache(make, n, slots, cfg)}
        return {"full": seg(3, True), "win_a": seg(wa, False),
                "win_b": seg(wb, False)}
    if cfg.attention == "mla":
        return {"c_kv": make((L, nb, bs, cfg.kv_lora_rank), KV_DTYPE),
                "k_rope": make((L, nb, bs, cfg.qk_rope_dim), KV_DTYPE),
                "table": make((L, slots, n_log), jnp.int32, fill=nb)}
    return _paged_attn_cache(make, L, nb, bs, slots, n_log, cfg)


def paged_cache_struct(cfg, slots: int, cache_len: int, block_size: int,
                       num_blocks: int):
    def mk(shape, dtype, fill=0):
        return _sds(shape, dtype)
    return _build_paged(cfg, slots, cache_len, block_size, num_blocks, mk)


def paged_cache_zeros(cfg, slots: int, cache_len: int, block_size: int,
                      num_blocks: int):
    def mk(shape, dtype, fill=0):
        if fill:
            return jnp.full(shape, fill, dtype)
        if dtype == jnp.int32:  # ring position buffers start at -1 (empty)
            return jnp.full(shape, -1, dtype)
        return jnp.zeros(shape, dtype)
    return _build_paged(cfg, slots, cache_len, block_size, num_blocks, mk)


def paged_scatter(cache, values, slot, table_row, pb, offs, t0: int, t1: int):
    """Install one request's prefilled cache entries into a paged cache.

    Pool leaves receive ``values`` positions ``[t0, t1)`` (seq axis 2 of the
    [L, 1, S, ...] prefill output) scattered to physical coordinates
    ``(pb[i], offs[i])``; the slot's block-table row is set to ``table_row``;
    slot-resident leaves (SSM state/conv, hybrid rings) are stripe-inserted
    at batch axis 1 — the paged counterpart of the engine's dense
    ``_insert_slot``. Pure traced function; the engine jits it with
    ``t0``/``t1`` static and the cache donated."""
    def walk(c, v):
        if isinstance(c, dict) and "table" in c:
            out = {}
            for k, leaf in c.items():
                if k == "table":
                    out[k] = leaf.at[:, slot, :].set(table_row)
                else:
                    vals = v[k][:, 0, t0:t1]
                    vals = vals.reshape(vals.shape[:2] + leaf.shape[3:])
                    out[k] = leaf.at[:, pb, offs].set(vals.astype(leaf.dtype))
            return out
        if isinstance(c, dict):
            return {k: walk(leaf, v[k]) for k, leaf in c.items()}
        return jax.lax.dynamic_update_slice_in_dim(
            c, v.astype(c.dtype), slot, axis=1)
    return walk(cache, values)


def paged_copy_block(cache, src, dst):
    """Copy pool block ``src`` -> ``dst`` on every pool leaf (the device half
    of the allocator's copy-on-write handshake). Tables and slot-resident
    leaves pass through."""
    def walk(c):
        if isinstance(c, dict) and "table" in c:
            return {k: (leaf if k == "table"
                        else leaf.at[:, dst].set(leaf[:, src]))
                    for k, leaf in c.items()}
        if isinstance(c, dict):
            return {k: walk(leaf) for k, leaf in c.items()}
        return c
    return walk(cache)


def paged_prefix_view(cache, ids, s: int):
    """Materialize the shared-prefix cache entries [L, 1, s, ...] from pool
    blocks ``ids`` (tail-only prefill input). Only defined for the families
    whose whole cache is one paged node (dense/moe/mla — the families that
    support prefix sharing)."""
    if not (isinstance(cache, dict) and "table" in cache):
        raise NotImplementedError("prefix gather requires a pure paged cache")
    out = {}
    for k, leaf in cache.items():
        if k == "table":
            continue
        pages = jnp.take(leaf, ids, axis=1)          # [L, n, bs, ...]
        flat = pages.reshape((leaf.shape[0], ids.shape[0] * leaf.shape[2])
                             + leaf.shape[3:])
        out[k] = flat[:, None, :s]
    return out


def slot_scatter(cache, values, slot, dst, t0: int, t1: int):
    """Commit one chunk of prefilled cache entries into a CONTIGUOUS
    slot-batched cache: positions ``[t0, t1)`` of the [L, 1, S, ...] chunk
    output land at stripe positions ``[dst, dst + t1 - t0)`` of ``slot`` —
    the contiguous counterpart of :func:`paged_scatter` for chunked prefill
    (a whole-prefill first chunk passes ``dst == t0 == 0``; a tail chunk's
    values are relative, so ``t0 == 0`` with ``dst`` at the committed
    boundary). Only the families whose every leaf is positional
    [L, B, C, ...] (dense/moe/mla — the chunkable families) use it; the
    engine jits it with ``t0``/``t1`` static and the cache donated."""
    def write(c, v):
        vals = v[:, :, t0:t1].astype(c.dtype)         # [L, 1, t1-t0, ...]
        start = (0, slot, dst) + (0,) * (c.ndim - 3)
        return jax.lax.dynamic_update_slice(c, vals, start)
    return jax.tree.map(write, cache, values)


def slot_prefix_view(cache, slot, s: int):
    """The first ``s`` committed positions of one slot's CONTIGUOUS cache as
    [L, 1, s, ...] — the prefix input for the next ``prefill_tail`` chunk
    (contiguous counterpart of :func:`paged_prefix_view`)."""
    def read(c):
        start = (0, slot, 0) + (0,) * (c.ndim - 3)
        size = (c.shape[0], 1, s) + c.shape[3:]
        return jax.lax.dynamic_slice(c, start, size)
    return jax.tree.map(read, cache)


def swap_read(cache, slot, ids):
    """Snapshot one slot's paged device state for preemption swap-out: the
    contents of pool blocks ``ids`` (the blocks NOT re-acquirable by content
    key, [L, n, bs, ...] per pool leaf) plus every slot-resident stripe
    (SSM state/conv, hybrid rings, [L, 1, ...]). Block tables are excluded —
    the table row is host-known bookkeeping, rebuilt on resume. The engine
    copies the result to host numpy; :func:`swap_write` restores it."""
    def walk(c):
        if isinstance(c, dict) and "table" in c:
            return {k: jnp.take(leaf, ids, axis=1)
                    for k, leaf in c.items() if k != "table"}
        if isinstance(c, dict):
            return {k: walk(leaf) for k, leaf in c.items()}
        return jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1)
    return walk(cache)


def swap_write(cache, payload, slot, ids, table_row):
    """Restore a :func:`swap_read` payload on resume: copied pool blocks land
    in the freshly allocated ``ids``, the slot's table row is rebuilt to
    ``table_row`` (sentinel-padded logical map over shared + restored
    blocks), and slot-resident stripes are re-inserted. Jitted by the engine
    with the cache donated."""
    def walk(c, v):
        if isinstance(c, dict) and "table" in c:
            out = {}
            for k, leaf in c.items():
                if k == "table":
                    out[k] = leaf.at[:, slot, :].set(table_row)
                else:
                    out[k] = leaf.at[:, ids].set(v[k].astype(leaf.dtype))
            return out
        if isinstance(c, dict):
            return {k: walk(leaf, v[k]) for k, leaf in c.items()}
        return jax.lax.dynamic_update_slice_in_dim(
            c, v.astype(c.dtype), slot, axis=1)
    return walk(cache, payload)


def commit_staged(staged, n_accept, cache_pos, t: int):
    """Resolve a staged speculative-verify cache at accepted depth
    ``n_accept`` [B] (see ``Model.verify_step`` for how the staged tree is
    built). This is where the per-family layout knowledge lives:

      * positional leaves — contiguous ``[L, B, C, ...]`` buffers or paged
        pools behind a block table — hold entries for ALL t verify tokens;
        the rejected tail (positions ``cache_pos + n_accept + 1 ..
        cache_pos + t - 1``) is CLEARED to zero, so no drafted K/V outlives
        its rejection and the committed cache is bit-identical to one built
        by stepping only the accepted tokens;
      * recurrent leaves — SSM state/conv and hybrid ring buffers, marked
        by their ``state``/``pos`` keys — arrive as per-step snapshots
        ``[L, T, B, ...]``; the snapshot after the last accepted token is
        selected per row (their updates are irreversible, so rollback is
        restore, not masking).

    Out-of-range positions (parked slots at ``cache_len``, over-draft tails
    at the end of a request's budget, sentinel table entries) drop — and a
    paged clear can only ever land in the slot's own private blocks, since
    decode positions sit strictly past any shared prefix."""
    b = n_accept.shape[0]
    pos0 = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
    steps = jnp.arange(t, dtype=jnp.int32)
    rej = steps[None, :] > n_accept[:, None]          # [B, T]
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    abs_pos = pos0[:, None] + steps[None, :]          # [B, T]
    lanes = jnp.arange(b, dtype=jnp.int32)

    def select(leaf):                                 # [L, T, B, ...]
        return leaf[:, n_accept, lanes]

    def clear_contig(leaf):                           # [L, B, C, ...]
        c = leaf.shape[2]
        cols = jnp.where(rej, abs_pos, c)             # accepted: park & drop
        return leaf.at[:, rows, cols].set(0, mode="drop")

    def clear_paged(node):                            # pools + block table
        table = node["table"]                         # [L, S, n_log]
        n_log = table.shape[2]
        pool = next(v for k, v in node.items() if k != "table")
        nb, bs = pool.shape[1], pool.shape[2]
        lb, off = abs_pos // bs, abs_pos % bs
        pb = jnp.take_along_axis(table[0], jnp.clip(lb, 0, n_log - 1),
                                 axis=1)
        pb = jnp.where(rej & (lb < n_log), pb, nb)
        return {k: (v if k == "table"
                    else v.at[:, pb, off].set(0, mode="drop"))
                for k, v in node.items()}

    def walk(node):
        if not isinstance(node, dict):
            raise TypeError(f"unexpected staged leaf {type(node)}")
        if "state" in node or "pos" in node:          # recurrent snapshots
            return {k: select(v) for k, v in node.items()}
        if "table" in node:
            return clear_paged(node)
        if all(not isinstance(v, dict) for v in node.values()):
            return {k: clear_contig(v) for k, v in node.items()}
        return {k: walk(v) for k, v in node.items()}

    return walk(staged)


def serve_cache_axes(cfg, slots: int, cache_len: int):
    """Logical axes tree matching ``cache_struct`` for the TENSOR-PARALLEL
    serve path: contiguous per-slot caches shard by kv-heads (dense/GQA) or
    the latent dim (MLA) under the serving rules, never by sequence — the
    donated carry keeps one stable layout across every compiled step. Ring /
    SSM leaves are replicated (sharded serving covers the attention-dominant
    families; see serving/sharded.py validation)."""
    def axes_for(shape, dtype):
        rank = len(shape)
        if rank == 5 and shape[3] == cfg.n_kv_heads:   # [L,S,C,KV,Dh]
            return ("stacked", "batch", None, "kv_heads", None)
        if rank == 4 and shape[-1] == cfg.n_kv_heads and \
                getattr(cfg, "kv_quant", False):       # scales [L,S,C,KV]
            return ("stacked", "batch", None, "kv_heads")
        if rank == 4 and cfg.attention == "mla" and \
                shape[-1] == cfg.kv_lora_rank:         # c_kv [L,S,C,r]
            return ("stacked", "batch", None, "latent")
        return ("stacked", "batch") + (None,) * (rank - 2)

    struct = cache_struct(cfg, slots, cache_len)
    return jax.tree.map(lambda s: axes_for(s.shape, s.dtype), struct)


def paged_cache_axes(cfg, slots: int, cache_len: int, block_size: int,
                     num_blocks: int):
    """Logical axes tree matching ``paged_cache_struct`` for tensor-parallel
    serving: pool leaves partition by kv-heads (dense/GQA) or the MLA latent
    dim — each device holds its heads' pages, 1/N of the pool bytes — while
    block tables (and the rope-key pool, whose dim is per-head-shared) stay
    replicated so the host-side allocator's decisions apply symmetrically on
    every shard."""
    def axes_for(shape, dtype):
        rank = len(shape)
        if rank == 4 and cfg.attention != "mla" and (
                shape[-1] == cfg.n_kv_heads * cfg.d_head  # pool [L,NB,BS,KV*Dh]
                or (shape[-1] == cfg.n_kv_heads and       # scales [L,NB,BS,KV]
                    getattr(cfg, "kv_quant", False))):
            return ("stacked", None, None, "kv_heads")
        if rank == 4 and cfg.attention == "mla" and \
                shape[-1] == cfg.kv_lora_rank:         # c_kv [L,NB,BS,r]
            return ("stacked", None, None, "latent")
        return ("stacked",) + (None,) * (rank - 1)     # tables, k_rope, rings

    struct = paged_cache_struct(cfg, slots, cache_len, block_size, num_blocks)
    return jax.tree.map(lambda s: axes_for(s.shape, s.dtype), struct)


def cache_axes(cfg, batch: int, cache_len: int, enc_len: int = 0):
    """Logical axes tree matching cache_struct (for dry-run in_shardings)."""
    def axes_for(shape, dtype):
        rank = len(shape)
        if rank == 5:   # [L, B, C, KV, Dh] attention cache -> split-KV
            return ("stacked", "batch", "kv_seq", None, None)
        if rank == 4 and shape[-1] == cfg.n_kv_heads and \
                getattr(cfg, "kv_quant", False):  # kv scales [L,B,C,KV]
            return ("stacked", "batch", "kv_seq", None)
        if rank == 4 and shape[-1] in (cfg.kv_lora_rank, cfg.qk_rope_dim) \
                and cfg.attention == "mla" and cfg.family != "hybrid":
            return ("stacked", "batch", "kv_seq", None)
        if rank == 4:   # conv cache [L,B,k-1,Cd]
            return ("stacked", "batch", None, "heads")
        # rank 3: ring pos [L, B, W] — falls through to the generic rule
        return ("stacked", "batch") + (None,) * (rank - 2)

    struct = cache_struct(cfg, batch, cache_len, enc_len)
    tree = jax.tree.map(lambda s: axes_for(s.shape, s.dtype), struct)
    if cfg.family in ("ssm", "hybrid"):
        # SSM state [L,B,H,P,N]: shard heads, not seq (there is no seq)
        def fix(path_axes):
            return path_axes
        def set_state(d):
            d["state"] = ("stacked", "batch", "heads", None, None)
        if cfg.family == "ssm":
            set_state(tree)
        else:
            for seg in ("full", "win_a", "win_b"):
                set_state(tree[seg]["ssm"])
    return tree
