"""Plain reference forward pass of a decoder-only transformer.

Written from the architecture descriptions, in float32, with every matrix
product going through ``mm`` (the caller fixes its precision), and the
attention softmax the configuration states: SoftmAP's integer-only softmax
(Alg. 1 of arXiv 2411.17847) at the configuration's precision point. No
cache, no batching, no kernels, and nothing taken from the program under
test but the weight arrays the harness made: the tree is read by key names
only.

Covers the dense GQA block (OLMo: non-parametric LayerNorm, tied embeddings;
Llama-style: RMSNorm) and multi-head latent attention (DeepSeek-V2 /
MiniCPM3: low-rank query and key-value compressions with RMSNorm, a
decoupled rotary key shared by all heads), each followed by a SwiGLU MLP.
Rotary embeddings rotate the two halves of each head (GPT-NeoX layout).

MiniCPM3 is covered as the program builds it, not as published in full:
its muP scalars (``scale_emb``, ``scale_depth``, ``dim_model_base``) and its
LongRoPE scaling are absent from the program, and so from this reference;
the configuration file lists each under ``departures``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-5
# I-BERT's second-order fit of e^r on (-ln 2, 0] (Alg. 1, line 8)
POLY = (0.3585, 1.353, 0.344)


def alg1_softmax(s, valid, smx):
    """SoftmAP Alg. 1 over the last axis of float scores ``s``; ``valid``
    marks the positions that take part.

    Scores less their row maximum are clipped to [T_C, 0] and rounded to a
    signed M-bit grid of scale S = -T_C / 2^(M-1). Each code v <= 0 is
    written v = r - q ln2/S with q = floor(-v / v_ln2), v_ln2 = floor(ln2/S),
    so r lies in (-v_ln2, 0]; the exponential is the polynomial
    (r + floor(b/S))^2 + floor(c/(a S^2)), shifted left by F - q bits, where
    F places q = 0 at the top of the (M + 6)-bit column. The sum saturates at
    the accumulator's M + 6 + N bits, and each probability is the floor of
    code * 2^(2M + 12) / sum, read back with scale 2^-(2M + 12)."""
    m, n, t_c = smx["M"], smx["N"], smx["T_C"]
    a, b, c = POLY
    scale = -t_c / 2 ** (m - 1)
    v_ln2 = max(1, math.floor(math.log(2.0) / scale))
    v_b, v_c = math.floor(b / scale), math.floor(c / (a * scale * scale))
    width = m + 6
    shift = max(0, width - (v_b * v_b + v_c).bit_length())
    frac = 2 * m + 12
    x = jnp.where(valid, s, -jnp.inf)
    x = jnp.clip(x - jnp.max(x, -1, keepdims=True), t_c, 0.0)
    v = jnp.clip(jnp.round(x / scale), -(2 ** (m - 1)), 0).astype(jnp.int32)
    # q = floor(-v / v_ln2): -v <= 2^(M-1), so a float quotient is exact
    q = jnp.floor((-v).astype(jnp.float32) / v_ln2).astype(jnp.int32)
    poly = (v + q * v_ln2 + v_b) ** 2 + v_c
    e = jnp.where(q <= shift, poly << jnp.maximum(shift - q, 0),
                  poly >> jnp.maximum(q - shift, 0))
    e = jnp.where(valid, jnp.minimum(e, 2 ** width - 1), 0)
    total = jnp.maximum(jnp.minimum(jnp.sum(e, -1, keepdims=True),
                                    min(2 ** (width + n) - 1, 2 ** 30 - 1)), 1)
    # floor(e * 2^frac / total), four bits at a time: each digit from a float
    # estimate, then corrected exactly in integers (no integer division, which
    # the TPU lacks); int32 holds it while the sum stays below 2^27
    quo, rem = jnp.zeros_like(e), e
    tf = total.astype(jnp.float32)
    for _ in range(frac // 4):
        rem = rem << 4
        d = jnp.floor(rem.astype(jnp.float32) / tf).astype(jnp.int32)
        rem = rem - d * total
        d, rem = jnp.where(rem < 0, d - 1, d), jnp.where(rem < 0, rem + total,
                                                         rem)
        d, rem = (jnp.where(rem >= total, d + 1, d),
                  jnp.where(rem >= total, rem - total, rem))
        quo = (quo << 4) | d
    # the result column holds 2M + 12 fraction bits: a lone maximum reads
    # all ones, not one
    quo = jnp.minimum(quo, 2 ** frac - 1)
    return quo.astype(jnp.float32) * 2.0 ** -frac


def _norm(x, p, kind):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, -1, keepdims=True)
                                 + EPS)
    if kind == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def _rope(x, theta):
    """x [T, H, D]; position t rotates pair (i, i + D/2) by t * theta^(-2i/D)."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v, scale, cfg, mm, block):
    """Causal attention, computed ``block`` query rows at a time.
    q [T, H, Dk], k [T, H, Dk], v [T, H, Dv] -> [T, H, Dv]."""
    t, h, _ = q.shape
    keys = jnp.arange(t)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        s = mm("qhd,khd->hqk", qi, k) * scale
        pos = i * block + jnp.arange(block)
        valid = (keys[None, :] <= pos[:, None])[None]
        return mm("hqk,khd->qhd", alg1_softmax(s, valid, cfg["softmax"]), v)

    out = jax.lax.map(rows, jnp.arange(t // block))
    return out.reshape(t, h, v.shape[-1])


def _gqa(a, h, cfg, mm, block):
    t = h.shape[0]
    hq, kv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    q = _rope(mm("td,de->te", h, a["wq"]["w"]).reshape(t, hq, dh),
              cfg["rope_theta"])
    k = _rope(mm("td,de->te", h, a["wk"]["w"]).reshape(t, kv, dh),
              cfg["rope_theta"])
    v = mm("td,de->te", h, a["wv"]["w"]).reshape(t, kv, dh)
    k, v = jnp.repeat(k, hq // kv, 1), jnp.repeat(v, hq // kv, 1)
    o = _attention(q, k, v, dh ** -0.5, cfg, mm, block)
    return mm("te,ed->td", o.reshape(t, hq * dh), a["wo"]["w"])


def _mla(a, h, cfg, mm, block):
    t = h.shape[0]
    hq, dn, dr, dv = (cfg["n_heads"], cfg["qk_nope_dim"], cfg["qk_rope_dim"],
                      cfg["v_head_dim"])
    if cfg["q_lora_rank"]:
        ql = _norm(mm("td,dr->tr", h, a["wdq"]["w"]), a["q_norm"], "rmsnorm")
        q = mm("tr,re->te", ql, a["wuq"]["w"])
    else:
        q = mm("td,de->te", h, a["wq"]["w"])
    q = q.reshape(t, hq, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])], -1)
    c = _norm(mm("td,dr->tr", h, a["wdkv"]["w"]), a["kv_norm"], "rmsnorm")
    kr = _rope(mm("td,de->te", h, a["wkr"]["w"])[:, None, :],
               cfg["rope_theta"])
    kn = mm("tr,re->te", c, a["wuk"]["w"]).reshape(t, hq, dn)
    v = mm("tr,re->te", c, a["wuv"]["w"]).reshape(t, hq, dv)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (t, hq, dr))], -1)
    o = _attention(q, k, v, (dn + dr) ** -0.5, cfg, mm, block)
    return mm("te,ed->td", o.reshape(t, hq * dv), a["wo"]["w"])


def forward(w, cfg, tokens, start, n_out: int, mm, block: int = 256):
    """Logits [n_out, vocab] at positions ``start .. start + n_out - 1`` of
    ``tokens`` [T] (T a multiple of ``block``; causal, so tokens after the
    last position read change nothing)."""
    norm = cfg["norm"]
    attend = _mla if cfg["attention"] == "mla" else _gqa
    x = jnp.take(w["embed"]["w"], tokens, axis=0)

    def layer(x, lw):
        x = x + attend(lw["attn"], _norm(x, lw["norm1"], norm), cfg, mm, block)
        h = _norm(x, lw["norm2"], norm)
        f = lw["ffn"]
        g = mm("td,df->tf", h, f["gate"]["w"])
        u = mm("td,df->tf", h, f["up"]["w"])
        return x + mm("tf,fd->td", jax.nn.silu(g) * u, f["down"]["w"]), None

    x, _ = jax.lax.scan(layer, x, w["stack"]["layers"])
    x = _norm(jax.lax.dynamic_slice_in_dim(x, start, n_out, 0),
              w["final_norm"], norm)
    if cfg["tie_embeddings"]:
        return mm("td,vd->tv", x, w["embed"]["w"])
    return mm("td,dv->tv", x, w["head"]["w"])
