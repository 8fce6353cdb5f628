"""Speculative decoding: draft-and-verify serving must be a pure scheduling
optimization — never a numerics change.

The oracles, in increasing integration order:

  * ``Model.verify_step`` logits over a T-token block are bit-identical to T
    successive single-token ``decode_step`` calls, for every cache family,
    contiguous and paged;
  * ``Model.verify_commit`` at accepted depth n yields a cache bit-identical
    to stepping only the n+1 accepted tokens — in particular, a full
    rejection leaves NO drafted K/V behind (the no-leak property);
  * greedy ``Engine.serve(speculative=True)`` emits bit-identical tokens to
    non-speculative serving (hence, transitively, to per-request eager
    generation) across families, backends, paged/contiguous, EOS;
  * stochastic verification is distribution-identical to autoregressive
    sampling (deterministic-proposal rejection sampling), checked by
    frequency against the analytic target distribution;
  * draft/verify telemetry conserves: per-request shares sum to the batch
    meter and the phase kinds partition it.

Plus the two sampler bugfix regressions this PR rides with: exact top-k
under ties (``jax.lax.top_k``, no full-vocab sort) and loud rejection of
unknown sampler options.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.backends.base import ZERO_COST
from repro.backends.telemetry import SlotCostAttributor
from repro.configs.registry import smoke_config
from repro.core.precision import PrecisionConfig
from repro.core.softmax_variants import SoftmaxSpec
from repro.models import build_model
from repro.serving.engine import Engine
from repro.serving.sampler import (
    NEG_INF, _temperature_logits, make_sampler, make_spec_verifier,
    temperature,
)
from repro.serving.scheduler import Request
from repro.serving.speculative import (
    DraftModelProposer, NgramProposer, ngram_propose,
)

FAMILY_ARCHS = ["olmo-1b", "minicpm3-4b", "mamba2-780m", "hymba-1.5b"]


def _setup(arch, softmax=None, **engine_kw):
    cfg = smoke_config(arch, softmax=softmax)
    m = build_model(cfg)
    params, _ = m.init_split(jax.random.PRNGKey(0))
    return cfg, m, Engine(m, params, **engine_kw)


def _mixed_trace(vocab, seed=0, n=6):
    rng = np.random.default_rng(seed)
    shapes = [(4, 6, 0.0), (8, 3, 0.0), (5, 8, 1.0), (4, 2, 3.0),
              (6, 5, 5.0), (8, 7, 6.0)][:n]
    return [Request(rid=i, prompt=rng.integers(0, vocab, (p,), dtype=np.int32),
                    max_new=mn, arrival=a, seed=100 + i)
            for i, (p, mn, a) in enumerate(shapes)]


def _assert_same_tokens(base, spec):
    for a, b in zip(base.results, spec.results):
        assert np.array_equal(a.tokens, b.tokens), (a.rid, a.tokens, b.tokens)
        assert a.done == b.done, a.rid


# ------------------------------------------------------- model-level oracles


def _paged_install(cfg, cache, pcache, B, C, bs):
    """Install per-row prefill entries into a paged pool through private
    block tables (test harness for the model-level paged oracle)."""
    n_log = C // bs

    def walk(pc, sc):
        if isinstance(pc, dict) and "table" in pc:
            out = dict(pc)
            for b in range(B):
                ids = np.arange(b * n_log, (b + 1) * n_log, dtype=np.int32)
                out["table"] = out["table"].at[:, b, :].set(jnp.asarray(ids))
                for k in pc:
                    if k == "table":
                        continue
                    v = sc[k][:, b]
                    ll = v.shape[0]
                    vv = v.reshape((ll, n_log, bs) + out[k].shape[3:])
                    out[k] = out[k].at[:, ids].set(vv.astype(out[k].dtype))
            return out
        if isinstance(pc, dict):
            return {k: walk(v, sc[k]) for k, v in pc.items()}
        return sc          # slot-resident leaf: keep the prefill value
    return walk(pcache, cache)


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
@pytest.mark.parametrize("paged", [False, True])
def test_verify_step_matches_sequential_decode(arch, paged):
    """The tentpole oracle: one T-token verify pass == T single-token decode
    steps, bit for bit — logits, the fully-accepted committed cache, AND the
    fully-rejected committed cache (rollback leaves no drafted K/V behind,
    contiguous or paged)."""
    if paged and arch == "mamba2-780m":
        pytest.skip("ssm pages nothing (state is slot-resident)")
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params, _ = m.init_split(jax.random.PRNGKey(0))
    B, P, C, T, bs = 2, 5, 16, 4, 4
    rng = np.random.default_rng(1)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (B, P)), jnp.int32)
    logits, cache = m.prefill(params, {"tokens": prompts}, cache_len=C)
    if paged:
        from repro.models import kv_cache
        pcache = kv_cache.paged_cache_zeros(cfg, B, C, bs, B * (C // bs))
        cache = _paged_install(cfg, cache, pcache, B, C, bs)
    tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    pos = jnp.full((B,), P, jnp.int32)

    seq_cache = cache
    toks, seq_logits = [tok0], []
    for i in range(T):
        lg, seq_cache = m.decode_step(params, seq_cache,
                                      {"token": toks[-1]}, pos + i)
        seq_logits.append(lg[:, 0])
        toks.append(jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None])
    seq_logits = jnp.stack(seq_logits, 1)

    block = jnp.concatenate(toks[:T], axis=1)
    v_logits, staged = m.verify_step(params, cache, {"token": block}, pos)
    assert np.array_equal(v_logits, seq_logits), arch

    # full accept: committed cache == the sequential T-step cache
    full = m.verify_commit(staged, jnp.full((B,), T - 1, jnp.int32), pos, T)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(seq_cache)):
        assert np.array_equal(a, b), (arch, a.shape)

    # full reject: committed cache == ONE decode step (token 0 only) — no
    # drafted K/V leaks past its rejection
    one_cache = cache
    _, one_cache = m.decode_step(params, one_cache, {"token": toks[0]}, pos)
    none = m.verify_commit(staged, jnp.zeros((B,), jnp.int32), pos, T)
    for a, b in zip(jax.tree.leaves(none), jax.tree.leaves(one_cache)):
        assert np.array_equal(a, b), (arch, a.shape)


# ------------------------------------------------------------ serving parity


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_greedy_spec_serve_parity_per_family(arch):
    """Greedy speculative serving emits bit-identical tokens to the
    non-speculative engine (whose own parity oracle is per-request eager
    generation) for every cache family."""
    cfg, m, eng = _setup(arch, max_new=8)
    reqs = _mixed_trace(cfg.vocab)
    base = eng.serve(reqs, slots=2)
    spec = eng.serve(reqs, slots=2, speculative=True, draft_k=3)
    _assert_same_tokens(base, spec)
    assert spec.speculative and spec.draft_k == 3
    assert spec.drafted_tokens > 0
    assert 0.0 <= spec.acceptance_rate <= 1.0


@pytest.mark.parametrize("arch", ["olmo-1b", "minicpm3-4b", "hymba-1.5b"])
def test_greedy_spec_serve_parity_paged(arch):
    """Same oracle through the paged block-table cache (rollback must not
    leak drafted K/V into pool blocks — a leak would corrupt the gathered
    attention view and break parity)."""
    cfg, m, eng = _setup(arch, max_new=8)
    reqs = _mixed_trace(cfg.vocab)
    base = eng.serve(reqs, slots=2, paged=True, block_size=4)
    spec = eng.serve(reqs, slots=2, paged=True, block_size=4,
                     speculative=True, draft_k=3)
    _assert_same_tokens(base, spec)


def test_greedy_spec_serve_parity_prefix_share():
    """Speculative decode writes land strictly past the prompt, in private
    (post-CoW) blocks — prefix sharing and drafting compose."""
    cfg, m, eng = _setup("olmo-1b", max_new=8)
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab, (12,), dtype=np.int32)
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, cfg.vocab, (2 + i,),
                                              dtype=np.int32)]),
                    max_new=6, arrival=0.0, seed=500 + i)
            for i in range(4)]
    kw = dict(slots=2, paged=True, block_size=4, prefix_share=True)
    base = eng.serve(reqs, **kw)
    spec = eng.serve(reqs, speculative=True, draft_k=3, **kw)
    _assert_same_tokens(base, spec)
    assert spec.shared_prefill_tokens > 0   # sharing actually engaged


@pytest.mark.parametrize("backend", ["int_jax", "ap_sim"])
def test_greedy_spec_serve_parity_per_backend(backend):
    """Verification sits above the softmax-backend layer: integer and
    AP-simulator execution speculate bit-identically to their own
    non-speculative serving."""
    spec_sm = SoftmaxSpec(backend, PrecisionConfig(M=6, N=16))
    n = 3 if backend == "ap_sim" else 6
    cfg, m, eng = _setup("olmo-1b", softmax=spec_sm, max_new=8)
    reqs = _mixed_trace(cfg.vocab, n=n)
    base = eng.serve(reqs, slots=2)
    spec = eng.serve(reqs, slots=2, speculative=True, draft_k=3)
    _assert_same_tokens(base, spec)


@pytest.mark.parametrize("arch", ["olmo-1b", "minicpm3-4b"])
def test_greedy_spec_serve_parity_pallas_kernel(arch):
    """``kernel="pallas"`` (fused block-table attention) composes with
    speculative verify: the fused kernel covers the K+1 verify block with
    per-row masking, and rollback of rejected drafts leaves pool blocks
    bit-identical — so both the plain and speculative pallas runs emit
    exactly the tokens of the jnp gather executor."""
    cfg, m, eng = _setup(arch, softmax=SoftmaxSpec("int"), max_new=8)
    reqs = _mixed_trace(cfg.vocab)
    kw = dict(slots=2, paged=True, block_size=4)
    base = eng.serve(reqs, **kw)
    fused = eng.serve(reqs, kernel="pallas", **kw)
    _assert_same_tokens(base, fused)
    spec = eng.serve(reqs, kernel="pallas", speculative=True, draft_k=3, **kw)
    _assert_same_tokens(base, spec)


@pytest.mark.parametrize("arch", ["olmo-1b", "minicpm3-4b"])
def test_pallas_verify_full_reject_rollback(arch):
    """Model-level no-leak oracle under the fused kernel: a fully rejected
    verify block commits to a cache bit-identical to one plain decode step —
    drafted K/V in pool blocks must not survive rejection."""
    B, C, bs, T, P = 2, 32, 4, 3, 6
    cfg = smoke_config(arch, softmax=SoftmaxSpec("int_pallas_paged"))
    m = build_model(cfg)
    params, _ = m.init_split(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, P)))}
    logits, cache = m.prefill(params, batch, C)
    from repro.models import kv_cache
    pcache = kv_cache.paged_cache_zeros(cfg, B, C, bs, B * (C // bs))
    cache = _paged_install(cfg, cache, pcache, B, C, bs)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    pos = jnp.full((B,), P, jnp.int32)
    block = jnp.concatenate(
        [tok, jnp.asarray(rng.integers(0, cfg.vocab, (B, T - 1)))], axis=1)
    _, staged = m.verify_step(params, cache, {"token": block}, pos)
    none = m.verify_commit(staged, jnp.zeros((B,), jnp.int32), pos, T)
    _, one = m.decode_step(params, cache, {"token": tok}, pos)
    for a, b in zip(jax.tree.leaves(none), jax.tree.leaves(one)):
        assert np.array_equal(a, b), arch


def test_pallas_kernel_validation():
    """The fused kernel demands a paged cache and an integer softmax — both
    misuses fail loudly, before any compilation."""
    cfg, m, eng = _setup("olmo-1b", softmax=SoftmaxSpec("int"), max_new=4)
    reqs = _mixed_trace(cfg.vocab, n=2)
    with pytest.raises(ValueError, match="requires paged"):
        eng.serve(reqs, kernel="pallas")
    _, _, eng_fp = _setup("olmo-1b", max_new=4)   # fp softmax default
    with pytest.raises(ValueError, match="integer softmax"):
        eng_fp.serve(reqs, kernel="pallas", paged=True, block_size=4)


def test_spec_serve_eos_parity():
    """EOS inside a verified block truncates exactly where the
    autoregressive loop would have stopped (done flag, pad fill, early slot
    release)."""
    cfg, m, eng0 = _setup("olmo-1b", max_new=8)
    probe_prompt = np.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (1, 5)), np.int32)
    probe = eng0.generate(probe_prompt)
    eos = int(probe.tokens[0, 5 + 2])
    cfg, m, eng = _setup("olmo-1b", max_new=8, eos_id=eos)
    reqs = _mixed_trace(cfg.vocab, seed=0)
    reqs.append(Request(rid=6, prompt=probe_prompt[0], max_new=8,
                        arrival=0.0, seed=200))
    base = eng.serve(reqs, slots=2)
    spec = eng.serve(reqs, slots=2, speculative=True, draft_k=3)
    _assert_same_tokens(base, spec)
    assert spec.by_rid()[6].done


def test_draft_model_self_proposal_full_acceptance():
    """A draft model that IS the target accepts every draft (greedy
    proposals == greedy targets), so the schedule collapses by ~K+1x while
    outputs stay bit-identical — the strongest end-to-end check that
    multi-token verify + commit preserve the autoregressive stream."""
    cfg, m, eng = _setup("olmo-1b", max_new=8)
    reqs = _mixed_trace(cfg.vocab)
    base = eng.serve(reqs, slots=2)
    spec = eng.serve(reqs, slots=2, speculative=True, draft_k=3,
                     draft="model", draft_model=m, draft_params=eng.params)
    _assert_same_tokens(base, spec)
    # the draft IS the target, so every proposal must survive — this pins
    # the draft-cache catch-up after fully-accepted rounds (the K-th
    # proposal's K/V is written before the next round proposes through it)
    assert spec.acceptance_rate == 1.0, spec.acceptance_rate
    assert spec.steps < base.steps
    for r in spec.results:
        assert 0 <= r.accepted <= r.drafted


def test_draft_model_rejects_recurrent_families():
    cfg = smoke_config("mamba2-780m")
    m = build_model(cfg)
    params, _ = m.init_split(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        DraftModelProposer(m, params, k=3)


def test_spec_requires_registry_sampler():
    cfg, m, eng = _setup("olmo-1b", max_new=4,
                         sampler=lambda logits, key: jnp.argmax(
                             logits, -1).astype(jnp.int32))
    with pytest.raises(ValueError):
        eng.serve(_mixed_trace(cfg.vocab, n=2), slots=2, speculative=True)


# ------------------------------------------------- stochastic verification


def test_spec_verifier_greedy_semantics():
    """Hand-built logits: greedy verify accepts exactly the matching draft
    prefix and emits the bonus from the first failing slot."""
    v = 8
    targets = [3, 5, 1, 6]                     # argmax per slot
    logits = np.full((4, v), -5.0, np.float32)
    for j, t in enumerate(targets):
        logits[j, t] = 5.0
    verify = make_spec_verifier("greedy", pad_id=7)
    key = jax.random.PRNGKey(0)
    # all drafts match -> 3 accepts + bonus from slot 3
    out, n, _ = verify(jnp.asarray(logits), jnp.asarray([3, 5, 1]), key)
    assert int(n) == 4 and out.tolist() == [3, 5, 1, 6]
    # first draft wrong -> bonus (the correct token) from slot 0, pad after
    out, n, _ = verify(jnp.asarray(logits), jnp.asarray([4, 5, 1]), key)
    assert int(n) == 1 and out.tolist() == [3, 7, 7, 7]
    # middle draft wrong -> accept prefix, resample at the failure
    out, n, _ = verify(jnp.asarray(logits), jnp.asarray([3, 0, 1]), key)
    assert int(n) == 2 and out.tolist() == [3, 5, 7, 7]


def test_spec_verifier_stochastic_distribution():
    """Deterministic-proposal rejection sampling is distribution-identical
    to autoregressive sampling: the first emitted token's frequencies over
    many keys match the analytic target distribution p = softmax(masked
    logits), within binomial noise — whether the draft is likely or not."""
    v, n_keys = 12, 20000
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 1.5, (3, v)), jnp.float32)
    kw = dict(temp=1.3, top_k=6)
    p = np.asarray(jax.nn.softmax(_temperature_logits(logits[0], **kw)))
    verify = make_spec_verifier("temperature", pad_id=0, **kw)
    # pure autoregressive reference: the registry sampler itself, same keys
    ar_keys = jax.random.split(jax.random.PRNGKey(7), n_keys)
    ar = np.asarray(jax.vmap(
        lambda k: temperature(logits[0], k, **kw))(ar_keys))
    ar_freq = np.bincount(ar, minlength=v) / n_keys
    tol = 4.0 * np.sqrt(np.maximum(p * (1 - p), 1e-9) / n_keys) + 1e-3
    assert np.all(np.abs(ar_freq - p) < tol)       # sanity: AR matches p
    for draft0 in (int(np.argmax(p)), int(np.argmin(p))):
        drafts = jnp.asarray([draft0, 1])
        keys = jax.random.split(jax.random.PRNGKey(42), n_keys)
        out, n, _ = jax.vmap(lambda k: verify(logits, drafts, k))(keys)
        first = np.asarray(out[:, 0])
        freq = np.bincount(first, minlength=v) / n_keys
        assert np.all(np.abs(freq - p) < tol), (draft0, freq, p)
        assert np.all(np.abs(freq - ar_freq) < 2 * tol), draft0
        assert np.all((np.asarray(n) >= 1) & (np.asarray(n) <= 3))


def test_spec_serve_stochastic_budgets_and_shape():
    """Integration smoke for stochastic speculative serving: budgets, pad
    fill, and report bookkeeping hold (bit-parity is a greedy-only
    guarantee; the distribution oracle is the verifier test above)."""
    cfg, m, eng = _setup("olmo-1b", max_new=8, sampler="temperature",
                         temp=1.3, top_k=8)
    reqs = _mixed_trace(cfg.vocab, seed=3)
    rep = eng.serve(reqs, slots=2, speculative=True, draft_k=3)
    for r, q in zip(rep.results, sorted(reqs, key=lambda x: x.rid)):
        assert r.tokens.shape == (q.prompt_len + q.max_new,)
        assert np.array_equal(r.tokens[:q.prompt_len], q.prompt)


# ------------------------------------------------------- proposers + stats


def test_ngram_propose_lookup():
    seq = np.asarray([5, 1, 2, 3, 9, 9, 1, 2, 3], np.int32)
    # suffix trigram (1,2,3) last occurred at 1..3, followed by 9, 9, 1
    assert ngram_propose(seq, 3, max_ngram=3).tolist() == [9, 9, 1]
    # short continuation pads by repeating its tail
    assert ngram_propose(seq[:5], 4, max_ngram=2).tolist() == [9, 9, 9, 9]
    # no match at all: repeat the last token
    assert ngram_propose(np.asarray([1, 2, 3], np.int32), 2).tolist() == [3, 3]


def test_ngram_index_matches_rescan():
    """The incremental per-slot n-gram index proposes exactly what a full
    rescan of the stream proposes, at every step of a growing sequence."""
    from repro.serving.speculative import _NgramIndex
    rng = np.random.default_rng(0)
    for trial in range(5):
        seq = rng.integers(0, 6, (60,), dtype=np.int32)   # tiny vocab: hits
        idx = _NgramIndex(max_ngram=3)
        idx.extend(seq[:4])
        for i in range(4, len(seq)):
            got = idx.propose(4)
            want = ngram_propose(seq[:i], 4, max_ngram=3)
            assert got.tolist() == want.tolist(), (trial, i)
            idx.extend([seq[i]])


def test_ngram_proposer_parks_inactive_slots():
    p = NgramProposer(k=2)
    p.begin(slots=3, cache_len=16)
    p.admit(1, np.asarray([4, 4], np.int32), 4, 2)
    out = p.propose([1], np.zeros((3, 1), np.int32),
                    np.zeros((3,), np.int32))
    assert out.shape == (3, 2)
    assert out[1].tolist() == [4, 4]
    assert out[0].tolist() == [0, 0]        # inactive lanes stay zero


def test_spec_draft_depth_tracking():
    """Per-slot draft depth/acceptance ride the scheduler into the report:
    each round proposes min(draft_k, remaining budget) — verifier hits past
    a request's end are not counted as useful drafting — and the totals
    agree with the per-request stats."""
    cfg, m, eng = _setup("olmo-1b", max_new=8)
    reqs = _mixed_trace(cfg.vocab)
    rep = eng.serve(reqs, slots=2, speculative=True, draft_k=3)
    assert rep.drafted_tokens == sum(r.drafted for r in rep.results)
    assert rep.accepted_tokens == sum(r.accepted for r in rep.results)
    for r, q in zip(rep.results, sorted(reqs, key=lambda x: x.rid)):
        assert 0 <= r.accepted <= r.drafted
        # accepted drafts were all COMMITTED tokens, and the admission-time
        # first token is never a draft — so the budget bounds them
        assert r.accepted <= max(q.max_new - 1, 0)


# ------------------------------------------------------------- cost meters


def test_spec_cost_conservation_and_phase_split():
    """Per-request shares still sum to the batch meter under speculation,
    and the verify phase is metered separately (draft is zero-cost for the
    host-side n-gram proposer, positive for a draft model)."""
    spec_sm = SoftmaxSpec("int", PrecisionConfig(M=6, N=16))
    cfg, m, eng = _setup("olmo-1b", softmax=spec_sm, max_new=8)
    reqs = _mixed_trace(cfg.vocab)
    rep = eng.serve(reqs, slots=2, report_cost=True, speculative=True,
                    draft_k=3)
    assert rep.cost is not None and rep.cost.cycles > 0
    summed = ZERO_COST
    for r in rep.results:
        summed = summed + r.cost
    assert summed.cycles == pytest.approx(rep.cost.cycles, rel=1e-9)
    assert summed.energy_j == pytest.approx(rep.cost.energy_j, rel=1e-9)
    assert rep.cost_verify.cycles > 0
    assert rep.cost_draft.cycles == 0           # n-gram drafts are host-side
    assert rep.cost_verify.cycles < rep.cost.cycles   # prefills are in too

    rep2 = eng.serve(reqs, slots=2, report_cost=True, speculative=True,
                     draft_k=3, draft="model", draft_model=m,
                     draft_params=eng.params)
    assert rep2.cost_draft.cycles > 0
    summed = ZERO_COST
    for r in rep2.results:
        summed = summed + r.cost
    assert summed.cycles == pytest.approx(rep2.cost.cycles, rel=1e-9)
    assert (rep2.cost_draft.cycles + rep2.cost_verify.cycles
            < rep2.cost.cycles)


def test_attributor_kinds_partition_batch_meter():
    from repro.backends.base import CostReport
    attr = SlotCostAttributor()
    c = CostReport(backend="x", vectors=1, cycles=100, latency_s=1.0,
                   energy_j=2.0)
    attr.record_request(1, c)                       # prefill
    attr.record_step(c.scaled(2), [1, 2], kind="verify")
    attr.record_step(c.scaled(3), [1, 2], kind="draft")
    total = attr.total()
    by_kind = sum((attr.total_kind(k) for k in attr.kinds()), ZERO_COST)
    assert by_kind.cycles == total.cycles == 600
    per_req = attr.report_for(1) + attr.report_for(2)
    assert per_req.cycles == pytest.approx(total.cycles, rel=1e-9)


# --------------------------------------------------- sampler bugfix rides


def test_top_k_exact_under_ties():
    """Regression: with logits tied at the k-th value, top-k must admit
    EXACTLY k tokens (lax.top_k, index tie-break) — the old value-threshold
    mask admitted every tied token."""
    v, k = 12, 4
    logits = jnp.zeros((1, v), jnp.float32)        # all 12 tied
    masked = _temperature_logits(logits, temp=1.0, top_k=k)
    kept = np.asarray(masked[0] > NEG_INF / 2)
    assert kept.sum() == k
    assert kept[:k].all()                          # index tie-break: 0..k-1
    keys = jax.random.split(jax.random.PRNGKey(0), 400)
    toks = np.asarray(jax.vmap(
        lambda kk: temperature(logits, kk, temp=1.0, top_k=k)[0])(keys))
    assert set(np.unique(toks)) <= set(range(k)), np.unique(toks)
    # partial tie across the boundary: ties at the k-th value keep only the
    # lowest-index tied token
    lg = jnp.asarray([[3.0, 2.0, 1.0, 1.0, 1.0, 0.0]], jnp.float32)
    kept = np.asarray(_temperature_logits(lg, top_k=3)[0] > NEG_INF / 2)
    assert kept.tolist() == [True, True, True, False, False, False]


def test_make_sampler_rejects_unknown_kwargs():
    with pytest.raises(ValueError, match="unexpected options"):
        make_sampler("greedy", top_k=8)
    with pytest.raises(ValueError, match="unexpected options"):
        make_sampler("temperature", topk=8)        # typo
    with pytest.raises(ValueError, match="unexpected options"):
        make_sampler("top_p", top_k=8)             # misplaced
    with pytest.raises(ValueError):
        make_sampler(lambda logits, key: logits, temp=1.0)
    # valid options still pass
    assert make_sampler("temperature", temp=0.7, top_k=8) is not None
    assert make_sampler("top_p", p=0.9, temp=1.1) is not None
    with pytest.raises(ValueError, match="unexpected options"):
        make_spec_verifier("temperature", typo=1)
    with pytest.raises(ValueError):
        make_spec_verifier(lambda logits, key: logits)
