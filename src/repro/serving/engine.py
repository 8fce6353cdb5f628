"""Batched serving engine: prefill + a SINGLE fused decode dispatch.

Generation is two device calls: one jitted prefill, then one jitted
``jax.lax.scan`` over all ``max_new`` decode steps (``make_generate_fn``).
The scan carries ``(kv_cache, prng_key, last_token, done_flags)``; sampling
runs inside the traced step body (samplers are pure jit-safe functions,
selected statically), and the cache is donated (``donate_argnums``) so each
step's ``dynamic_update_slice`` writes in place instead of copying the
multi-MB cache per token. The pre-fusion eager loop (one dispatch + one host
sampling round-trip per token) is kept as ``mode="eager"`` — it is the golden
reference for bit-exactness tests and the baseline ``benchmarks/decode_bench``
measures the fusion speedup against.

EOS early-masking: with ``eos_id`` set, per-sequence done-flags ride in the
scan carry; finished rows emit ``pad_id`` (default: ``eos_id``) for the
remaining steps. The scan still runs ``max_new`` iterations (static shape),
but finished rows stop changing.

The serve path the dry-run lowers (``serve_step``) is exactly the
``decode_step`` / whole-generation closure built here; the engine adds
batching, sampling, and the prompt-alignment policy (left-padding so all
sequences share a cache position — the uniform-position batching documented
in DESIGN.md).

Cost telemetry: with ``report_cost=True``, ``generate`` also returns a
per-call :class:`repro.backends.CostReport` covering the WHOLE batch — the AP
cycles / latency / energy the paper's hardware would spend on its softmaxes
(divide by the batch size for a per-sequence figure). The meter is a
``jax.eval_shape`` abstract trace of the prefill and ONE decode-scan body
(every softmax call site in ``models/attention.py`` records its static shape
into the active telemetry accumulator), scaled by the number of generated
tokens — matching the fused execution, where the scan body traces once and
runs ``max_new - 1`` times. It costs no device compute and never perturbs the
jit caches.

Continuous batching: ``Engine.serve(trace)`` replaces the lockstep batch
with request-level scheduling — a FIFO queue feeding a fixed set of decode
slots (``serving/scheduler.py``), ONE compiled slot-batched decode step
(``make_serve_step_fn``: per-slot positions, per-slot PRNG streams, per-slot
EOS masking), and mid-flight slot refill via a donated stripe insert. Every
served request's output is bit-identical to generating it alone with
``mode="eager"``; per-request AP cost shares are attributed through
``telemetry.SlotCostAttributor`` and sum to the batch meter. See the
scheduler section of ARCHITECTURE.md for the dataflow.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends import CostReport, telemetry
from repro.models import kv_cache
from repro.models.model import Model
from repro.serving.options import ServeOptions
from repro.serving.sampler import make_sampler, make_spec_verifier
from repro.serving.scheduler import (
    BlockAllocator, Request, SlotScheduler, prefix_keys,
)
from repro.serving.spans import LoopSpans
from repro.serving.speculative import make_proposer

_legacy_serve_warned = False


def _warn_legacy_serve_kwargs():
    """One DeprecationWarning per process for Engine.serve(**kwargs) calls."""
    global _legacy_serve_warned
    if not _legacy_serve_warned:
        _legacy_serve_warned = True
        warnings.warn(
            "Engine.serve(**kwargs) is deprecated; build a "
            "repro.serving.ServeOptions and call "
            "serve(requests, options=...) instead",
            DeprecationWarning, stacklevel=3)


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # [B, prompt + generated]
    prompt_len: int
    steps: int
    cost: Optional[CostReport] = None   # softmax AP cost of the whole batch
    done: Optional[np.ndarray] = None   # [B] bool, only when eos_id is set


@dataclasses.dataclass
class RequestResult:
    """One served request: prompt + exactly ``max_new`` generated tokens
    (pad-filled past an early EOS — bit-identical to the per-request eager
    row), plus scheduling and cost attribution metadata."""
    rid: int
    tokens: np.ndarray          # [prompt_len + max_new]
    prompt_len: int
    done: bool                  # EOS hit (False when eos_id unset)
    admitted_at: float          # serve-clock step time of admission
    finished_at: float          # serve-clock step time of completion
    latency_s: float            # wall seconds, queue entry -> completion
    cost: Optional[CostReport] = None   # this request's attributed share
    shared_prefix: int = 0      # prompt tokens served from shared blocks
    drafted: int = 0            # speculative: draft tokens proposed
    accepted: int = 0           # speculative: draft tokens accepted
    # SLA telemetry (chunked prefill / priority classes / preemption)
    priority: int = 0           # static class (0 = most urgent)
    deadline: Optional[float] = None    # relative completion budget (steps)
    deadline_met: Optional[bool] = None  # None when no deadline was set
    first_token_at: float = 0.0  # serve-clock step time of the first token
    ttft_s: float = 0.0         # wall seconds, queue entry -> first token
    tbt_s: List[float] = dataclasses.field(default_factory=list)
    preempts: int = 0           # times this request was swapped out


@dataclasses.dataclass
class ServeReport:
    """Result of one ``Engine.serve`` run over a trace."""
    results: List[RequestResult]        # ordered by rid
    steps: int                          # decode steps executed
    wall_s: float
    slots: int
    cache_len: int
    cost: Optional[CostReport] = None   # batch meter (prefills + all steps)
    paged: bool = False
    block_size: int = 0
    prefill_tokens: int = 0             # prompt tokens actually prefilled
    shared_prefill_tokens: int = 0      # prompt tokens served from shared blocks
    cow_copies: int = 0
    evictions: int = 0
    speculative: bool = False
    draft_k: int = 0
    drafted_tokens: int = 0             # draft tokens proposed (all rounds)
    accepted_tokens: int = 0            # draft tokens the verifier accepted
    cost_draft: Optional[CostReport] = None    # batch meter, draft phase
    cost_verify: Optional[CostReport] = None   # batch meter, verify phase
    # SLA-aware scheduling telemetry
    prefill_chunk: int = 0              # 0: whole prefill per admission
    max_prefill_per_step: int = 0       # worst prompt tokens in one step
    preemptions: int = 0
    resumes: int = 0
    leaked_blocks: int = 0              # pool blocks unaccounted after drain
    class_latency: Optional[dict] = None  # per-priority-class latency/SLA
    # host loop (serving/spans.py): phase -> [self seconds, count, longest
    # span]; "loop" is the rest of wall_s. Gap kinds count the gaps between
    # tokens holding a whole-prompt ("full") or prefix-hit ("tail")
    # admission prefill, or neither ("plain").
    host_s: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    gap_kinds: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens (0.0 when not speculative)."""
        return self.accepted_tokens / max(self.drafted_tokens, 1)

    def by_rid(self) -> Dict[int, RequestResult]:
        return {r.rid: r for r in self.results}


def _step_inputs(model: Model, nxt, b: int, pos):
    """Decode-step input dict for one traced position (scalar, may be traced)."""
    step_in = {"token": nxt}
    if model.cfg.rope_type == "mrope":
        step_in["positions"] = jnp.full((3, b, 1), pos, jnp.int32)
    return step_in


def make_generate_fn(model: Model, sample_fn: Callable, max_new: int,
                     eos_id: Optional[int] = None,
                     pad_id: Optional[int] = None) -> Callable:
    """Build the whole-generation function: (params, cache, prefill_logits,
    key, base_pos) -> (tokens [B, max_new], cache, done [B]).

    One ``lax.scan`` over ``max_new - 1`` decode steps; the body traces once.
    Carry layout: ``(cache, key, last_token [B,1], done [B])``. ``base_pos``
    is a traced int32 scalar (the shared prompt length). Jit with
    ``donate_argnums=(1,)`` so the cache updates in place.
    """
    pad = eos_id if pad_id is None else pad_id

    def mask_done(tok, done):
        if eos_id is None:
            return tok, done
        tok = jnp.where(done, jnp.int32(pad), tok)
        return tok, done | (tok == eos_id)

    def generate_fn(params, cache, logits, key, base_pos):
        b = logits.shape[0]
        done = jnp.zeros((b,), bool)
        key, sub = jax.random.split(key)
        tok = sample_fn(logits[:, -1], sub)
        tok, done = mask_done(tok, done)
        if max_new <= 1:
            return tok[:, None], cache, done

        # Align the prefill-built cache to the decode-step output structure
        # (dtypes must be identical for a type-stable scan carry; shapes
        # already match or lax.scan errors loudly).
        out_struct = jax.eval_shape(
            model.decode_step, params, cache,
            _step_inputs(model, tok[:, None], b, base_pos), base_pos)
        cache = jax.tree.map(lambda c, s: c.astype(s.dtype), cache,
                             out_struct[1])

        def step(carry, t):
            cache, key, nxt, done = carry
            pos = base_pos + t
            logits, cache = model.decode_step(
                params, cache, _step_inputs(model, nxt, b, pos), pos)
            key, sub = jax.random.split(key)
            tok = sample_fn(logits[:, -1], sub)
            tok, done = mask_done(tok, done)
            return (cache, key, tok[:, None], done), tok

        with telemetry.repeat(max_new - 1):  # body traces once, runs n times
            (cache, _, _, done), rest = jax.lax.scan(
                step, (cache, key, tok[:, None], done),
                jnp.arange(max_new - 1, dtype=jnp.int32))
        toks = jnp.concatenate([tok[:, None], rest.T], axis=1)
        return toks, cache, done

    return generate_fn


def make_serve_step_fn(model: Model, sample_fn: Callable,
                       eos_id: Optional[int] = None,
                       pad_id: Optional[int] = None) -> Callable:
    """Build the continuous-batching decode step: (params, cache, tok [S,1],
    pos [S], keys [S,2], done [S]) -> (cache, tok [S], keys, done).

    ONE jitted function drives the whole serve loop — slots at arbitrary
    positions decode together (``decode_step`` takes the per-slot position
    vector), each slot samples from its own PRNG stream (vmapped key split +
    sample, so every lane reproduces the per-request eager stream bit-for-
    bit), and EOS masking runs per slot. Jit with ``donate_argnums=(1,)``.
    Free slots ride along as dead lanes: their positions are parked at
    ``cache_len`` (no cache write lands) and their outputs are ignored.
    """
    pad = eos_id if pad_id is None else pad_id

    def serve_step(params, cache, tok, pos, keys, done):
        step_in = {"token": tok}
        if model.cfg.rope_type == "mrope":
            # text-only decode: all three M-RoPE position streams sit at the
            # slot's cache position — the same values the eager loop's
            # jnp.full((3, b, 1), pos) feeds, so slot streams replay the
            # per-request eager streams bit-for-bit
            step_in["positions"] = jnp.broadcast_to(
                pos.astype(jnp.int32)[None, :, None], (3, pos.shape[0], 1))
        logits, cache = model.decode_step(params, cache, step_in, pos)

        def one(row_logits, key):
            key, sub = jax.random.split(key)
            t = sample_fn(row_logits[None, :], sub)[0]
            return t, key

        toks, keys = jax.vmap(one)(logits[:, -1], keys)
        if eos_id is not None:
            toks = jnp.where(done, jnp.int32(pad), toks)
            done = done | (toks == eos_id)
        return cache, toks, keys, done

    return serve_step


def make_spec_step_fn(model: Model, verifier: Callable, k: int) -> Callable:
    """Build the speculative draft-verify step: (params, cache, tok [S,1],
    drafts [S,K], pos [S], keys [S,2]) -> (cache, out [S,K+1], n_emit [S],
    keys).

    ONE jitted dispatch per round: the K+1-token block (last committed token
    ++ drafts) runs through ``Model.verify_step`` (all slots, all positions
    in one forward pass), the per-slot rejection sampler turns the K+1
    logits rows into 1..K+1 emissions, and ``Model.verify_commit`` rolls the
    cache back to exactly the accepted depth — rejected drafts leave no K/V
    behind in either the contiguous or the paged layout. Jit with
    ``donate_argnums=(1,)``. Free slots ride along as dead lanes (positions
    parked at ``cache_len``: every write drops, outputs are ignored)."""
    t = k + 1

    def spec_step(params, cache, tok, drafts, pos, keys):
        block = jnp.concatenate([tok, drafts], axis=1)          # [S, K+1]
        logits, staged = model.verify_step(params, cache,
                                           {"token": block}, pos)
        out, n_emit, keys = jax.vmap(verifier)(logits, drafts, keys)
        cache = model.verify_commit(staged, n_emit - 1, pos, t)
        return cache, out, n_emit, keys

    return spec_step


def _default_num_blocks(slots: int, n_logical: int, prefix_share: bool):
    """Pool size when ``ServeOptions.num_blocks`` is None: every slot's worst
    case, plus one request's worth of slack for the cross-request prefix
    cache to live in."""
    return slots * n_logical + (n_logical if prefix_share else 0)


class Engine:
    def __init__(self, model: Model, params, max_new: int = 64,
                 sampler: str = "greedy", eos_id: Optional[int] = None,
                 pad_id: Optional[int] = None, **sampler_kw):
        self.model = model
        self.params = params
        self.max_new = max_new
        self.eos_id = eos_id
        self.pad_id = eos_id if pad_id is None else pad_id
        self.sample = make_sampler(sampler, **sampler_kw)
        # registry samplers keep their spec around so speculative serving can
        # derive the target distribution (callable samplers cannot be
        # speculated against — their distribution is opaque)
        self._sampler_kind = sampler if isinstance(sampler, str) else None
        self._sampler_kw = dict(sampler_kw)
        self._spec_jits: dict = {}   # (draft_k, kernel[, mesh]) -> verify step
        self._kernel_models: dict = {}   # kernel name -> Model variant
        self._serve_jits: dict = {}      # kernel[, mesh] -> jitted serve step
        self._mesh_models: dict = {}     # (kernel, mesh) -> serving Model
        self._mesh_execs: dict = {}      # mesh -> placed params + per-mesh jits
        # donate the cache (arg 1): decode updates it in place; params (arg 0)
        # are reused across calls and must NOT be donated. Prefill donates
        # nothing: params are reused, the int32 token batch feeds a gather XLA
        # cannot alias, and callers may reuse their extra_inputs arrays
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
        self._prefill = jax.jit(model.prefill, static_argnames=("cache_len",))
        self._fused = jax.jit(
            make_generate_fn(model, self.sample, max_new, eos_id, pad_id),
            donate_argnums=(1,))
        # continuous-batching executor: the serve step jit is shared across
        # every serve() call with the same (slots, cache_len); the slot insert
        # writes a freshly prefilled [1, cache_len] cache into slot s of the
        # donated [slots, cache_len] buffers (batch axis 1 on every leaf)
        self._serve_step = jax.jit(
            make_serve_step_fn(model, self.sample, eos_id, pad_id),
            donate_argnums=(1,))
        self._serve_jits["jnp"] = self._serve_step
        self._insert_slot = jax.jit(
            lambda cache, slot_cache, slot: jax.tree.map(
                lambda c, s: jax.lax.dynamic_update_slice_in_dim(
                    c, s.astype(c.dtype), slot, axis=1), cache, slot_cache),
            donate_argnums=(0,))
        # paged-cache executors: install a prefilled request through the slot's
        # block table (pool scatter + table row + slot-resident stripe), copy a
        # block for the allocator's copy-on-write handshake, gather a shared
        # prefix back into contiguous form for tail-only prefill. All shapes
        # are static per (prompt-length, block-count) pair, so the jit caches
        # stay as bounded as the prefill shape set.
        self._paged_scatter = jax.jit(
            kv_cache.paged_scatter, static_argnames=("t0", "t1"),
            donate_argnums=(0,))
        self._paged_copy = jax.jit(kv_cache.paged_copy_block,
                                   donate_argnums=(0,))
        self._paged_prefix = jax.jit(kv_cache.paged_prefix_view,
                                     static_argnames=("s",))
        self._prefill_tail = jax.jit(model.prefill_tail,
                                     static_argnames=("prefix_len",))
        # chunked prefill (contiguous layout): commit one chunk into a slot
        # stripe / gather the committed prefix back for the next tail chunk.
        # Static per (chunk length) pair — as bounded as the prefill shapes.
        self._slot_scatter = jax.jit(
            kv_cache.slot_scatter, static_argnames=("t0", "t1"),
            donate_argnums=(0,))
        self._slot_prefix = jax.jit(kv_cache.slot_prefix_view,
                                    static_argnames=("s",))
        # preemption swap-out/-in: snapshot a victim's non-shared blocks +
        # slot stripes to host, restore them on resume (cache donated)
        self._swap_read = jax.jit(kv_cache.swap_read)
        self._swap_write = jax.jit(kv_cache.swap_write, donate_argnums=(0,))
        self._meter_cache: dict = {}  # (batch shapes, cache_len) -> CostReport

    def _decode_inputs(self, nxt, b: int, p: int, t: int):
        return _step_inputs(self.model, nxt, b, p + t)

    def meter_request(self, batch: dict, cache_len: int, cache,
                      max_new: Optional[int] = None) -> CostReport:
        """Abstract-trace the request's softmax AP cost (no device compute).

        ``cache`` is any decode-ready cache pytree of the right shapes (the
        one prefill just returned); decode cost is one scan-body trace at the
        full cache length — the AP processes whole rows with its mask
        register, exactly like the model's masked attention — times the
        generated tokens, mirroring the fused scan's trace-once/run-n
        execution. The report depends only on static shapes, so it is memoized
        on the batch's input shapes + cache_len: repeated same-shape calls
        skip the trace.
        """
        b, p = batch["tokens"].shape
        n_new = self.max_new if max_new is None else max_new
        key = (tuple(sorted((k, tuple(v.shape)) for k, v in batch.items())),
               cache_len, n_new)
        if key in self._meter_cache:
            return self._meter_cache[key]
        with telemetry.collect() as acc:
            jax.eval_shape(
                functools.partial(self.model.prefill, cache_len=cache_len),
                self.params, batch)
        cost = acc.total()
        decode_steps = n_new - 1
        if decode_steps > 0:
            step_in = self._decode_inputs(
                jnp.zeros((b, 1), jnp.int32), b, p, 0)
            with telemetry.collect() as acc:
                jax.eval_shape(self.model.decode_step, self.params, cache,
                               step_in, jnp.int32(p))
            cost = cost + acc.total().scaled(decode_steps)
        self._meter_cache[key] = cost
        return cost

    def generate(self, prompts: np.ndarray, key=None,
                 extra_inputs: Optional[dict] = None,
                 report_cost: bool = False,
                 mode: str = "fused",
                 max_new: Optional[int] = None,
                 cache_len: Optional[int] = None) -> GenerationResult:
        """prompts: [B, P] int32 (left-pad with a fill token upstream; the
        engine batches uniformly at cache position P). mode: "fused" (one
        dispatch after prefill) or "eager" (the pre-fusion per-token loop —
        golden reference / benchmark baseline).

        ``max_new`` overrides the engine default for THIS call — eager mode
        only (the fused scan is compiled for the engine's ``max_new``).
        ``cache_len`` pins the decode cache length (default: P + max_new);
        the serve parity harness uses it so the per-request eager reference
        runs against cache buffers shaped exactly like the serving slots."""
        if mode not in ("fused", "eager"):
            raise ValueError(f"mode must be 'fused' or 'eager', got {mode!r}")
        n_new = self.max_new if max_new is None else max_new
        if n_new != self.max_new and mode != "eager":
            raise ValueError("per-call max_new override is eager-only")
        key = key if key is not None else jax.random.PRNGKey(0)
        b, p = prompts.shape
        cache_len = p + n_new if cache_len is None else cache_len
        if cache_len < p + n_new:
            raise ValueError(f"cache_len {cache_len} < prompt {p} + "
                             f"max_new {n_new}")
        batch = {"tokens": jnp.asarray(prompts), **(extra_inputs or {})}
        logits, cache = self._prefill(self.params, batch, cache_len=cache_len)
        cost = (self.meter_request(batch, cache_len, cache, n_new)
                if report_cost else None)
        if mode == "fused":
            gen, cache, done = self._fused(self.params, cache, logits, key,
                                           jnp.int32(p))
            gen, done = np.asarray(gen), np.asarray(done)
        else:
            gen, done = self._generate_eager(cache, logits, key, b, p, n_new)
        out = np.concatenate([prompts.astype(np.int32), gen], axis=1)
        return GenerationResult(out, prompt_len=p, steps=n_new,
                                cost=cost,
                                done=done if self.eos_id is not None else None)

    def _generate_eager(self, cache, logits, key, b: int, p: int,
                        max_new: Optional[int] = None):
        """Pre-fusion loop: one device dispatch + one host sampling
        round-trip per generated token."""
        max_new = self.max_new if max_new is None else max_new
        done = jnp.zeros((b,), bool)
        key, sub = jax.random.split(key)
        nxt = self.sample(logits[:, -1], sub)
        if self.eos_id is not None:
            done = done | (nxt == self.eos_id)
        toks = [nxt[:, None]]
        for t in range(max_new - 1):
            step_in = self._decode_inputs(nxt[:, None], b, p, t)
            logits, cache = self._decode(self.params, cache, step_in,
                                         jnp.int32(p + t))
            key, sub = jax.random.split(key)
            tok = self.sample(logits[:, -1], sub)
            if self.eos_id is not None:
                tok = jnp.where(done, jnp.int32(self.pad_id), tok)
                done = done | (tok == self.eos_id)
            nxt = tok
            toks.append(nxt[:, None])
        return (np.asarray(jnp.concatenate(toks, axis=1)),
                np.asarray(done))

    # ------------------------------------------------- continuous batching

    @staticmethod
    def _spec_kind(model: Model) -> Optional[str]:
        spec = model.cfg.softmax
        return None if spec is None else spec.kind

    def _meter_prefill(self, p_len: int, cache_len: int, enc_len: int = 0,
                       model: Optional[Model] = None) -> CostReport:
        model = self.model if model is None else model
        key = ("prefill", p_len, cache_len, enc_len, self._spec_kind(model))
        if key not in self._meter_cache:
            batch = {"tokens": jnp.zeros((1, p_len), jnp.int32)}
            if enc_len:
                batch["frames"] = jnp.zeros((1, enc_len, model.cfg.d_model),
                                            jnp.float32)
            if model.cfg.rope_type == "mrope":
                batch["positions"] = jnp.zeros((3, 1, p_len), jnp.int32)
            with telemetry.collect() as acc:
                jax.eval_shape(
                    functools.partial(model.prefill, cache_len=cache_len),
                    self.params, batch)
            self._meter_cache[key] = acc.total()
        return self._meter_cache[key]

    def _meter_serve_step(self, slots: int, cache_len: int,
                          paged_geom=None, t: int = 1, enc_len: int = 0,
                          model: Optional[Model] = None) -> CostReport:
        """Softmax AP cost of ONE slot-batched step (static shapes — one
        abstract trace, memoized). ``t=1`` meters the plain decode step;
        ``t>1`` meters the speculative verify step (``Model.verify_step``
        over a ``t``-token block — the softmax rows grow from 1 to t
        queries per head, which the meter sees through the static score
        shapes). ``paged_geom``: (block_size, num_blocks) to meter the
        paged layout (same softmax shapes — the gather materializes the
        same [B, C] view — but kept honest). ``model`` (default: the
        engine's own) lets a softmax-variant serve meter ITS schedule."""
        model = self.model if model is None else model
        key = ("serve_step", slots, cache_len, paged_geom, t, enc_len,
               self._spec_kind(model))
        if key not in self._meter_cache:
            if paged_geom is None:
                struct = kv_cache.cache_struct(model.cfg, slots, cache_len,
                                               enc_len)
            else:
                struct = kv_cache.paged_cache_struct(
                    model.cfg, slots, cache_len, *paged_geom)
            fn = model.decode_step if t == 1 else model.verify_step
            step_in = {"token": jnp.zeros((slots, t), jnp.int32)}
            if model.cfg.rope_type == "mrope":
                step_in["positions"] = jnp.zeros((3, slots, t), jnp.int32)
            with telemetry.collect() as acc:
                jax.eval_shape(fn, self.params, struct,
                               {**step_in},
                               jnp.zeros((slots,), jnp.int32))
            self._meter_cache[key] = acc.total()
        return self._meter_cache[key]

    _INT_KINDS = ("int", "int_jax", "int_pallas", "int_pallas_paged")

    def _variant_model(self, softmax_kind: Optional[str]) -> Model:
        """The Model serving under ``ServeOptions.softmax_kind`` — the
        engine's own config with the softmax spec's kind swapped (precision
        point kept), SHARING ``self.params``. A model whose params carry no
        learned softmax state (``p["smx"]``) serves a learnable variant at
        the backend cfg's default operating point; extra param leaves under a
        non-learnable variant simply ride along unused."""
        if softmax_kind is None:
            return self.model
        key = ("softmax", softmax_kind)
        if key not in self._kernel_models:
            from repro.core.softmax_variants import SoftmaxSpec

            spec = self.model.cfg.softmax or SoftmaxSpec()
            var = (spec if spec.kind == softmax_kind
                   else dataclasses.replace(spec, kind=softmax_kind))
            ctx = self.model.ctx
            self._kernel_models[key] = Model(
                self.model.cfg.with_softmax(var), rules=ctx.rules,
                mesh=ctx.mesh, dtype=ctx.dtype)
        return self._kernel_models[key]

    def _variant_prefill(self, softmax_kind: Optional[str], tail: bool):
        """Memoized prefill / prefill_tail jit for a softmax variant (the
        engine's own jits when ``softmax_kind`` is None)."""
        if softmax_kind is None:
            return self._prefill_tail if tail else self._prefill
        key = ("prefill_tail" if tail else "prefill", softmax_kind)
        if key not in self._serve_jits:
            m = self._variant_model(softmax_kind)
            self._serve_jits[key] = (
                jax.jit(m.prefill_tail, static_argnames=("prefix_len",))
                if tail else
                jax.jit(m.prefill, static_argnames=("cache_len",)))
        return self._serve_jits[key]

    def _kernel_model(self, kernel: str,
                      softmax_kind: Optional[str] = None) -> Model:
        """The Model variant executing decode under ``kernel``.

        ``"jnp"`` is the engine's own model (or its ``softmax_kind``
        variant). ``"pallas"`` swaps the softmax spec to ``int_pallas_paged``
        — the SAME Alg.-1 ``apply`` body, so prefill and every
        non-paged-decode site lower identically and the variant SHARES
        ``self.params`` — while the paged decode/verify sites route through
        the fused block-table kernel. Requires an integer-family effective
        spec: the fused kernel runs Alg. 1 and nothing else, so a float or
        zoo-variant softmax has no bit-identical fused counterpart and is
        rejected loudly."""
        base = self._variant_model(softmax_kind)
        if kernel == "jnp":
            return base
        if kernel != "pallas":
            raise ValueError(
                f"unknown decode kernel {kernel!r} (expected jnp | pallas)")
        key = ("pallas", softmax_kind)
        if key not in self._kernel_models:
            spec = base.cfg.softmax
            if spec is None or spec.kind not in self._INT_KINDS:
                kind = None if spec is None else spec.kind
                raise ValueError(
                    "kernel='pallas' serves the Alg.-1 integer softmax "
                    f"family (one of {self._INT_KINDS}); the requested "
                    f"softmax {kind!r} is not an Alg.-1 dataflow — serve "
                    "it with kernel='jnp'")
            var = dataclasses.replace(spec, kind="int_pallas_paged")
            ctx = base.ctx
            self._kernel_models[key] = Model(
                base.cfg.with_softmax(var), rules=ctx.rules,
                mesh=ctx.mesh, dtype=ctx.dtype)
        return self._kernel_models[key]

    def _serving_model(self, kernel: str, mesh,
                       softmax_kind: Optional[str] = None) -> Model:
        """The Model variant decoding under ``kernel`` ON ``mesh``: same
        config and params as :meth:`_kernel_model`, but built with the
        serving rules (heads / MLA latents on the model axis, kv_seq
        unsharded) so every ``ctx.shard`` carry constraint resolves to the
        stable head-sharded layout. Memoized per (kernel, mesh[, softmax]) —
        a mesh is hashable and serve() reuses one mesh object across
        calls."""
        from repro.distributed.sharding import ShardingRules, serving_rules

        key = (kernel, mesh, softmax_kind)
        if key not in self._mesh_models:
            base = self._kernel_model(kernel, softmax_kind)  # validates both
            ctx = base.ctx
            rules = serving_rules(
                ctx.rules if ctx.rules is not None
                else ShardingRules(base.cfg.sharding_overrides))
            self._mesh_models[key] = Model(base.cfg, rules=rules, mesh=mesh,
                                           dtype=ctx.dtype)
        return self._mesh_models[key]

    def _mesh_exec(self, mesh, softmax_kind: Optional[str] = None) -> dict:
        """Per-mesh executor state: params placed ONCE (column/row-parallel
        NamedShardings via the serving rules) plus the prefill jits bound to
        the mesh-rules model. Committed-device arrays cannot mix with
        single-device ones inside a jit, so every function that touches
        params or cache gets a per-mesh instance; the cache-surgery jits
        (scatter / copy / insert / prefix-gather) are placement-agnostic
        pytree ops and are shared with the single-device path."""
        key = (mesh, softmax_kind)
        if key not in self._mesh_execs:
            from repro.serving.sharded import shard_params

            m = self._serving_model("jnp", mesh, softmax_kind)
            # params place once PER MESH — the variant models share the
            # engine's param tree, so any already-placed copy is reused
            placed = next((ex["params"] for (ms, _), ex
                           in self._mesh_execs.items() if ms == mesh), None)
            if placed is None:
                placed = shard_params(self.params, self.model.param_axes(),
                                      m.ctx.rules, mesh)
            self._mesh_execs[key] = {
                "rules": m.ctx.rules,
                "params": placed,
                "prefill": jax.jit(m.prefill, static_argnames=("cache_len",)),
                "prefill_tail": jax.jit(m.prefill_tail,
                                        static_argnames=("prefix_len",)),
            }
        return self._mesh_execs[key]

    def _get_serve_step(self, kernel: str = "jnp", mesh=None,
                        softmax_kind: Optional[str] = None):
        """The compiled continuous-batching step for one (decode kernel,
        softmax variant) (memoized; plain ``"jnp"`` aliases the step built in
        ``__init__``; with a ``mesh`` the step closes over the serving-rules
        model variant)."""
        key = (kernel if mesh is None else (kernel, mesh)
               ) if softmax_kind is None else (kernel, mesh, softmax_kind)
        if key not in self._serve_jits:
            model = (self._kernel_model(kernel, softmax_kind) if mesh is None
                     else self._serving_model(kernel, mesh, softmax_kind))
            self._serve_jits[key] = jax.jit(
                make_serve_step_fn(model, self.sample,
                                   self.eos_id, self.pad_id),
                donate_argnums=(1,))
        return self._serve_jits[key]

    def decode_executor(self, kernel: str = "jnp", mesh=None):
        """``(model, params)`` that ``serve`` decodes with under ``kernel``
        on ``mesh`` (None: one device) — for callers that replay decode
        steps outside the serve loop, e.g. to compare two executors'
        logits on one cache."""
        if mesh is None:
            return self._kernel_model(kernel), self.params
        return (self._serving_model(kernel, mesh),
                self._mesh_exec(mesh)["params"])

    def lower_serve_step(self, options: ServeOptions, cache_len: int):
        """Lower — without running — the single-device paged decode step
        ``serve(..., options=options)`` runs at ``cache_len`` (the served
        report's ``cache_len``), so callers can read the program it
        dispatches (``.as_text()``: which kernels, which custom calls)."""
        opt = options
        if not opt.paged:
            raise ValueError("lower_serve_step covers paged serving")
        cfg = self._variant_model(opt.softmax_kind).cfg
        s, bs = opt.slots, opt.block_size
        nb = opt.num_blocks or _default_num_blocks(s, cache_len // bs,
                                                   opt.prefix_share)
        cache = kv_cache.paged_cache_struct(cfg, s, cache_len, bs, nb)
        sds = jax.ShapeDtypeStruct
        step = self._get_serve_step(opt.kernel, None, opt.softmax_kind)
        return step.lower(self.params, cache, sds((s, 1), jnp.int32),
                          sds((s,), jnp.int32), sds((s, 2), jnp.uint32),
                          sds((s,), jnp.bool_))

    def _get_spec_step(self, draft_k: int, kernel: str = "jnp", mesh=None,
                       softmax_kind: Optional[str] = None):
        """The compiled draft-verify step for one (draft depth, kernel[,
        mesh, softmax]) — shapes are static per (slots, cache_len, K), so
        serving any number of traces shares one compilation per geometry."""
        key = (draft_k, kernel, mesh, softmax_kind)
        if key not in self._spec_jits:
            verifier = make_spec_verifier(
                self._sampler_kind,
                pad_id=self.pad_id if self.pad_id is not None else 0,
                **self._sampler_kw)
            model = (self._kernel_model(kernel, softmax_kind) if mesh is None
                     else self._serving_model(kernel, mesh, softmax_kind))
            self._spec_jits[key] = jax.jit(
                make_spec_step_fn(model, verifier, draft_k),
                donate_argnums=(1,))
        return self._spec_jits[key]

    def _prefix_struct(self, s: int):
        """Abstract shared-prefix pytree for metering tail-only prefill —
        derived from the real pool builders (a degenerate one-block pool of
        block_size ``s``, viewed through ``paged_prefix_view``) so it can
        never drift from the serving layouts in ``models/kv_cache.py``."""
        struct = kv_cache.paged_cache_struct(self.model.cfg, 1, s, s, 1)
        return jax.eval_shape(
            functools.partial(kv_cache.paged_prefix_view, s=s),
            struct, jax.ShapeDtypeStruct((1,), jnp.int32))

    def _meter_prefill_tail(self, s: int, tail: int,
                            model: Optional[Model] = None) -> CostReport:
        """Softmax AP cost of a tail-only prefill (tail tokens attending over
        s shared-prefix positions) — what a prefix-shared admission actually
        executes."""
        model = self.model if model is None else model
        key = ("prefill_tail", s, tail, self._spec_kind(model))
        if key not in self._meter_cache:
            with telemetry.collect() as acc:
                jax.eval_shape(
                    functools.partial(model.prefill_tail, prefix_len=s),
                    self.params,
                    {"tokens": jnp.zeros((1, tail), jnp.int32)},
                    self._prefix_struct(s))
            self._meter_cache[key] = acc.total()
        return self._meter_cache[key]

    def serve(self, requests: Sequence[Request],
              options: Optional[ServeOptions] = None, **legacy) -> ServeReport:
        """Continuous-batching serving over a trace of timed arrivals.

        Configuration lives in ONE object: ``serve(reqs,
        options=ServeOptions(paged=True, prefix_share=True, ...))``. Every
        field below keeps the name and default of the keyword argument it
        replaced; cross-field constraints (``prefix_share`` requires
        ``paged``, ...) are validated by ``ServeOptions.__post_init__`` at
        construction time. The legacy spelling ``serve(reqs, paged=True,
        ...)`` still works — the kwargs are mapped onto a ``ServeOptions``
        with a one-time ``DeprecationWarning``; passing both ``options=`` and
        extra kwargs is an error.

        Runs ONE compiled decode step (``make_serve_step_fn``) in a host
        loop; between steps the scheduler admits arrived requests into free
        slots — a batch-1 prefill of the new prompt is written into the
        slot's ``[slots, cache_len]`` cache stripe (``_insert_slot``, cache
        donated) without touching the compiled step. Each request's output
        is bit-identical to generating it alone with ``mode="eager"`` and
        ``key=PRNGKey(request.seed)`` at the same ``cache_len``.

        ``policy="gang"`` admits only whole batches (static batching on the
        same executor — the serve_bench baseline). With ``report_cost``,
        ``ServeReport.cost`` is the batch AP meter and each request carries
        its attributed share (prefill + an even split of every decode step
        it was active in); the shares sum to the batch meter.

        ``paged=True`` swaps the per-slot contiguous cache for the paged
        layout: a global pool of ``num_blocks`` KV blocks of ``block_size``
        tokens plus per-slot block tables (attention gathers through the
        table — outputs stay bit-identical). ``prefix_share=True``
        additionally reuses resident prompt blocks across requests with a
        common prefix (block-granular, cumulative-content matched, refcounted
        by a :class:`~repro.serving.scheduler.BlockAllocator`, copy-on-write
        on the first divergent write) and prefills only the unshared tail.
        Sharing covers the dense/moe/MLA families — including int8 KV
        (``cfg.kv_quant``): prefill is fake-quant (the prompt attends the
        dequantized codes it caches — see ``transformer.attn_prefill``), and
        per-position scales ride the pool next to the codes through scatter /
        CoW / swap / tail gather, so shared int8 blocks replay byte-for-byte.
        SSM state and hybrid rings are whole-prefix summaries, so those
        families page without sharing.

        ``speculative=True`` switches every active slot to draft-and-verify
        decoding: a proposer guesses ``draft_k`` tokens per round
        (``draft="ngram"`` — host-side prompt lookup, the default — or
        ``draft="model"`` with a small ``draft_model``/``draft_params`` from
        the config registry), one compiled verify step scores all K+1
        positions at once (``Model.verify_step``), jit-safe rejection
        sampling accepts a prefix and emits one extra token, and the cache
        rolls back rejected positions (``Model.verify_commit``) in both the
        contiguous and paged layouts. Greedy sampling makes the emitted
        stream bit-identical to non-speculative serving; stochastic registry
        samplers stay distribution-identical (deterministic-proposal
        rejection sampling). Works with every cache family serve() covers
        and composes with ``paged``/``prefix_share``. With ``report_cost``,
        draft and verify phases are charged separately to the batch meter
        (``ServeReport.cost_draft`` / ``cost_verify``; conservation across
        per-request shares is preserved).

        ``kernel="pallas"`` (paged, integer-softmax models only) runs decode
        and verify steps through the fused block-table attention kernel
        (``kernels/paged_attention``) instead of gather-then-attend —
        bit-identical outputs in interpret mode (compiled on a TPU, logits
        agree within bf16 rounding), one compiled step per geometry exactly like
        the default executor, and composes with ``prefix_share`` and
        ``speculative``.

        ``mesh`` (or ``shards=N``, which builds a 1-D
        :func:`repro.launch.mesh.make_serving_mesh`) serves tensor-parallel:
        attention heads — the MLA latent rank for ``attention="mla"`` —
        shard across the mesh's ``"model"`` axis and the paged block pool
        partitions with them, so each device holds its heads' slice of every
        block (~1/N pool bytes per device; block tables and allocator
        metadata stay replicated/host-side and shard-agnostic). Params are
        placed once per mesh and the loop still runs ONE compiled step with
        the donated sharded carry. Head counts (or the latent rank) that do
        not divide the shard count raise up front
        (``serving.sharded.validate_serving_shards``); greedy outputs stay
        token-identical to single-device serving on the CPU (on a TPU the
        sharded program's logits agree within bf16 rounding, so tokens can
        part at near-ties) and the path composes with
        ``paged``/``prefix_share``/``speculative``/``kernel``.

        ``prefill_chunk=N`` bounds the prompt tokens prefilled per engine
        step: long prompts commit in N-token chunks INTERLEAVED with decode
        steps (in-flight slots keep emitting while the newcomer prefills),
        so one long prompt no longer spikes every other request's
        time-between-tokens. Dense/moe (incl. MLA; fp or int8 KV — the
        fake-quant prefill's per-position scales make quantized chunks
        byte-stable) chunk truly incrementally — each chunk is a
        ``prefill_tail`` against the chunks committed so far, and the result
        is bit-identical to whole prefill; SSM/hybrid recurrences are not
        chunk-resumable at exact bit parity (the SSD scan grid depends on
        the whole prompt), so those families ACCRUE the same N-token budget
        per step and run one whole prefill when it covers the prompt —
        identical interleaving bounds, trivially identical bits. Composes
        with every mode above; the compiled decode step is untouched
        (zero retraces).

        ``preemption=True`` (paged only) lets the scheduler swap out a
        low-priority victim when a strictly higher-class request is blocked
        on slots or pool blocks: registered prompt blocks are simply
        released (resume re-acquires them by content key, or re-prefills an
        evicted gap through the prefix-share path), private blocks and
        slot-resident stripes are host-copied, and the resumed stream —
        PRNG state included — continues bit-identical to an uninterrupted
        run. ``Request.priority``/``aging``/``hol_grace`` tune the admission
        order (see ``SlotScheduler``); per-class latency lands in
        ``ServeReport.class_latency``.
        """
        if options is not None and legacy:
            raise TypeError("pass either options=ServeOptions(...) or legacy "
                            "keyword arguments, not both")
        if options is None:
            # legacy kwarg surface: unknown names raise TypeError from the
            # dataclass ctor exactly like the old signature did; cross-field
            # validation happens in ServeOptions.__post_init__
            options = ServeOptions(**legacy)
            if legacy:
                _warn_legacy_serve_kwargs()
        opt = options
        slots, cache_len, policy = opt.slots, opt.cache_len, opt.policy
        report_cost, paged = opt.report_cost, opt.paged
        block_size, num_blocks = opt.block_size, opt.num_blocks
        prefix_share, speculative = opt.prefix_share, opt.speculative
        draft_k, draft, max_ngram = opt.draft_k, opt.draft, opt.max_ngram
        draft_model, draft_params = opt.draft_model, opt.draft_params
        kernel, mesh, shards = opt.kernel, opt.mesh, opt.shards
        prefill_chunk, preemption = opt.prefill_chunk, opt.preemption
        aging, hol_grace = opt.aging, opt.hol_grace
        smx_kind = opt.softmax_kind
        cfg = self._variant_model(smx_kind).cfg
        if cfg.family == "encdec":
            off = [n for n, v in (
                ("paged", paged), ("prefix_share", prefix_share),
                ("speculative", speculative),
                ("prefill_chunk", prefill_chunk is not None),
                ("kernel", kernel != "jnp"),
                ("mesh/shards", mesh is not None or shards is not None),
            ) if v]
            if off:
                raise NotImplementedError(
                    "encdec serving covers the contiguous single-device "
                    "executor (cross K/V is slot-resident in the cache "
                    f"pytree); unsupported option(s): {', '.join(off)}")
        if cfg.rope_type == "mrope" and (speculative or prefix_share):
            raise NotImplementedError(
                "mrope serving covers plain and paged decode; speculative "
                "verify and prefix sharing need scalar-position rope "
                "(Model.verify_step / prefill_tail)")
        reqs = list(requests)
        if not reqs:
            return ServeReport([], 0, 0.0, slots, cache_len or 0, None)
        enc_len = 0
        if cfg.family == "encdec":
            # cross-attention is mask-free (attn_cross), so every admitted
            # request must share ONE encoder frame geometry — padding a
            # shorter clip would change its attention rows vs eager
            shapes = {None if r.frames is None
                      else tuple(np.asarray(r.frames).shape) for r in reqs}
            if None in shapes or len(shapes) != 1:
                raise ValueError(
                    "encdec serving needs every request to carry encoder "
                    "frames of one shared [enc_len, d_model] shape "
                    f"(cross-attention is mask-free); got {sorted(shapes, key=str)}")
            enc_len = next(iter(shapes))[0]
        need = max(r.prompt_len + r.max_new for r in reqs)
        C = need if cache_len is None else cache_len
        if cfg.family == "hybrid":
            # prefill builds window-capacity rings; the slot buffers must match
            C = max(C, cfg.window)
        if shards is not None and mesh is None:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(shards)
        if mesh is not None:
            from repro.serving.sharded import validate_serving_mesh
            validate_serving_mesh(cfg, mesh)
            ex = self._mesh_exec(mesh, smx_kind)
            params, prefill = ex["params"], ex["prefill"]
            prefill_tail = ex["prefill_tail"]
        else:
            params, prefill = self.params, self._variant_prefill(smx_kind,
                                                                 tail=False)
            prefill_tail = self._variant_prefill(smx_kind, tail=True)
        serve_step = self._get_serve_step(kernel, mesh, smx_kind)
        meter_model = self._variant_model(smx_kind)
        alloc = None
        shareable = False
        if paged:
            C = -(-C // block_size) * block_size     # round up to block grid
            n_logical = C // block_size
            if num_blocks is None:
                num_blocks = _default_num_blocks(slots, n_logical,
                                                 prefix_share)
            alloc = BlockAllocator(num_blocks, block_size)
            # debug/test handle: pool bookkeeping of the most recent serve
            # (tests assert allocator-state invariants across cache dtypes)
            self._last_alloc = alloc
            need_max = max(alloc.blocks_needed(r.prompt_len, r.max_new)
                           for r in reqs)
            if num_blocks < need_max:
                raise ValueError(
                    f"num_blocks {num_blocks} cannot fit the largest "
                    f"request (worst case {need_max} blocks of "
                    f"{block_size})")
            # int8 KV shares too (PR 9 lifted the PR 4 exclusion): fake-quant
            # prefill + position-local scales make pool bytes replayable
            shareable = prefix_share and cfg.family in ("dense", "moe")
            sched = SlotScheduler(
                reqs, slots, C, policy=policy,
                admit_ok=lambda r: alloc.available() >= alloc.blocks_needed(
                    r.prompt_len, r.max_new),
                aging=aging, hol_grace=hol_grace)
            cache = kv_cache.paged_cache_zeros(cfg, slots, C, block_size,
                                               num_blocks)
        else:
            sched = SlotScheduler(reqs, slots, C, policy=policy,
                                  aging=aging, hol_grace=hol_grace)
            cache = kv_cache.cache_zeros(cfg, slots, C, enc_len=enc_len)
        # chunked prefill: dense/moe (incl. MLA, fp or int8 KV) chunk truly
        # incrementally (prefill_tail against the committed prefix, bit-
        # identical); recurrent families — and mrope, whose prefill_tail is
        # rejected — accrue the same budget and prefill whole once it covers
        # the prompt (see the docstring)
        chunkable = (prefill_chunk is not None
                     and cfg.family in ("dense", "moe")
                     and cfg.rope_type != "mrope")
        if mesh is not None:
            # place the zeroed cache on the serving layout up front — the
            # donated carry then keeps it there with zero relayouts
            from repro.serving.sharded import place_cache
            axes = (kv_cache.paged_cache_axes(cfg, slots, C, block_size,
                                              num_blocks) if paged
                    else kv_cache.serve_cache_axes(cfg, slots, C))
            cache = place_cache(cache, axes, ex["rules"], mesh)
        proposer = None
        spec_step = None
        if speculative:
            if self._sampler_kind is None:
                raise ValueError(
                    "speculative serving needs a registry sampler (the "
                    "verifier must know the target distribution); this "
                    "engine was built with a callable sampler")
            proposer = make_proposer(draft, draft_k, max_ngram=max_ngram,
                                     draft_model=draft_model,
                                     draft_params=draft_params)
            if getattr(proposer, "model", None) is not None and \
                    proposer.model.cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft model vocab {proposer.model.cfg.vocab} != "
                    f"target vocab {cfg.vocab}")
            proposer.begin(slots, C)
            spec_step = self._get_spec_step(draft_k, kernel, mesh, smx_kind)
        attr = telemetry.SlotCostAttributor() if report_cost else None
        geom = (block_size, num_blocks) if paged else None
        step_cost = (self._meter_serve_step(slots, C, geom, enc_len=enc_len,
                                            model=meter_model)
                     if report_cost and not speculative else None)
        verify_cost = (self._meter_serve_step(slots, C, geom, t=draft_k + 1,
                                              model=meter_model)
                       if report_cost and speculative else None)
        draft_cost = (proposer.meter_round()
                      if report_cost and speculative else None)
        slot_blocks: Dict[int, List[int]] = {}
        prefill_tok = shared_tok = 0
        shared_of: Dict[int, int] = {}
        tok = np.zeros((slots, 1), np.int32)
        pos = np.full((slots,), C, np.int32)      # parked: no write lands
        keys = np.zeros((slots, 2), np.uint32)
        done = np.ones((slots,), bool)

        spans = LoopSpans()   # the loop's clock, host phases and gap kinds
        first_at: Dict[int, float] = {}           # rid -> serve clock of TTFT
        results: Dict[int, RequestResult] = {}
        # chunked prefill: slot -> in-flight prompt-commit job, processed one
        # job-step per engine step (FIFO) so prefill work per step is bounded
        chunk_jobs: "OrderedDict[int, dict]" = OrderedDict()
        # preemption: rid -> host payload (copied blocks/stripes + PRNG key)
        swap_store: Dict[int, dict] = {}
        t, steps = 0.0, 0
        pf_this_step, max_pf = 0, 0

        def finish(slot: int) -> None:
            st = sched.release(slot)
            r = st.request
            gen = list(st.generated)
            if len(gen) < r.max_new:   # EOS early-exit: pad like eager rows
                gen += [int(self.pad_id)] * (r.max_new - len(gen))
            toks = np.concatenate([np.asarray(r.prompt, np.int32),
                                   np.asarray(gen, np.int32)])
            pos[slot] = C
            if alloc is not None:
                for b in slot_blocks.pop(slot, ()):
                    alloc.release_block(b)
            if proposer is not None:
                proposer.release(slot)
            ttft_s, tbt_s, latency_s = spans.timings(r.rid)
            results[r.rid] = RequestResult(
                rid=r.rid, tokens=toks, prompt_len=r.prompt_len,
                done=st.done, admitted_at=st.admitted_at, finished_at=t,
                latency_s=latency_s,
                cost=attr.report_for(r.rid) if attr else None,
                shared_prefix=shared_of.get(r.rid, 0),
                drafted=st.drafted, accepted=st.accepted,
                priority=r.priority, deadline=r.deadline,
                deadline_met=(None if r.deadline is None
                              else (t - r.arrival) <= r.deadline),
                first_token_at=first_at.pop(r.rid, st.admitted_at),
                ttft_s=ttft_s, tbt_s=tbt_s,
                preempts=st.preempts)

        def prompt_batch(req: Request, lo: int = 0, hi=None) -> dict:
            """Prefill input dict for prompt positions [lo, hi): tokens plus
            the family's extra stream — encoder frames (encdec, whole-prompt
            admissions only) or text-axis M-RoPE positions (a text-only
            serving trace walks all three streams along the token axis,
            matching the eager reference's ``extra_inputs``)."""
            b = {"tokens": jnp.asarray(req.prompt[None, lo:hi])}
            if cfg.family == "encdec":
                b["frames"] = jnp.asarray(req.frames)[None]
            elif cfg.rope_type == "mrope":
                n = (req.prompt_len if hi is None else hi) - lo
                b["positions"] = jnp.broadcast_to(
                    jnp.arange(lo, lo + n, dtype=jnp.int32)[None, None, :],
                    (3, 1, n))
            return b

        def paged_admit(req: Request) -> dict:
            """Reserve one request's paged residency: match + refcount the
            shared prefix, copy-on-write a partial boundary block, allocate
            the private blocks, build the table row. Prompt CONTENT lands
            later — whole (paged_commit once) or chunked (one commit per
            engine step) — against these same ids."""
            nonlocal cache
            bs = block_size
            P = req.prompt_len
            pkeys = prefix_keys(req.prompt, bs) if shareable else []
            shared = alloc.match_prefix(pkeys)
            # always leave >= 1 tail token: the admission-time first token is
            # sampled from the tail prefill's last-position logits
            s = min(len(shared) * bs, P - 1)
            keep = -(-s // bs)
            for b in shared[keep:]:
                alloc.release_block(b)
            shared = shared[:keep]
            cow = s > 0 and s % bs != 0
            if cow:
                # the boundary block is shared but position s (the forced
                # tail token) lands inside it: first divergent write -> copy
                old = shared[-1]
                fresh, copied = alloc.writable(old)
                assert copied, "boundary block was shared, writable must copy"
                cache = self._paged_copy(cache, jnp.int32(old),
                                         jnp.int32(fresh))
                shared[-1] = fresh
            ids = shared + [alloc.alloc() for _ in
                            range(alloc.blocks_needed(P, req.max_new)
                                  - len(shared))]
            row = np.full((C // bs,), alloc.num_blocks, np.int32)
            row[:len(ids)] = np.asarray(ids, np.int32)
            return {"ids": ids, "row": row, "pkeys": pkeys, "keep": keep,
                    "s": s, "cow": cow}

        def paged_register(adm: dict) -> None:
            """Publish the prompt's full blocks once their content is final
            (whole install, or a chunked prompt's last commit)."""
            for i, key in enumerate(adm["pkeys"]):
                if i < adm["keep"] and not (adm["cow"]
                                            and i == adm["keep"] - 1):
                    continue    # still the registered original we acquired
                alloc.register(key, adm["ids"][i])

        def paged_commit(slot: int, req: Request, adm: dict,
                         c0: int, c1: int):
            """Prefill prompt positions [c0, c1) — ``c0 == 0`` whole-prefix,
            else a tail against the committed/shared prefix — and scatter
            them through the slot's table row. Returns the piece's logits
            (the last piece's final position feeds first-token sampling)."""
            nonlocal cache, prefill_tok, pf_this_step
            bs = block_size
            id_arr = np.asarray(adm["ids"], np.int32)
            if c0 == 0:
                logits, slot_cache = prefill(params, prompt_batch(req, 0, c1),
                                             cache_len=C)
            else:
                kp = -(-c0 // bs)
                prefix = self._paged_prefix(cache, jnp.asarray(id_arr[:kp]),
                                            s=c0)
                logits, slot_cache = prefill_tail(
                    params, {"tokens": jnp.asarray(req.prompt[None, c0:c1])},
                    prefix, prefix_len=c0)
            wpos = np.arange(c0, c1)
            cache = self._paged_scatter(
                cache, slot_cache, jnp.int32(slot), jnp.asarray(adm["row"]),
                jnp.asarray(id_arr[wpos // bs]),
                jnp.asarray((wpos % bs).astype(np.int32)), t0=0, t1=c1 - c0)
            prefill_tok += c1 - c0
            pf_this_step += c1 - c0
            if attr is not None:
                if c0 == 0:
                    attr.record_request(req.rid, self._meter_prefill(
                        c1, C, model=meter_model))
                elif c0 == adm["s"]:
                    # first executed piece past a shared prefix: log the
                    # sharing savings once
                    attr.record_shared_prefill(
                        req.rid,
                        self._meter_prefill_tail(c0, c1 - c0,
                                                 model=meter_model),
                        self._meter_prefill(c0, C, model=meter_model), c0)
                else:
                    attr.record_request(
                        req.rid, self._meter_prefill_tail(c0, c1 - c0,
                                                          model=meter_model))
            return logits

        def contig_commit(slot: int, req: Request, c0: int, c1: int):
            """Contiguous-layout chunk commit: prefill [c0, c1) and write it
            into the slot's cache stripe (chunkable families only — every
            leaf is positional)."""
            nonlocal cache, prefill_tok, pf_this_step
            if c0 == 0:
                logits, slot_cache = prefill(
                    params, {"tokens": jnp.asarray(req.prompt[None, :c1])},
                    cache_len=C)
                if attr is not None:
                    attr.record_request(req.rid, self._meter_prefill(
                        c1, C, model=meter_model))
            else:
                prefix = self._slot_prefix(cache, jnp.int32(slot), s=c0)
                logits, slot_cache = prefill_tail(
                    params, {"tokens": jnp.asarray(req.prompt[None, c0:c1])},
                    prefix, prefix_len=c0)
                if attr is not None:
                    attr.record_request(
                        req.rid, self._meter_prefill_tail(c0, c1 - c0,
                                                          model=meter_model))
            cache = self._slot_scatter(cache, slot_cache, jnp.int32(slot),
                                       jnp.int32(c0), t0=0, t1=c1 - c0)
            prefill_tok += c1 - c0
            pf_this_step += c1 - c0
            return logits

        def activate(slot: int, req: Request, logits) -> None:
            """Sample the first token from the (last) prefill logits and turn
            the reserved slot into a live decode lane."""
            with spans.phase("first_token", rid=req.rid):
                if mesh is not None:
                    # detach admission logits from the mesh: the eager
                    # sampler should not dispatch an SPMD program per admit
                    logits = jnp.asarray(np.asarray(logits))
                k = jax.random.PRNGKey(req.seed)
                k, sub = jax.random.split(k)
                first = int(self.sample(logits[:, -1], sub)[0])
                done0 = self.eos_id is not None and first == self.eos_id
                if proposer is not None:
                    proposer.admit(slot, np.asarray(req.prompt, np.int32),
                                   first, req.prompt_len)
                sched.slots[slot].prefilling = False
                sched.install(slot, first, done0)
                tok[slot, 0] = first
                pos[slot] = req.prompt_len
                keys[slot] = np.asarray(k, np.uint32)
                done[slot] = done0
                first_at[req.rid] = t
                spans.activated("tail" if shared_of.get(req.rid, 0) > 0
                                else "full")
                spans.emitted(req.rid)
            if sched.slot_done(slot):
                finish(slot)

        def swap_out(slot: int) -> None:
            """Preempt one victim: split its blocks into re-acquirable-by-key
            (released — the prefix registry keeps them resident/evictable)
            vs host-copied (private content), release everything through the
            allocator, park the lane, and bank the request in the scheduler's
            swapped set with its PRNG state."""
            nonlocal cache
            st = sched.slots[slot]
            r = st.request
            # the engine's host arrays are authoritative for lane position —
            # sync it into the scheduler record the resume will restore
            st.pos = int(pos[slot])
            bs = block_size
            ids = slot_blocks.pop(slot)
            pk = prefix_keys(r.prompt, bs) if shareable else []
            nwritten = -(-int(st.pos) // bs)     # blocks with live positions
            nreg = 0
            while nreg < min(len(pk), len(ids)) and \
                    alloc.key_of(ids[nreg]) == pk[nreg]:
                nreg += 1
            copy_ids = np.asarray(ids[nreg:nwritten], np.int32)
            payload = jax.tree.map(np.asarray, self._swap_read(
                cache, jnp.int32(slot), jnp.asarray(copy_ids)))
            for b in ids:
                alloc.release_block(b)
            sched.preempt(slot, t)
            swap_store[r.rid] = {"payload": payload, "nreg": nreg,
                                 "nwritten": nwritten,
                                 "key": keys[slot].copy()}
            if proposer is not None:
                proposer.release(slot)
            pos[slot] = C
            done[slot] = True

        def resume(slot: int, req: Request) -> None:
            """Swap a preempted request back in: re-acquire registered prompt
            blocks by content key, re-prefill any evicted gap through the
            prefix-share path, restore the host-copied blocks/stripes, and
            rebuild the decode lane (token, position, PRNG key) exactly —
            the continued stream is bit-identical to an uninterrupted run."""
            nonlocal cache
            meta = swap_store.pop(req.rid)
            st = sched.slots[slot]        # restored by admit()
            bs = block_size
            nblocks = alloc.blocks_needed(req.prompt_len, req.max_new)
            pk = (prefix_keys(req.prompt, bs)[:meta["nreg"]]
                  if shareable else [])
            shared = alloc.match_prefix(pk)
            got = len(shared)
            ids = shared + [alloc.alloc() for _ in range(nblocks - got)]
            row = np.full((C // bs,), alloc.num_blocks, np.int32)
            row[:nblocks] = np.asarray(ids, np.int32)
            slot_blocks[slot] = ids
            copy_dst = np.asarray(ids[meta["nreg"]:meta["nwritten"]],
                                  np.int32)
            cache = self._swap_write(cache, meta["payload"], jnp.int32(slot),
                                     jnp.asarray(copy_dst), jnp.asarray(row))
            if got < meta["nreg"]:
                # registered blocks evicted while swapped: their positions
                # are pure prompt prefill — rebuild them bit-identically and
                # re-publish ("s": -1 keeps the sharing meter untouched)
                adm = {"ids": ids, "row": row, "s": -1}
                paged_commit(slot, req, adm, got * bs, meta["nreg"] * bs)
                for i in range(got, meta["nreg"]):
                    alloc.register(pk[i], ids[i])
            if proposer is not None:
                proposer.admit(slot, np.asarray(req.prompt, np.int32),
                               st.generated[0], req.prompt_len)
                if len(st.generated) > 1:
                    proposer.observe(slot, st.generated[1:])
            tok[slot, 0] = st.generated[-1]
            pos[slot] = st.pos
            keys[slot] = meta["key"]
            done[slot] = st.done

        def handle_admission(slot: int, req: Request) -> None:
            nonlocal cache, shared_tok, prefill_tok, pf_this_step
            if req.rid in swap_store:
                with spans.phase("resume", rid=req.rid):
                    resume(slot, req)
                return
            P = req.prompt_len
            if alloc is not None:
                adm = paged_admit(req)
                slot_blocks[slot] = adm["ids"]
                shared_of[req.rid] = adm["s"]
                shared_tok += adm["s"]
                if prefill_chunk is not None and \
                        pf_this_step + P - adm["s"] > prefill_chunk:
                    sched.slots[slot].prefilling = True
                    chunk_jobs[slot] = {
                        "kind": "chunk" if chunkable else "staged",
                        "req": req, "adm": adm, "committed": adm["s"],
                        "budget": 0}
                    return
                logits = paged_commit(slot, req, adm, adm["s"], P)
                paged_register(adm)
            else:
                if prefill_chunk is not None and \
                        pf_this_step + P > prefill_chunk:
                    sched.slots[slot].prefilling = True
                    chunk_jobs[slot] = {
                        "kind": "chunk" if chunkable else "staged",
                        "req": req, "adm": None, "committed": 0, "budget": 0}
                    return
                logits, slot_cache = prefill(params, prompt_batch(req),
                                             cache_len=C)
                cache = self._insert_slot(cache, slot_cache, jnp.int32(slot))
                prefill_tok += P
                pf_this_step += P
                if attr is not None:
                    attr.record_request(req.rid, self._meter_prefill(
                        P, C, enc_len=enc_len, model=meter_model))
            activate(slot, req, logits)

        def advance_chunks() -> None:
            """One engine step's worth of prompt-commit work: the OLDEST job
            advances by ``prefill_chunk`` tokens (true chunk) or accrues that
            budget (staged recurrent/quantized families, whole prefill once
            covered) — so admission never stalls decode by more than one
            bounded prefill piece per step."""
            nonlocal cache, prefill_tok, pf_this_step
            slot, job = next(iter(chunk_jobs.items()))
            req = job["req"]
            P = req.prompt_len
            if job["kind"] == "staged":
                job["budget"] += prefill_chunk
                if job["budget"] < P - job["committed"]:
                    return
                if alloc is not None:
                    logits = paged_commit(slot, req, job["adm"],
                                          job["committed"], P)
                    paged_register(job["adm"])
                else:
                    logits, slot_cache = prefill(
                        params, prompt_batch(req), cache_len=C)
                    cache = self._insert_slot(cache, slot_cache,
                                              jnp.int32(slot))
                    prefill_tok += P
                    pf_this_step += P
                    if attr is not None:
                        attr.record_request(req.rid, self._meter_prefill(
                            P, C, model=meter_model))
                del chunk_jobs[slot]
                activate(slot, req, logits)
                return
            c0 = job["committed"]
            c1 = min(c0 + prefill_chunk, P)
            if alloc is not None:
                logits = paged_commit(slot, req, job["adm"], c0, c1)
            else:
                logits = contig_commit(slot, req, c0, c1)
            job["committed"] = c1
            if c1 == P:
                if alloc is not None:
                    paged_register(job["adm"])
                del chunk_jobs[slot]
                activate(slot, req, logits)

        while sched.unfinished:
            spans.tick()
            sched.advance(t)
            pf_this_step = 0
            spans.queued(r.rid for r in sched.queue)
            while True:
                for slot, req in sched.admit(t):
                    with spans.phase("admission", rid=req.rid):
                        handle_admission(slot, req)
                if not preemption:
                    break
                victim = sched.preempt_victim(t)
                if victim is None:
                    break
                with spans.phase("swap_out",
                                 rid=sched.slots[victim].request.rid):
                    swap_out(victim)
            progressed = False
            if chunk_jobs and (pf_this_step == 0
                               or not sched.active_slots()):
                # one bounded prompt-commit piece per step — but never in a
                # step that already spent its admission prefill budget while
                # decode lanes are live (TBT protection); with no live lanes
                # the step is prefill-only and chunk work proceeds regardless
                advance_chunks()
                progressed = True
            active = sched.active_slots()
            if active and speculative:
                drafts = proposer.propose(active, tok, pos)
                cache, out_d, n_d, keys_d = spec_step(
                    params, cache, jnp.asarray(tok), jnp.asarray(drafts),
                    jnp.asarray(pos), jnp.asarray(keys))
                with spans.phase("step_sync", step=steps):
                    out_np = np.asarray(out_d)
                    n_np = np.asarray(n_d)
                    keys = np.array(keys_d)  # copy: host arrays stay writable
                with spans.phase("emit", step=steps):
                    now = spans.now()
                    if attr is not None:
                        rids = sched.active_requests()
                        attr.record_step(verify_cost, rids, kind="verify")
                        if draft_cost is not None:
                            attr.record_step(draft_cost, rids, kind="draft")
                    for slot in active:
                        st = sched.slots[slot]
                        r = st.request
                        n_emit = int(n_np[slot])
                        budget = r.max_new - len(st.generated)
                        # commit emissions host-side, truncating at EOS or
                        # the request budget — exactly where the
                        # non-speculative loop would have stopped stepping
                        # this slot
                        used = 0
                        for tk in out_np[slot, :n_emit]:
                            st.generated.append(int(tk))
                            spans.emitted(r.rid, now)
                            used += 1
                            if self.eos_id is not None and \
                                    int(tk) == self.eos_id:
                                st.done = True
                                done[slot] = True
                                break
                            if len(st.generated) >= r.max_new:
                                break
                        # draft accounting counts only slots that could have
                        # been committed (the budget cap is known up front)
                        # and were: acceptance_rate measures useful drafting,
                        # not verifier hits past the request's end
                        sched.record_draft(slot, min(draft_k, budget),
                                           min(used, n_emit - 1))
                        proposer.observe(slot, out_np[slot, :used])
                        tok[slot, 0] = st.generated[-1]
                        pos[slot] += n_emit
                        if sched.slot_done(slot):
                            finish(slot)
                steps += 1
                t += 1.0
            elif active:
                cache, toks_d, keys_d, done_d = serve_step(
                    params, cache, jnp.asarray(tok), jnp.asarray(pos),
                    jnp.asarray(keys), jnp.asarray(done))
                with spans.phase("step_sync", step=steps):
                    toks_np = np.asarray(toks_d)
                    keys = np.array(keys_d)  # copy: host arrays stay writable
                    done_np = np.array(done_d)
                with spans.phase("emit", step=steps):
                    now = spans.now()
                    if attr is not None:
                        attr.record_step(step_cost, sched.active_requests())
                    for slot in active:
                        st = sched.slots[slot]
                        st.generated.append(int(toks_np[slot]))
                        spans.emitted(st.request.rid, now)
                        if self.eos_id is not None:
                            st.done = bool(done_np[slot])
                            done[slot] = done_np[slot]
                        tok[slot, 0] = int(toks_np[slot])
                        pos[slot] += 1
                        if sched.slot_done(slot):
                            finish(slot)
                steps += 1
                t += 1.0
            elif progressed:
                t += 1.0    # chunk-only step: prompt commits still take time
            else:
                nxt = sched.next_arrival()
                if nxt is None:
                    assert not sched.swapped, "swapped requests unreachable"
                    break   # defensive: nothing active, queued, or pending
                t = max(t + 1.0, float(nxt))
            max_pf = max(max_pf, pf_this_step)

        ordered = [results[r.rid] for r in sorted(reqs, key=lambda q: q.rid)]
        wall_s, host_s = spans.close()
        return ServeReport(
            results=ordered, steps=steps,
            wall_s=wall_s, slots=slots, cache_len=C,
            cost=attr.total() if attr else None,
            paged=paged, block_size=block_size if paged else 0,
            prefill_tokens=prefill_tok, shared_prefill_tokens=shared_tok,
            cow_copies=alloc.cow_copies if alloc else 0,
            evictions=alloc.evictions if alloc else 0,
            speculative=speculative, draft_k=draft_k if speculative else 0,
            drafted_tokens=sum(r.drafted for r in ordered),
            accepted_tokens=sum(r.accepted for r in ordered),
            cost_draft=attr.total_kind("draft") if attr and speculative
            else None,
            cost_verify=attr.total_kind("verify") if attr and speculative
            else None,
            prefill_chunk=prefill_chunk or 0, max_prefill_per_step=max_pf,
            preemptions=sched.preemptions, resumes=sched.resumes,
            leaked_blocks=(alloc.num_blocks - alloc.available())
            if alloc else 0,
            class_latency=telemetry.class_latency_summary(ordered),
            host_s=host_s, gap_kinds=dict(spans.gap_kinds))


def make_serve_step(model: Model, kind: str, max_new: int = 64,
                    sampler: str = "greedy", eos_id: Optional[int] = None):
    """The function the dry-run lowers. ``decode``: one token for the whole
    batch against a fixed-size cache. ``generate``: the whole-generation
    fused scan (prefill logits in, all ``max_new`` tokens out) — lower it
    with ``donate_argnums=(1,)`` to keep the cache in place."""
    if kind == "decode":
        def serve_step(params, cache, token, cache_pos, positions=None):
            batch = {"token": token}
            if positions is not None:
                batch["positions"] = positions
            return model.decode_step(params, cache, batch, cache_pos)
        return serve_step
    if kind == "generate":
        return make_generate_fn(model, make_sampler(sampler), max_new, eos_id)
    if kind == "prefill":
        def prefill_step(params, batch, cache_len):
            return model.prefill(params, batch, cache_len=cache_len)
        return prefill_step
    raise ValueError(kind)
