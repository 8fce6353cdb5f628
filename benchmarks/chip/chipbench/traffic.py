"""One general generator for every traffic mix.

A mix is a JSON file under ``traffic/``: sessions that each share one
prefix (a document; length 0 for chat) and ask it a few times, each ask a
prompt of its own and an answer budget. Every length and arrival comes from
the file's ``shape_seed``, so every run seed serves the same set of sizes and
arrivals; the run seed draws only the token ids. Arrivals are in engine
steps, the serve loop's clock (``repro.serving.scheduler.Request.arrival``):
the trace is a fixed step-indexed replay, not a wall-clock open loop.

The mix's ``serve`` block gives the engine's batch (``slots``) and the
pool's size (``pool_blocks``, the HBM a deployment gives it: fewer than
every slot's worst case, so admission waits for blocks when long requests
pile up, as it would in service). Any other key is a
``repro.serving.ServeOptions`` field, over the benchmark's own settings
(``SERVE_DEFAULTS``: the paged pool of 16-token blocks, prefix sharing on,
the Pallas decode kernel); ``cache_len`` and ``num_blocks`` come from the
mix, and the serving mesh from the cell's chips. ``cache_len`` is derived
from the longest prompt plus the longest answer the FILE allows, not from
the drawn trace, so warm-up and every window share one compiled decode step.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np

KEYS = ("why", "sessions_per_s", "initial_sessions", "session_every_steps",
        "asks_per_session",
        "ask_every_steps", "prefix_len", "prompt_len", "answer_len", "serve",
        "shape_seed")
SERVE_REQUIRED = ("slots", "pool_blocks")
SERVE_DEFAULTS = {"block_size": 16, "prefix_share": True, "kernel": "pallas"}
# set by the harness, from the mix's lengths and the cell's chips
SERVE_DERIVED = ("cache_len", "num_blocks", "paged", "mesh", "shards")


@dataclasses.dataclass(frozen=True)
class Ask:
    """One request of the replay, before its token ids are drawn."""

    rid: int
    session: int
    arrival: float        # engine steps
    prefix_len: int       # shared part (the session's document)
    prompt_len: int       # the ask's own part, after the prefix
    max_new: int


def _choice(rng, spec: dict, n: int) -> np.ndarray:
    vals = np.asarray(spec["values"], np.int64)
    w = np.asarray(spec.get("weights", [1] * len(vals)), np.float64)
    return vals[rng.choice(len(vals), size=n, p=w / w.sum())]


def validate(mix: dict) -> None:
    missing = [k for k in KEYS if k not in mix]
    extra = [k for k in mix if k not in KEYS]
    if missing or extra:
        raise ValueError(f"traffic mix keys: missing {missing}, unknown {extra}")
    serve = mix["serve"]
    missing = [k for k in SERVE_REQUIRED if k not in serve]
    derived = [k for k in serve if k in SERVE_DERIVED]
    if missing or derived:
        raise ValueError(f"traffic 'serve' block: missing {missing}; "
                         f"{derived} are derived, not set")
    for k in ("asks_per_session", "prefix_len", "prompt_len"):
        if not mix[k]["values"] or min(mix[k]["values"]) < 0:
            raise ValueError(f"traffic {k}: values must be non-empty, >= 0")
    if min(mix["prompt_len"]["values"]) < 1:
        raise ValueError("traffic prompt_len values must be >= 1")
    a = mix["answer_len"]
    if not 1 <= a["min"] <= a["max"]:
        raise ValueError(f"traffic answer_len needs 1 <= min <= max, got {a}")


def shape(mix: dict, seconds: float) -> List[Ask]:
    """The replay's sizes and arrivals: ``ceil(sessions_per_s * seconds)``
    sessions; the first ``initial_sessions`` start one per step from step 0
    (the backlog of a server already in its steady state), the rest at
    exponential spacings in engine steps, as do the asks of a session.
    Independent of the run seed."""
    validate(mix)
    rng = np.random.Generator(np.random.PCG64(int(mix["shape_seed"])))
    n = max(1, math.ceil(mix["sessions_per_s"] * seconds))
    k = min(int(mix["initial_sessions"]), n)
    starts = np.concatenate([
        np.arange(k, dtype=np.float64),
        k + np.cumsum(rng.exponential(mix["session_every_steps"], n - k))])
    starts -= starts[0]
    n_asks = _choice(rng, mix["asks_per_session"], n)
    prefix = _choice(rng, mix["prefix_len"], n)
    rows: List[Tuple[float, int, int]] = []
    for s in range(n):
        gaps = rng.exponential(mix["ask_every_steps"], int(n_asks[s]))
        gaps[0] = 0.0
        for a in np.cumsum(gaps):
            rows.append((float(np.floor(starts[s] + a)), s, int(prefix[s])))
    rows.sort(key=lambda r: (r[0], r[1]))
    plen = _choice(rng, mix["prompt_len"], len(rows))
    ans = mix["answer_len"]
    if ans.get("log", False):     # log-uniform: short answers more often
        new = np.floor(np.exp(rng.uniform(np.log(ans["min"]),
                                          np.log(ans["max"] + 1), len(rows))))
    else:
        new = rng.integers(ans["min"], ans["max"] + 1, len(rows))
    return [Ask(rid=i, session=s, arrival=t, prefix_len=p,
                prompt_len=int(plen[i]), max_new=int(new[i]))
            for i, (t, s, p) in enumerate(rows)]


def serve_settings(mix: dict) -> dict:
    """The mix's ``serve`` block over ``SERVE_DEFAULTS``, less
    ``pool_blocks`` (see ``geometry``)."""
    validate(mix)
    out = dict(SERVE_DEFAULTS, **mix["serve"])
    del out["pool_blocks"]
    return out


def geometry(mix: dict) -> Dict[str, int]:
    """Serving geometry fixed by the mix file: the longest prompt plus the
    longest answer, rounded up to whole blocks, and the pool's blocks, which
    must hold the longest request twice over (once live, once as cached
    prefix) when prefixes are shared."""
    serve = serve_settings(mix)
    pool = int(mix["serve"]["pool_blocks"])
    bs = int(serve["block_size"])
    need = (max(mix["prefix_len"]["values"]) + max(mix["prompt_len"]["values"])
            + int(mix["answer_len"]["max"]))
    cache_len = -(-need // bs) * bs
    n_logical = cache_len // bs
    least = n_logical * (2 if serve["prefix_share"] else 1)
    if pool < least:
        raise ValueError(f"pool_blocks {pool} < {least}, the blocks of the "
                         f"longest request the mix allows")
    return {"cache_len": cache_len, "num_blocks": pool}


def tokens(asks: List[Ask], vocab: int, seed: int) -> List[np.ndarray]:
    """Prompt token ids for each ask: the session's document (shared by its
    asks) followed by the ask's own tokens, drawn from the run seed."""
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [seed % 2 ** 64, 1])))
    docs: Dict[int, np.ndarray] = {}
    out = []
    for a in asks:
        if a.session not in docs:
            docs[a.session] = rng.integers(0, vocab, a.prefix_len,
                                           dtype=np.int64).astype(np.int32)
        own = rng.integers(0, vocab, a.prompt_len,
                           dtype=np.int64).astype(np.int32)
        out.append(np.concatenate([docs[a.session], own]))
    return out


def warm_asks(mix: dict, first: Ask) -> List[Ask]:
    """Requests, served in one call before the window, that run every
    program the window can run. The first admission of a serve call
    scatters into a freshly made pool and the later ones into a pool a
    program has written, which the compiled scatter tells apart: so the
    warm-up opens with the shape of the window's ``first`` request, then
    prefills every prefix + prompt length whole and, where the mix shares
    prefixes, asks each resident document every prompt length again (a tail
    prefill). Two answer tokens each: enough to run the decode step and the
    first-token sampler."""
    prompts = sorted(set(mix["prompt_len"]["values"]))
    shapes = [(first.prefix_len, first.prompt_len)] + [
        (p, q) for p in sorted(set(mix["prefix_len"]["values"]))
        for q in prompts]
    asks = [Ask(i, i, float(i), p, q, 2) for i, (p, q) in enumerate(shapes)]
    if serve_settings(mix)["prefix_share"]:
        for lead in [a for a in asks[1:] if a.prefix_len and
                     a.prompt_len == prompts[0]]:
            for q in prompts:
                asks.append(Ask(len(asks), lead.session, float(len(asks)),
                                lead.prefix_len, q, 2))
    return asks
