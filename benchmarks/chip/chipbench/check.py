"""Whether what the timed window served is correct.

Once the window has closed, a sample of the requests it finished, drawn from
the run seed and always holding the longest one, is run through the plain
reference (``references/<name>.py``) in float32 at ``highest`` matmul
precision with the integer softmax the configuration states, each over its
prompt and served tokens. The window served greedy tokens, so each served
token should be the reference's best, up to the rounding of the program's
bfloat16 arithmetic (which moves some scores across the integer softmax's
grid): the numbers compared are the widest and the mean gap by which a
served token's reference logit lies below the reference's best at its
position.

The control is the same reference with every matrix product taken in fp8
(e4m3, operands scaled per tensor to the format's range), the step below the
bfloat16 the configuration serves in: at each position of the same tokens,
the gap of the token that the fp8 forward puts first.
"""

from __future__ import annotations

import functools
import importlib.util
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def mm_highest(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def mm_fp8(spec, a, b):
    return mm_highest(spec, _fp8(a), _fp8(b))


def load_reference(bench_dir: str, name: str):
    path = os.path.join(bench_dir, "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_ref_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(served: Dict[int, int], total: Dict[int, int], seed: int,
           target_tokens: int) -> List[int]:
    """Request ids to compare: the longest request (prompt + served), then
    others in an order drawn from ``seed``, until ``target_tokens`` served
    tokens are covered."""
    longest = max(total, key=lambda r: (total[r], r))
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [seed % 2 ** 64, 2])))
    rest = [r for r in sorted(total) if r != longest]
    order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for r in order:
        out.append(r)
        n += served[r]
        if n >= target_tokens:
            break
    return out


class Reference:
    """The reference forward of one configuration over one weight tree,
    compiled once per padded length (a multiple of ``pad``, itself a
    multiple of the attention's row ``block``)."""

    def __init__(self, module, model_cfg: dict, weights, n_out: int,
                 block: int = 256, pad: int = 512):
        self.w, self.n_out, self.pad = weights, n_out, pad
        self._fns = {
            mode: jax.jit(functools.partial(module.forward, cfg=model_cfg,
                                            n_out=n_out, mm=mm, block=block))
            for mode, mm in (("reference", mm_highest), ("control", mm_fp8))}

    def logits(self, prompt: np.ndarray, served: np.ndarray,
               mode: str = "reference") -> np.ndarray:
        """[len(served), vocab] logits at the positions that predicted each
        served token."""
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        start = len(prompt) - 1
        tp = -(-(start + self.n_out) // self.pad) * self.pad
        toks = np.zeros(tp, np.int32)
        toks[:len(seq)] = seq
        out = self._fns[mode](self.w, tokens=jnp.asarray(toks),
                              start=jnp.int32(start))
        return np.asarray(out)[:len(served)]


def gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the best."""
    return ref.max(-1) - ref[np.arange(len(chosen)), chosen]


def compare(reference: Reference, prompts: Dict[int, np.ndarray],
            served: Dict[int, np.ndarray], rids: List[int],
            control: bool = False) -> Dict[str, float]:
    """Widest and mean logit gap over the sampled requests' served tokens
    (and the control's, when asked)."""
    prog, ctrl = [], []
    for r in rids:
        ref = reference.logits(prompts[r], served[r])
        prog.append(gaps(ref, served[r]))
        if control:
            low = reference.logits(prompts[r], served[r], mode="control")
            ctrl.append(gaps(ref, low.argmax(-1)))
    p = np.concatenate(prog)
    out = {"logit_gap": float(p.max()), "mean_logit_gap": float(p.mean()),
           "checked_tokens": int(p.size)}
    if control:
        c = np.concatenate(ctrl)
        out.update(control_logit_gap=float(c.max()),
                   control_mean_logit_gap=float(c.mean()))
    return out
