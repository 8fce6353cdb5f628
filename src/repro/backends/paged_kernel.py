"""``int_pallas_paged``: the integer softmax riding the fused paged-decode
attention kernel.

``apply`` is byte-for-byte the ``int_jax`` body — prefill and any
non-paged-decode site that resolves this backend lowers to exactly the same
jnp program, so swapping a model's spec to ``int_pallas_paged`` changes
nothing outside paged decode. What the name DOES change is the paged decode
and verify paths in ``models/attention.py`` / ``models/mla.py``: they probe
``fused_paged_decode`` and, when set, route through
``kernels/paged_attention`` — the block-table-walking Pallas kernel —
instead of gather-then-attend. Metering is inherited from
``IntBackendBase``: the Table-II AP cost of the softmax work is identical
on either substrate (same Alg.-1 body over the same score rows), so cost
reports stay comparable across ``int_jax`` / ``int_pallas`` /
``int_pallas_paged`` runs.
"""

from __future__ import annotations

from repro.backends.jax_backends import IntBackendBase
from repro.backends.registry import register_backend
from repro.core.int_softmax import int_softmax


@register_backend("int_pallas_paged")
class IntPallasPagedBackend(IntBackendBase):
    """Integer softmax whose paged-decode sites run the fused block-table
    kernel (one VMEM residency per (slot, block of kv heads); no dense
    gather)."""

    name = "int_pallas_paged"
    fused_paged_decode = True
    differentiable = False  # decode-only substrate; train with int_jax/ste

    def apply(self, scores, mask=None, axis: int = -1):
        return int_softmax(scores, cfg=self.cfg, mask=mask, axis=axis)
