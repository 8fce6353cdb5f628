"""Seeded random weights, made on the device in one jitted call.

The harness makes the weights, in the parameter layout the program's
``Model`` expects, and hands the same arrays to the program and to the plain
reference: the reference takes nothing that the program made. Scales follow
the usual initialisation, so activations and logits are of ordinary size:
linear maps N(0, 1/d_in), embeddings N(0, 0.02^2), norm gains 1 + N(0, 0.1^2)
(so that a path that drops a gain is seen), biases 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int):
    """A PRNG key from any whole number (run seeds may exceed 32 bits)."""
    data = np.random.SeedSequence(seed % 2 ** 64).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data, jnp.uint32),
                                    impl="threefry2x32")


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _init(shapes, key):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, sds), k in zip(leaves, keys):
        name = _leaf_name(path)
        shape, dtype = sds.shape, sds.dtype
        if name.endswith("scale"):
            v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name.endswith("/b") or name.endswith("bias"):
            v = jnp.zeros(shape, jnp.float32)
        elif name.startswith("embed"):
            v = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            v = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
        out.append(v.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def make(model, seed: int, layout=None):
    """Parameters for ``model`` (a ``repro.models.model.Model``) from
    ``seed``: on the first device, or, given ``layout`` (a function that
    places a parameter tree, such as ``repro.serving.sharded.shard_params``
    on a serving mesh), made in that placement, so that no device ever holds
    more than its share. The values do not depend on the placement."""
    shapes = jax.eval_shape(lambda k: model.init_split(k)[0],
                            jax.random.PRNGKey(0))
    place = layout or (lambda p: p)
    return jax.jit(lambda k: place(_init(shapes, k)))(key_of(seed))
