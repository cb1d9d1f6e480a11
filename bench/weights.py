"""Weights made from the seed, leaf by leaf, the same for the program
and the reference.

Layer ``g``'s leaf ``i`` (in ``model.layer_leaves`` order) is drawn from
``fold_in(fold_in(key(seed), g), i)``; the shared leaves (embedding,
head, final norm) use ``g = SHARED``.  A leaf is rounded to the type the
configuration serves it in (bfloat16): the program holds it so, and the
reference computes in float32 from the same rounded values.

The rounding is made explicit (``reduce_precision``) before the cast: a
compiler that may keep excess precision is free to drop a cast pair
``float32 -> bfloat16 -> float32`` inside one program, and on a TPU it
does, so a leaf that is cast back up in the program that drew it would
otherwise keep its unrounded float32 value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SHARED = 1 << 20


def seed32(seed: int) -> int:
    """Any whole-number seed (also one over 32 bits) as a uint32."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def base_key(seed):
    """``seed`` is a uint32 (a Python int or a traced scalar)."""
    return jax.random.key(jnp.asarray(seed, jnp.uint32))


def draw(key, shape, init) -> jnp.ndarray:
    kind = init[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * init[1]
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    raise ValueError(f"unknown init {init!r}")


def leaf(key0, g: int, i: int, shape, init, dtype) -> jnp.ndarray:
    """Leaf ``i`` of layer ``g`` (``SHARED`` for the shared leaves), in
    its served type."""
    k = jax.random.fold_in(jax.random.fold_in(key0, g), i)
    x, dt = draw(k, shape, init), jnp.dtype(dtype)
    if dt != jnp.float32:
        fi = jnp.finfo(dt)
        x = jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                     mantissa_bits=fi.nmant)
    return x.astype(dt)


#: random sign vectors per leaf that the check projects gradients on
SKETCH = 16


def signs(key0, g: int, i: int, k, shape) -> jnp.ndarray:
    """The ``k``-th random +-1 tensor of leaf ``i`` of layer ``g``."""
    kk = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key0, g), i), (1 << 16) + k)
    return jax.random.rademacher(kk, shape, jnp.float32)


def sketch(key0, g: int, i: int, a) -> jnp.ndarray:
    """``[SKETCH]`` projections of a float32 leaf on its sign tensors:
    ``|sketch(a) - sketch(b)| / |sketch(b)|`` estimates ``|a - b| / |b|``
    without holding both tensors in one place."""
    return jax.lax.map(lambda k: jnp.sum(signs(key0, g, i, k, a.shape) * a),
                       jnp.arange(SKETCH))
