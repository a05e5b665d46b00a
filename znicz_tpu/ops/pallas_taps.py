"""What the bodies of the kernels that walk rows of a sequence share:
the delta rule's (``pallas_delta``: the chunk algebra and ``qkv_prep``)
and the gated short convolution's (``pallas_short_conv``).  A causal
depthwise convolution is the same few lines in both — u_{t−s} by a
sublane rotation of a stretch that starts 8 rows early, the taps' sum,
the 8-row reads, the select past an array's end — and so are a 0/1 mask
to multiply by and a column's sum.  Plain functions over values and
refs inside a ``pallas_call``'s body: nothing here is a kernel, a grid
or a ``BlockSpec``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: a vreg: lanes × sublanes of f32
LANES, SUBLANES = 128, 8


def ones_where(condition):
    """0/1 in f32: a mask to multiply by.  (A ``jnp.where`` or an
    integer ``//`` in a kernel's body is a nested call that the host
    traces and lowers once per use, a third of a process's start for
    these kernels; a product is one equation.)"""
    return condition.astype(jnp.float32)


def column_sums(x):
    """Σ over a column's entries, (C, ·) → (1, ·)."""
    return jnp.sum(x, axis=0, keepdims=True)


def delayed(ext, width: int, rows: int):
    """u_{t−s} for s < ``width`` over ``rows`` rows, from ``ext`` — a
    stretch of u that starts 8 rows before the first of them — by a
    sublane rotation each (s < 8: nothing wraps into the rows kept)."""
    return [ext[SUBLANES:SUBLANES + rows]] + [
        pltpu.roll(ext, s, 0)[SUBLANES:SUBLANES + rows]
        for s in range(1, width)]


def taps_sum(shifted, taps):
    """c_t = Σ_j taps[j] · u_{t−J+1+j} (``delta_net.causal_conv``)
    from :func:`delayed`'s list."""
    width = len(taps)
    c = shifted[width - 1] * taps[0]
    for j in range(1, width):
        c = c + shifted[width - 1 - j] * taps[j]
    return c


def eight_rows(ref, start):
    """8 rows of a block from the sublane-aligned ``start``, f32."""
    return ref[pl.ds(pl.multiple_of(start, SUBLANES), SUBLANES),
               :].astype(jnp.float32)


def unless(seen, x):
    """``x`` where ``seen`` (a column of booleans), zeros elsewhere —
    a select, not a product: a block past an array's end holds
    anything."""
    return jax.lax.select(jnp.broadcast_to(seen, x.shape), x,
                          jnp.zeros_like(x))
