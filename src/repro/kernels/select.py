"""Core selection on sorted core-free rows — *the* core-selection primitive
of the jnp schedule evaluator.

List scheduling needs, per task step, the time at which ``c`` cores of the
assigned node are simultaneously free: the ``c``-th smallest entry of that
node's core-free row, followed by replacing the ``c`` smallest entries with
the task's finish time ``f``.  The evaluator only ever reads a row through
that ``c``-th smallest value and only ever writes it by that replacement, so
it depends on the *multiset* of a row's entries, not their order.  Keeping
each row in ascending order takes both operations from O(CMAX²) compares to
O(CMAX log CMAX) lane operations, with no sort, no gather and no scatter
inside the scan:

* the ``c``-th smallest is ``row[c-1]``, read by a masked sum over the lanes;
* with ``s[j] = row[j+c]`` (``+inf`` past the end) and ``f >= row[c-1]``,
  the new ascending row is ``max(min(s, f), row)``: ``s[j]`` while it is
  below ``f``, then ``c`` copies of ``f``, then ``row[j]`` once that is at
  least ``f``.  The shift by ``c`` goes one digit of ``c``
  (:data:`DIGIT_BITS` bits) a stage, each stage a select among static lane
  shifts.

The values written are the ones a stable sort gives, so results match the
numpy oracle (and the Pallas kernel, which keeps the comparison-rank rule
``rank[m] = #{m' : row[m'] < row[m] or (row[m'] == row[m] and m' < m)}``
inline) bit for bit.  Both functions broadcast over leading dims: ``c`` and
``f`` carry the row's leading shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: bits of ``c`` the shifter takes per stage; XLA:TPU fuses a stage's selects
DIGIT_BITS = 3


def kth_sorted(row: jax.Array, c) -> jax.Array:
    """``c``-th smallest (1-indexed) of the ascending ``row [..., C]``.

    ``1 <= c <= C``; a masked sum of one value and zeros is that value.
    """
    lanes = jnp.arange(row.shape[-1], dtype=jnp.int32)
    c = jnp.asarray(c, jnp.int32)[..., None]
    return jnp.sum(jnp.where(lanes == c - 1, row, 0.0), axis=-1)


def _shift_left(x: jax.Array, s: int) -> jax.Array:
    """``x[..., j + s]`` at lane ``j``, ``+inf`` past the end.

    A pad with a negative low edge: XLA:TPU fuses it into its consumer,
    where a slice at a lane offset is a copy of its own."""
    fill = jnp.array(jnp.inf, x.dtype)
    return jax.lax.pad(x, fill, [(0, 0, 0)] * (x.ndim - 1) + [(-s, s, 0)])


def insert_sorted(row: jax.Array, c, f) -> jax.Array:
    """The ascending ``row [..., C]`` with its ``c`` smallest entries replaced
    by ``f >= row[c-1]``, still ascending."""
    width = row.shape[-1]
    c = jnp.asarray(c, jnp.int32)[..., None]
    f = jnp.asarray(f, row.dtype)[..., None]
    # row[j + c] at lane j (+inf past the end), one digit of c a stage
    shifted = row
    for low in range(0, width.bit_length(), DIGIT_BITS):
        digit = (c >> low) & ((1 << DIGIT_BITS) - 1)
        out = shifted
        for d in range(1, 1 << DIGIT_BITS):
            if d << low > width:
                break
            out = jnp.where(digit == d, _shift_left(shifted, d << low), out)
        shifted = out
    return jnp.maximum(jnp.minimum(shifted, f), row)
