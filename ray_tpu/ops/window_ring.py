"""A sliding-window layer's K/V rows as a RING a slot.

What a window layer pins for a slot is bounded by its window whatever the
context: ``ring [window layers, slots, ring blocks, block rows, lanes]``
(one for K, one for V; the two may differ in lanes), position ``p`` of a
slot in block ``(p // block rows) mod ring blocks`` at row ``p mod block
rows``. A ring is the window and a block more, so that the blocks one decode
step attends are distinct entries of it. The decode kernel
(``ops/paged_attention.py:paged_attention(window=)``) walks the rings as a
pool ``[window layers, slots * ring blocks, ...]`` (the two major dimensions
merged: the same bytes) through a table that is each slot's own blocks, read
modulo its width. Both sizes are read off the arrays. Two families keep such
rings (``models/afmoe.py``, ``models/mimo_v2.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def write(rings, wl: int, slot, positions, keep, k, v):
    """Rows ``k`` / ``v`` [N, lanes] at ``positions`` [N] of slots ``slot``
    [N] into window layer ``wl`` of ``rings`` (K ring, V ring); rows not
    ``keep`` are dropped (a parked slot, a pad position, a position a later
    one of the same call overwrites)."""
    k_ring, v_ring = rings
    slots, blocks, rb = k_ring.shape[1:4]
    # An index past the slots is out of bounds: ``mode="drop"`` skips it.
    where = (wl, jnp.where(keep, slot, slots), (positions // rb) % blocks,
             positions % rb)
    with jax.named_scope("window_ring_write"):
        return (k_ring.at[where].set(k, mode="drop"),
                v_ring.at[where].set(v, mode="drop"))


def as_blocks(ring):
    """[window layers, slots, blocks, rows, lanes] -> the kernel's pool
    ``[window layers, slots * blocks, rows, lanes]``."""
    n, s, r = ring.shape[:3]
    return ring.reshape((n, s * r) + ring.shape[3:])


def slot_tables(ring):
    """``[slots, blocks]`` int32: each slot's table over :func:`as_blocks`,
    its own blocks in the ring's order."""
    s, r = ring.shape[1:3]
    return (jnp.arange(s)[:, None] * r
            + jnp.arange(r)[None, :]).astype(jnp.int32)
