"""Array utilities for the device check engine.

A small, jittable building block: the prefix-sum "arena" expansion that
turns per-task child counts into flat child slots (the batched replacement
for goroutine fan-out in
`internal/check/checkgroup/concurrent_checkgroup.go:66-138`).

Everything works on int32 arrays and static shapes so XLA can tile it.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def arena_assign(counts: jax.Array, arena_size: int) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Flatten per-task child counts into arena slots.

    ``counts``: int32[T] children requested per task (0 for inactive tasks).

    Returns ``(offsets, total, parent, ordinal)`` where ``offsets[t]`` is the
    exclusive prefix sum (the arena base of task t's children), ``total`` the
    scalar total, and for each arena slot ``j < arena_size``: ``parent[j]`` =
    the task index owning the slot and ``ordinal[j]`` its child ordinal;
    slots >= total get parent == -1.
    """
    counts = counts.astype(jnp.int32)
    offsets = jnp.cumsum(counts) - counts
    total = jnp.sum(counts)
    j = jnp.arange(arena_size, dtype=jnp.int32)
    # parent[j] = last t with offsets[t] <= j (only among counts>0 rows).
    # Occupied ranges have strictly increasing starts, so scattering each
    # task index at its range start and forward-filling with a running max
    # recovers the owner of every slot — linear scatter+scan instead of the
    # argsort+searchsorted this used to do (the sort was the level cost).
    t = jnp.arange(counts.shape[0], dtype=jnp.int32)
    mark = jnp.full((arena_size,), -1, jnp.int32).at[
        jnp.where(counts > 0, offsets, arena_size)
    ].max(t, mode="drop")
    parent = jax.lax.associative_scan(jnp.maximum, mark)
    parent = jnp.where(j < total, parent, -1)
    safe_parent = jnp.clip(parent, 0, counts.shape[0] - 1)
    ordinal = jnp.where(parent >= 0, j - offsets[safe_parent], 0).astype(jnp.int32)
    return offsets.astype(jnp.int32), total.astype(jnp.int32), parent, ordinal
