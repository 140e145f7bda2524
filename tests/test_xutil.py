"""Unit tests for the device-engine array utilities."""

import jax.numpy as jnp

from ketotpu.engine.xutil import arena_assign


def test_arena_assign():
    counts = jnp.array([2, 0, 3, 0, 1], jnp.int32)
    offsets, total, parent, ordinal = arena_assign(counts, 8)
    assert offsets.tolist() == [0, 2, 2, 5, 5]
    assert int(total) == 6
    assert parent.tolist() == [0, 0, 2, 2, 2, 4, -1, -1]
    assert ordinal.tolist() == [0, 1, 0, 1, 2, 0, 0, 0]


def test_arena_assign_all_zero():
    offsets, total, parent, ordinal = arena_assign(jnp.zeros((4,), jnp.int32), 4)
    assert int(total) == 0
    assert parent.tolist() == [-1, -1, -1, -1]
