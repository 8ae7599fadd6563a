"""Pallas kernel parity tests (run in interpreter mode on CPU; the same
kernel compiles for real TPU)."""

import jax.numpy as jnp
import numpy as np
import pytest


def make_inputs(seed, n=512):
    rng = np.random.default_rng(seed)
    idle = np.tile([8000.0, 64e9, 8.0], (n, 1))
    idle[:, 2] -= rng.integers(0, 9, n)
    rel = np.zeros((n, 3))
    rel[:, 2] = rng.integers(0, 3, n)
    labels = rng.integers(-1, 3, (n, 2)).astype(np.int32)
    taints = np.where(rng.random((n, 1)) < 0.2, 0, -1).astype(np.int32)
    room = rng.integers(0, 111, n).astype(np.float64)
    alloc = np.tile([8000.0, 64e9, 8.0], (n, 1))
    req = np.array([1000.0, 1e9, float(rng.integers(1, 4))])
    sel = np.array([rng.integers(-1, 3), -1], np.int32)
    tol = np.array([0], np.int32) if rng.random() < 0.5 else \
        np.array([-1], np.int32)
    return (jnp.asarray(req), jnp.asarray(sel), jnp.asarray(tol),
            jnp.asarray(idle), jnp.asarray(rel), jnp.asarray(labels),
            jnp.asarray(taints), jnp.asarray(room), jnp.asarray(alloc))


class TestGroupStepPallas:
    """The fused per-group-step row kernel vs the fused-jnp row at f32:
    keys and capacities must agree exactly (same formulas, same
    precision) — the interpret-mode guardian for the TPU rung."""

    def _args(self, seed, n=512, releasing=True):
        rng = np.random.default_rng(seed)
        req, sel, tol, idle, rel, labels, taints, room, alloc = \
            make_inputs(seed, n)
        if not releasing:
            rel = jnp.zeros_like(rel)
        f32 = jnp.float32
        return (alloc.astype(f32), idle.astype(f32), rel.astype(f32),
                labels, taints, room.astype(f32), req.astype(f32), sel,
                tol, rng)

    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("releasing_empty", [False, True])
    def test_matches_fused_jnp_row(self, seed, releasing_empty):
        from kai_scheduler_tpu.ops.allocate_grouped import _fused_row
        from kai_scheduler_tpu.ops.pallas_kernels import group_step_pallas
        (alloc, idle, rel, labels, taints, room, req, sel, tol,
         rng) = self._args(seed, releasing=not releasing_empty)
        extra = jnp.asarray(
            np.where(rng.random(idle.shape[0]) < 0.3, 10000.0,
                     0.0).astype(np.float32))
        mask = jnp.asarray(rng.random(idle.shape[0]) < 0.85)
        pipe = not releasing_empty
        for extra_row, mask_row in ((None, None), (extra, mask)):
            args = (alloc, idle, None if releasing_empty else rel,
                    labels, taints, room, req, sel, tol, extra_row,
                    mask_row)
            kw = dict(gpu_strategy=0, cpu_strategy=0,
                      allow_pipeline=True, pipeline_only=False,
                      releasing_empty=releasing_empty, pipe_items=pipe)
            jref = _fused_row(*args, **kw)
            pal = group_step_pallas(*args, **kw)
            names = ("key_now", "key_pipe", "cap_now", "cap_tot")
            for name, a, b in zip(names, jref[:4], pal[:4]):
                if a is None:
                    assert b is None
                    continue
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"{name} seed={seed} "
                            f"rel_empty={releasing_empty} "
                            f"extra={extra_row is not None}")

    def test_multi_tile_minmax_accumulation(self):
        """The SMEM min/max fold must span tiles: a binpack spread that
        straddles the tile boundary would read wrong on a per-tile-only
        minmax."""
        from kai_scheduler_tpu.ops.allocate_grouped import _fused_row
        from kai_scheduler_tpu.ops.pallas_kernels import (NODE_TILE,
                                                          group_step_pallas)
        n = NODE_TILE * 2
        rng = np.random.default_rng(11)
        alloc = np.tile([8000.0, 64e9, 8.0], (n, 1)).astype(np.float32)
        idle = alloc.copy()
        # All the emptiest nodes in tile 0, the fullest in tile 1.
        idle[:NODE_TILE, 2] = 8.0
        idle[NODE_TILE:, 2] = rng.integers(1, 4, NODE_TILE)
        args = (jnp.asarray(alloc), jnp.asarray(idle), None,
                jnp.full((n, 1), -1, jnp.int32),
                jnp.full((n, 1), -1, jnp.int32),
                jnp.full(n, 110.0, jnp.float32),
                jnp.asarray(np.array([100.0, 1e8, 1.0], np.float32)),
                jnp.full(1, -1, jnp.int32), jnp.full(1, -1, jnp.int32),
                None, None)
        kw = dict(gpu_strategy=0, cpu_strategy=0, allow_pipeline=True,
                  pipeline_only=False, releasing_empty=True,
                  pipe_items=False)
        jref = _fused_row(*args, **kw)
        pal = group_step_pallas(*args, **kw)
        np.testing.assert_array_equal(np.asarray(jref[0]),
                                      np.asarray(pal[0]))
        np.testing.assert_array_equal(np.asarray(jref[2]),
                                      np.asarray(pal[2]))
