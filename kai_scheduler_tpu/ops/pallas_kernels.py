"""Pallas TPU kernel for the grouped allocation's hot row pass.

``group_step_pallas`` is the fused per-GROUP-STEP row pass the grouped
fill-plan kernel (ops/allocate_grouped, fused_mode="pallas") runs inside
its scan.  One ``pallas_call`` with a (phase, node-tile) grid sweeps the
resident node state twice, entirely in VMEM per tile: phase 0 accumulates
the bin-pack min/max over the task's valid nodes into SMEM scratch;
phase 1 emits the fill keys (sign-flipped f32 score bitcasts, ready for
the radix-descent fill) and the idle/total whole-task capacities.  That
is TWO HBM reads of the node tensors per group step and zero materialized
[N]-wide intermediates, versus the ~dozen reduction-separated passes of
the unfused composition.

Semantics match ops.predicates.feasibility_caps_row +
ops.scoring.score_row_selected at f32 (parity-tested in interpret mode);
the host wrapper's mode resolution picks the fused-jnp path on non-TPU
backends or when the node bucket doesn't tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NODE_TILE = 512
NEG = -1e18


def _where_s(flag, if_true, if_false):
    """``where`` on a SCALAR flag over [1, TILE] rows.  The flag rides as
    an f32 0/1 scalar and is compared in vector land: Mosaic legalizes
    neither a broadcast of an i1 scalar nor a select between i1 vectors
    (the refusal the first v5e compile of this kernel hit)."""
    shape = jnp.shape(if_true) or jnp.shape(if_false)
    return jnp.where(jnp.full(shape, flag, jnp.float32) > 0.5,
                     if_true, if_false)


def _tile_row_terms(req_ref, sel_ref, tol_ref, idle_ref, rel_ref,
                    labels_ref, taints_ref, room, mask,
                    releasing_empty: bool):
    """Shared per-tile feasibility + capacity terms (f32, every axis but
    the node axis unrolled).

    Mirrors predicates.feasibility_caps_row on one VMEM-resident tile in
    the lane-dense layout: node state refs are [X, TILE] (node axis on
    the lanes), req/sel/tol are SMEM scalars.  Returns (fit_now,
    fit_future, cap_now_f, cap_tot_f), each [1, TILE]."""
    from .predicates import EPS, NO_LABEL, NO_TAINT
    hard = room >= 1.0
    one, zero = jnp.int32(1), jnp.int32(0)
    for l in range(labels_ref.shape[0]):
        want = sel_ref[l]
        wild = jnp.where(want == NO_LABEL, one, zero)
        match = jnp.where(labels_ref[l:l + 1, :] == want, one, zero)
        hard = hard & ((match + wild) > 0)
    for t in range(taints_ref.shape[0]):
        taint = taints_ref[t:t + 1, :]
        tolerated = taint == NO_TAINT
        for k in range(tol_ref.shape[0]):
            tolerated = tolerated | (taint == tol_ref[k])
        hard = hard & tolerated
    if mask is not None:
        hard = hard & (mask > 0.5)

    fits_idle = hard
    fits_total = hard
    cap_now_f = None
    cap_tot_f = None
    for r in range(idle_ref.shape[0]):
        rq = req_ref[r]
        safe = jnp.where(rq > 0, rq, 1.0)
        # +inf where the task does not ask for this resource, else +0.
        unasked = jnp.where(rq > 0, 0.0, jnp.inf)
        col = idle_ref[r:r + 1, :]
        fits_idle = fits_idle & (rq <= col + EPS)
        ratio = jnp.floor(col / safe) + unasked
        cap_now_f = ratio if cap_now_f is None \
            else jnp.minimum(cap_now_f, ratio)
        if not releasing_empty:
            tot = col + rel_ref[r:r + 1, :]
            fits_total = fits_total & (rq <= tot + EPS)
            ratio_t = jnp.floor(tot / safe) + unasked
            cap_tot_f = ratio_t if cap_tot_f is None \
                else jnp.minimum(cap_tot_f, ratio_t)
    if releasing_empty:
        return fits_idle, fits_idle, cap_now_f, cap_now_f
    return fits_idle, fits_total, cap_now_f, cap_tot_f


def _f32_key(score):
    """Order-preserving key for an f32 score, as the int32 bit pattern of
    ops.allocate_grouped._score_keys' u32 key (the caller bitcasts)."""
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    sign = jnp.int32(-2 ** 31)
    return jnp.where(bits < 0, bits ^ jnp.int32(-1), bits | sign)


def group_step_pallas(node_allocatable, idle, rel, node_labels,
                      node_taints, room, req, sel, tol, extra_row,
                      mask_row, gpu_strategy: int, cpu_strategy: int,
                      allow_pipeline: bool, pipeline_only: bool,
                      releasing_empty: bool, pipe_items: bool,
                      interpret: bool | None = None):
    """Fused per-group-step row pass over node tiles: returns
    (key_now, key_pipe | None, cap_now, cap_tot | None, levels, utype)
    exactly like ops.allocate_grouped._fused_row, computed at f32.

    Layout: every node tensor enters transposed, [X, N] with the node
    axis on the lanes, and every output is a [1, N] row — the blocks are
    lane-dense (X, TILE) slabs; req/sel/tol ride in SMEM as scalars.
    Grid (2, n_tiles): phase 0 reduces the selected resource column's
    valid min/max into SMEM scratch; phase 1 recomputes the tile terms
    from VMEM and writes keys + capacities.  ``interpret`` defaults to
    True off-TPU (the test suite's parity path); on TPU the kernel
    compiles to Mosaic."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from ..api.resources import RES_CPU, RES_GPU
    from .scoring import (AVAILABILITY, MAX_HIGH_DENSITY, RESOURCE_TYPE,
                          SPREAD)

    n = idle.shape[0]
    tile = min(NODE_TILE, n)
    if n % tile != 0:
        raise ValueError(f"node count {n} must tile by {tile}")
    n_tiles = n // tile
    r = idle.shape[1]
    L = node_labels.shape[1]
    tt = node_taints.shape[1]
    have_rel = not releasing_empty
    have_extra = extra_row is not None
    have_mask = mask_row is not None

    def kernel(*refs):
        it = iter(refs)
        req_ref, sel_ref, tol_ref = next(it), next(it), next(it)
        alloc_ref, idle_ref = next(it), next(it)
        rel_ref = next(it) if have_rel else None
        labels_ref, taints_ref, room_ref = next(it), next(it), next(it)
        extra_ref = next(it) if have_extra else None
        mask_ref = next(it) if have_mask else None
        key_now_ref = next(it)
        cap_now_ref = next(it)
        key_pipe_ref = next(it) if pipe_items else None
        cap_tot_ref = next(it) if pipe_items else None
        minmax = next(it)  # SMEM scratch [2]

        phase = pl.program_id(0)
        j = pl.program_id(1)

        roomv = room_ref[...]
        fit_now, fit_future, cap_now_f, cap_tot_f = _tile_row_terms(
            req_ref, sel_ref, tol_ref, idle_ref, rel_ref, labels_ref,
            taints_ref, roomv, mask_ref[...] if have_mask else None,
            releasing_empty)
        if pipeline_only:
            fit_now = jnp.zeros_like(fit_now)
        feasible = fit_now | (fit_future
                              if (allow_pipeline or pipeline_only)
                              else jnp.zeros_like(fit_future))

        gpu_job = jnp.where(req_ref[RES_GPU] > 0.0, 1.0, 0.0)
        free = _where_s(gpu_job, idle_ref[RES_GPU:RES_GPU + 1, :],
                        idle_ref[RES_CPU:RES_CPU + 1, :])
        axcap = _where_s(gpu_job, alloc_ref[RES_GPU:RES_GPU + 1, :],
                         alloc_ref[RES_CPU:RES_CPU + 1, :])
        has_res = axcap > 0.0
        valid = feasible & has_res

        @pl.when(phase == 0)
        def _accumulate():
            tile_min = jnp.min(jnp.where(valid, free, jnp.inf))
            tile_max = jnp.max(jnp.where(valid, free, -jnp.inf))

            @pl.when(j == 0)
            def _init():
                minmax[0] = tile_min
                minmax[1] = tile_max

            @pl.when(j != 0)
            def _fold():
                minmax[0] = jnp.minimum(minmax[0], tile_min)
                minmax[1] = jnp.maximum(minmax[1], tile_max)

        @pl.when(phase == 1)
        def _emit():
            if gpu_strategy == SPREAD:  # == cpu_strategy (wrapper gate)
                placement = jnp.where(
                    has_res, free / jnp.where(has_res, axcap, 1.0), 0.0)
            else:
                min_free = minmax[0]
                max_free = minmax[1]
                span = max_free - min_free
                flat = span <= 0.0
                placement = MAX_HIGH_DENSITY * (
                    1.0 - (free - min_free) / jnp.where(flat, 1.0, span))
                placement = _where_s(jnp.where(flat, 1.0, 0.0),
                                     MAX_HIGH_DENSITY, placement)
                placement = jnp.where(has_res, placement, 0.0)
            node_has_gpu = jnp.where(
                alloc_ref[RES_GPU:RES_GPU + 1, :] > 0.0, 1.0, 0.0)
            score = placement \
                + jnp.where(node_has_gpu == gpu_job, RESOURCE_TYPE, 0.0) \
                + jnp.where(fit_now, AVAILABILITY, 0.0)
            if have_extra:
                score = score + extra_ref[...]
            score = jnp.where(feasible, score, NEG)
            key_now_ref[...] = _f32_key(score)
            cap_now_ref[...] = jnp.where(
                fit_now, jnp.minimum(cap_now_f, roomv), 0.0)
            if pipe_items:
                score_pipe = score - jnp.where(fit_now, AVAILABILITY, 0.0)
                key_pipe_ref[...] = _f32_key(score_pipe)
                cap_tot_ref[...] = jnp.where(
                    feasible, jnp.minimum(cap_tot_f, roomv), 0.0)

    def node_block(rows):
        return pl.BlockSpec((rows, tile), lambda p, j: (0, j))

    # Phase 0 writes no output: its steps all map to output block 0,
    # which stays resident until phase 1 has filled it, so every block is
    # flushed exactly once and with phase-1 values.
    out_block = pl.BlockSpec((1, tile), lambda p, j: (0, j * p))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    in_specs = [smem, smem, smem, node_block(r), node_block(r)]
    args = [req.astype(jnp.float32), sel.astype(jnp.int32),
            tol.astype(jnp.int32),
            node_allocatable.astype(jnp.float32).T,
            idle.astype(jnp.float32).T]
    if have_rel:
        in_specs.append(node_block(r))
        args.append(rel.astype(jnp.float32).T)
    in_specs += [node_block(L), node_block(tt), node_block(1)]
    args += [node_labels.astype(jnp.int32).T,
             node_taints.astype(jnp.int32).T,
             room.astype(jnp.float32)[None, :]]
    if have_extra:
        in_specs.append(node_block(1))
        args.append(extra_row.astype(jnp.float32)[None, :])
    if have_mask:
        in_specs.append(node_block(1))
        args.append(mask_row.astype(jnp.float32)[None, :])

    n_outs = 4 if pipe_items else 2
    out_shape = [jax.ShapeDtypeStruct((1, n), jnp.int32),
                 jax.ShapeDtypeStruct((1, n), jnp.float32)] * (n_outs // 2)

    outs = pl.pallas_call(
        kernel,
        grid=(2, n_tiles),
        in_specs=in_specs,
        out_specs=[out_block] * n_outs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((2,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*args)

    def as_key(row):
        return jax.lax.bitcast_convert_type(row[0], jnp.uint32)

    key_now = as_key(outs[0])
    cap_now = outs[1][0]
    key_pipe = as_key(outs[2]) if pipe_items else None
    cap_tot = outs[3][0] if pipe_items else None
    return key_now, key_pipe, cap_now, cap_tot, 4, jnp.uint32
