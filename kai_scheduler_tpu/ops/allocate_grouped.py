"""Grouped gang allocation: scan over task GROUPS, not tasks.

The exact kernel (ops/allocate.py) pays a fixed while-loop step cost per
task (~50us/step on TPU, dominating cycle latency: 2048 tasks ~ 100ms).
Real gangs are overwhelmingly runs of IDENTICAL tasks (same request,
selector, tolerations) — the same observation behind the reference's
scheduling-signature representors (job_info.go:547,
minimal_job_comparison.go).  This kernel scores once per identical-task
run, computes an analytic *fill plan*, bulk-updates node state, and emits
the plan as at most ``max_group`` compact (node, count, pipelined)
segments — so the scan length is the number of GROUPS, cutting step count
by the mean gang size.

Equivalence to the sequential greedy (tested against the exact kernel):
- under bin-pack, greedy fills the best-scoring node to capacity before
  moving on, and filling one node never reorders the rest (their free
  amounts are untouched; relative bin-pack order between two untouched
  nodes depends only on their free amounts, whatever the min/max span
  does), so the greedy sequence equals "sort by initial score, fill in
  order";
- each node contributes TWO fill items — an idle-capacity item keyed by
  its full score (availability included) and a releasing-capacity item
  keyed by score minus the availability boost — and ONE fill runs over
  the interleaved 2N items.  This reproduces the exact kernel's
  interleaving of tiers: a topology/nominated-boosted pipeline candidate
  (extra >= 10000 > availability 100) correctly beats an unboosted
  fit-now node, while within one extra level every fit-now item still
  beats every pipeline item.  A node's releasing item can only be taken
  after its idle item (strictly smaller key, same node), so the static
  capacity split (floor over idle vs floor over idle+releasing minus the
  former) is exact;
- per-node capacity = floor(min_r free_r / req_r) bounded by pod room;
- gang failure (demand exceeds total capacity) rolls the job back at the
  next job boundary, exactly like the per-task kernel.

Spread strategy round-robins as nodes fill and must use the exact kernel:
true of this fill, whose placements claim idle, the quantity spread scores;
the scenario prescreen's pipeline-only runs claim none, and land by either
strategy's key through this module's fill (ops/scenario_batch.py).

The per-step row pass has two rungs (docs/DESIGN.md §3.2b): the
TPU-Pallas node-tile row kernel and the fused-jnp single-pass row, both
feeding the masked-sum radix-descent fill.  Both are bit-identical in
placements to the exact per-task kernel (tests/test_fused_parity.py,
tools/kernel_parity.py); the wrapper resolves the rung from the backend
and the node bucket and counts it in ``allocate_fused_taken_total``.
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.tracing import TRACER
from .allocate import NEG, AllocationResult
from .predicates import feasibility_caps_row
from .scoring import AVAILABILITY, BINPACK, score_row, score_row_selected

# Fused-path selection (docs/DESIGN.md fused-kernel section): ``auto``
# resolves TPU-Pallas or fused-jnp from the backend and the node bucket;
# the two rung names are what a caller may ask for outright.
FUSED_MODES = ("auto", "pallas", "jnp")

# Digit width (bits) of the fused fill's radix descent.  Each level costs
# one in-prefix mask pass plus (2^W - 1) masked-sum reductions that XLA
# multi-output-fuses over one read of the keys; W=2 balances level count
# (16 for u32) against per-level reduction fan-out on both CPU and TPU.
SELECT_DIGIT_BITS = 2


@contextlib.contextmanager
def fused_dispatch_span(**attrs):
    """Cycle-thread ``allocate_fused`` span around a guarded grouped
    dispatch: yields, then stamps the guard verdict (fallback/timeout/
    breaker — the contract every kernel-kind span carries).  The rung
    (mode/groups/nodes/releasing_empty) is stamped onto it by
    ``allocate_grouped`` itself, where it is resolved: the cycle's trace
    follows the dispatch onto the guard's worker thread.  One definition
    for the session fast path and the bulk action, so the span contract
    cannot drift one-sided."""
    from ..utils.deviceguard import device_guard
    guard = device_guard()
    fb0, to0 = guard.fallback_calls, guard.timeouts
    with TRACER.span("allocate_fused", kind="kernel", **attrs) as sp:
        yield
        sp.set(fallback=guard.fallback_calls > fb0,
               timed_out=guard.timeouts > to0,
               breaker=guard.breaker.state)


def group_tasks(task_req: np.ndarray, task_job: np.ndarray,
                task_selector: np.ndarray, task_tolerations: np.ndarray,
                task_mergeable: np.ndarray | None = None):
    """Host-side prep: run-length groups over identical adjacent tasks.

    ``task_mergeable`` ([T] bool): tasks whose jobs place INDEPENDENTLY
    (single-task chunks with trivial gang semantics) — identical adjacent
    mergeable tasks group together ACROSS job boundaries, collapsing e.g.
    a burst of 20k identical one-pod jobs into one scan step.

    Returns (group_of_task [T], group_req [G,R], group_sel [G,L],
    group_tol [G,Tl], group_count [G], group_job [G], group_indep [G]).
    """
    t = task_req.shape[0]
    if t == 0:
        return (np.zeros(0, np.int32), np.zeros((0, task_req.shape[1])),
                np.zeros((0, task_selector.shape[1]), np.int32),
                np.zeros((0, task_tolerations.shape[1]), np.int32),
                np.zeros(0), np.zeros(0, np.int32), np.zeros(0, bool))
    if task_mergeable is None:
        task_mergeable = np.zeros(t, bool)
    change = np.zeros(t, bool)
    change[0] = True
    job_break = task_job[1:] != task_job[:-1]
    job_break &= ~(task_mergeable[1:] & task_mergeable[:-1])
    change[1:] = (
        job_break
        | (task_req[1:] != task_req[:-1]).any(axis=1)
        | (task_selector[1:] != task_selector[:-1]).any(axis=1)
        | (task_tolerations[1:] != task_tolerations[:-1]).any(axis=1))
    group_of_task = (np.cumsum(change) - 1).astype(np.int32)
    starts = np.flatnonzero(change)
    counts = np.diff(np.append(starts, t)).astype(np.float64)
    return (group_of_task, task_req[starts], task_selector[starts],
            task_tolerations[starts], counts,
            task_job[starts].astype(np.int32), task_mergeable[starts])


def _compact(take, key, max_group: int):
    """Gather the nonzero fill segments into [max_group] slots in
    ascending node-index order (score ordering is applied AFTER the scan,
    as one batched sort over all groups — see _order_segments).

    Slot s holds the s-th node with a nonzero take, found by binary
    search over the running nonzero count.  This is gather-only: the
    scatter formulation (.at[slot].set over the full node axis) lowered
    to per-element stores and dominated large-cluster cycle latency
    (~1.2ms per call at 98k nodes), and a per-step argsort would sit on
    the sequential scan's critical path."""
    flag = take > 0
    csum = jnp.cumsum(flag.astype(jnp.int32))
    total = csum[-1]
    nodes = jnp.searchsorted(
        csum, jnp.arange(1, max_group + 1, dtype=jnp.int32)).astype(
        jnp.int32)
    valid = jnp.arange(max_group) < jnp.minimum(total, max_group)
    nodes = jnp.where(valid, nodes, -1)
    counts = jnp.where(valid, take[jnp.clip(nodes, 0)],
                       jnp.zeros((), take.dtype))
    seg_key = jnp.where(valid, key[jnp.clip(nodes, 0)],
                        jnp.zeros((), key.dtype))
    return nodes, counts, seg_key


def _order_segments(seg_nodes, seg_counts, seg_pipe, seg_keys):
    """One batched sort over [G, K]: within each group, segments order
    descending by score key (availability folded in, so fit-now items of
    a tier precede its pipeline items and boosted pipeline items precede
    unboosted fit-now ones) with the ascending-item-index tie-break (the
    input order is ascending interleaved item index and the sort is
    stable), empty slots last — reproducing the exact kernel's placement
    sequence.  Batched across groups, this runs once per kernel call
    instead of once per scan step."""
    empty = jnp.where(seg_counts > 0, jnp.uint32(0), jnp.uint32(1))
    _, _, seg_nodes, seg_counts, seg_pipe = jax.lax.sort(
        (empty, ~seg_keys, seg_nodes, seg_counts,
         seg_pipe.astype(jnp.uint32)),
        dimension=-1, num_keys=2, is_stable=True)
    return seg_nodes, seg_counts, seg_pipe > 0


def _score_keys(score, force_f32: bool = False):
    """Order-preserving unsigned-integer keys for float scores: key(a) >
    key(b) iff a > b.  (levels, utype) size the radix select below.

    On TPU the float64 path downcasts to float32 first: XLA's x64-rewrite
    pass cannot lower a u64 bitcast-convert on TPU (crashes at compile),
    and score ORDER at f32 precision is what the hardware natively
    supports — CPU runs (the x64 parity suite) keep the exact u64 path.

    ``force_f32`` SIMULATES the TPU downcast on any backend: the
    precision-split property suite (tests/test_score_precision.py) pins
    it to prove f32 keys only ever COLLAPSE f64 ties (downcast is
    monotone), never invert an ordering — the tier-1 guardian for what
    otherwise only a run on the chip can compare.
    """
    # kailint: disable=KAI001 — force_f32 mirrors a static_argname flag
    if not force_f32 and score.dtype == jnp.float64 \
            and jax.default_backend() != "tpu":
        bits = jax.lax.bitcast_convert_type(score, jnp.uint64)
        key = jnp.where(bits >> jnp.uint64(63) == 1, ~bits,
                        bits | jnp.uint64(1 << 63))
        return key, 8, jnp.uint64
    bits = jax.lax.bitcast_convert_type(score.astype(jnp.float32),
                                        jnp.uint32)
    key = jnp.where(bits >> jnp.uint32(31) == 1, ~bits,
                    bits | jnp.uint32(1 << 31))
    return key, 4, jnp.uint32


def _fill_by_score_descent(key, levels, utype, cap, count):
    """Exact greedy fill WITHOUT sorting: distribute ``count`` units over
    items in descending-key order (ascending index among ties), each item
    bounded by ``cap``.

    The fill is monotone in score, so it is fully described by a threshold
    key: items strictly above it take their whole capacity, items at it
    split the remainder in index order.  The threshold is found by a
    radix descent: per W-bit level the threshold digit falls out of 2^W
    masked capacity sums — XLA multi-output-fuses them over a single read
    of (key, cap) — so the whole select is O(items x levels) with no
    scatter, no sort, no top_k, no materialized one-hot.

    Accumulation stays in ``cap.dtype``.  In f32 a digit's capacity sum
    can exceed 2^24 at large shapes — e.g. 98k nodes with per-node caps
    clipped to the gang count — so the sums themselves are not guaranteed
    exact there.  The threshold decision stays correct because ``need <=
    count`` keeps the compared region (cumulative capacity up to the
    threshold digit vs the remaining need) within the exactly-
    representable range: the select only reads the sums where the running
    total is still below ``need``.  Every per-digit sum is computed FRESH
    from the current in-prefix mask (never derived by subtracting a
    carried total, which would drag early >2^24-scale f32 rounding error
    into the deep levels where the in-prefix set has shrunk back to exact
    range).
    """
    w = SELECT_DIGIT_BITS
    n_bits = levels * 8
    while n_bits % w:
        w -= 1
    n_levels = n_bits // w
    mask = utype((1 << w) - 1)

    def level_body(level, state):
        # A lax loop, not an unrolled Python one: unrolling 16-32 levels
        # of scalar select machinery ballooned XLA:CPU compile time by
        # >30s at even trivial shapes; the rolled form compiles in
        # milliseconds and the per-level loop overhead is noise next to
        # the masked-sum reductions.
        prefix, above = state
        shift = (jnp.asarray(n_bits, utype)
                 - utype(w) * (level.astype(utype) + utype(1)))
        cur = key >> shift
        # Level 0: cur >> w == 0 == prefix, so every key is in-prefix —
        # no special case (both shifts stay < the key width).
        capw = jnp.where((cur >> utype(w)) == prefix, cap,
                         jnp.zeros((), cap.dtype))
        dig = cur & mask
        h = [jnp.sum(jnp.where(dig == utype(d), capw,
                               jnp.zeros((), cap.dtype)))
             for d in range(1 << w)]
        # ge[d] = capacity(digit >= d); threshold digit d* is the unique
        # crossing gt(d) < need <= ge(d) (first match; fall to 0 when
        # capacity is short: everything ends up full-taken, clipped by
        # cap).
        ge = [None] * (1 << w)
        acc = jnp.zeros((), cap.dtype)
        for d in reversed(range(1 << w)):
            acc = acc + h[d]
            ge[d] = acc
        need = count - above
        d_star = jnp.zeros((), utype)
        gt_sel = ge[0] - h[0]
        found = jnp.asarray(False)
        for d in range(1 << w):
            gt = ge[d] - h[d]
            c = (gt < need) & (need <= ge[d]) & ~found
            d_star = jnp.where(c, utype(d), d_star)
            gt_sel = jnp.where(c, gt, gt_sel)
            found = found | c
        d_star = jnp.where(found, d_star, utype(0))
        gt_sel = jnp.where(found, gt_sel, ge[0] - h[0])
        return ((prefix << utype(w)) | d_star, above + gt_sel)

    prefix, above = jax.lax.fori_loop(
        0, n_levels, level_body,
        (jnp.zeros((), utype), jnp.zeros((), cap.dtype)))
    take_full = jnp.where(key > prefix, cap, 0.0)
    eqcap = jnp.where(key == prefix, cap, 0.0)
    rem = jnp.maximum(count - above, 0.0)
    pref = jnp.cumsum(eqcap)
    take_eq = jnp.clip(rem - (pref - eqcap), 0.0, eqcap)
    # count <= 0 (gated/fully-satisfied): the no-crossing fallback above
    # would otherwise full-take everything.
    return jnp.where(count > 0, take_full + take_eq, 0.0)


def _fused_row(node_allocatable, idle, rel, node_labels, node_taints,
               room, req, sel, tol, extra_row, mask_row,
               gpu_strategy: int, cpu_strategy: int,
               allow_pipeline: bool, pipeline_only: bool,
               releasing_empty: bool, pipe_items: bool,
               f32_keys: bool = False):
    """One fused pass over the node state for one group step:
    (key_now, key_pipe | None, cap_now, cap_rel | None, levels, utype).

    Composes the unrolled feasibility+capacity helper
    (predicates.feasibility_caps_row) with the column-selected scorer
    (scoring.score_row_selected) so the whole row is one elementwise DAG
    plus the two binpack min/max reductions — no [N]-wide intermediate
    crosses a fusion boundary more than once.  Formula-identical to the
    exact kernel's feasibility_row + score_row composition.
    """
    fit_now, fit_future, cap_now_f, cap_tot_f = feasibility_caps_row(
        idle, None if releasing_empty else rel,
        node_labels, node_taints, room, req, sel, tol)
    if mask_row is not None:
        fit_now = fit_now & mask_row
        fit_future = fit_future & mask_row
    # The flag params mirror the kernel's static_argnames (the jitted
    # caller pins them); they are Python bools/ints at trace time.
    if pipeline_only:  # kailint: disable=KAI001
        fit_now = jnp.zeros_like(fit_now)
    feasible = fit_now | (fit_future if (allow_pipeline or pipeline_only)
                          else jnp.zeros_like(fit_future))
    if gpu_strategy == cpu_strategy:  # kailint: disable=KAI001
        score = score_row_selected(node_allocatable, idle, req, feasible,
                                   fit_now, gpu_strategy, cpu_strategy)
    else:  # mixed strategies: keep the two-axis canonical form
        score = score_row(node_allocatable, idle, req, feasible, fit_now,
                          gpu_strategy, cpu_strategy)
    if extra_row is not None:
        score = score + extra_row
    score = jnp.where(feasible, score, NEG)
    key_now, levels, utype = _score_keys(score, f32_keys)

    cap_now = jnp.where(fit_now, jnp.minimum(cap_now_f, room), 0.0)
    cap_tot = jnp.where(feasible, jnp.minimum(cap_tot_f, room), 0.0)
    if not pipe_items:  # kailint: disable=KAI001
        return key_now, None, cap_now, None, levels, utype
    score_pipe = score - jnp.where(fit_now, AVAILABILITY, 0.0)
    key_pipe, _, _ = _score_keys(score_pipe, f32_keys)
    return key_now, key_pipe, cap_now, cap_tot, levels, utype


@functools.partial(jax.jit,
                   static_argnames=("max_group", "gpu_strategy",
                                    "cpu_strategy", "allow_pipeline",
                                    "pipeline_only", "single_group_jobs",
                                    "fused_mode", "releasing_empty",
                                    "f32_keys"))
def allocate_groups_kernel(node_allocatable, node_idle, node_releasing,
                           node_labels, node_taints, node_pod_room,
                           group_req, group_sel, group_tol, group_count,
                           group_job, job_allowed, max_group: int,
                           group_indep=None, group_extra=None,
                           group_mask=None,
                           gpu_strategy: int = BINPACK,
                           cpu_strategy: int = BINPACK,
                           allow_pipeline: bool = True,
                           pipeline_only: bool = False,
                           single_group_jobs: bool = False,
                           fused_mode: str = "jnp",
                           releasing_empty: bool = False,
                           f32_keys: bool = False):
    """Scan over groups; per group emit up to max_group fill segments.

    Returns (seg_nodes [G,K], seg_counts [G,K], seg_pipe [G,K] — phase-B
    segments marked pipelined, group_placed [G], job_success [J],
    node_idle', node_releasing').

    ``single_group_jobs``: every job consists of exactly one group, so a
    failed gang never has prior groups to roll back — the checkpoint
    carries are dropped entirely (a failing group's own take is zeroed by
    its capacity gate).  The host wrapper enables this automatically.

    ``group_extra`` ([J,N] additive score row per JOB — topology and
    nominated-node boosts; groups gather their job's row on device) and
    ``group_mask`` ([J,N] bool hard feasibility — inter-pod-affinity/
    upstream-predicate verdicts, node subsets) extend the fill plan to
    heterogeneous-constraint gangs.
    PRECONDITION for exact parity with the per-task kernel: extra values
    are tier constants (multiples of 10, scoring.py) — the binpack term
    spans < 10, so a group's fill can never reorder nodes ACROSS extra
    levels mid-fill, and WITHIN a level the pure-binpack invariance
    argument above applies unchanged.  The session fast path checks this
    before routing (framework/session.py).

    ``fused_mode`` picks the per-step row implementation (static, decided
    by the host wrapper — docs/DESIGN.md fused-kernel section): ``jnp``
    runs the fused single-pass row (predicates.feasibility_caps_row +
    scoring.score_row_selected) with the masked-sum radix-descent fill;
    ``pallas`` swaps the row pass for the Pallas node-tile kernel
    (ops/pallas_kernels.group_step_pallas).
    ``releasing_empty`` declares the releasing pool all-zero, which
    provably collapses the pipeline item tier: fit_future == fit_now,
    cap_rel == 0, so the step skips the pipe keys, the interleave, and the
    releasing update entirely.  The wrapper only sets it from a
    host-verified hint and never under ``pipeline_only`` (a pipeline-only
    fill mutates releasing below zero, invalidating the premise
    mid-scan)."""
    G = group_req.shape[0]
    N = node_allocatable.shape[0]
    K = max_group
    if group_indep is None:
        group_indep = jnp.zeros(G, bool)
    assert fused_mode in ("jnp", "pallas"), fused_mode
    # A pipeline-only fill mutates releasing below zero mid-scan, which
    # invalidates the all-zero premise the specialization rests on; the
    # wrapper never combines them, direct callers must not either.
    assert not (releasing_empty and pipeline_only), \
        "releasing_empty is unsound under pipeline_only"
    # Pipe (phase-B) items exist unless the releasing tier is provably
    # dead.
    pipe_items = pipeline_only or (allow_pipeline and not releasing_empty)

    class Carry(NamedTuple):
        idle: jnp.ndarray
        rel: jnp.ndarray
        room: jnp.ndarray
        ck_idle: jnp.ndarray
        ck_rel: jnp.ndarray
        ck_room: jnp.ndarray
        cur_job: jnp.ndarray
        cur_ok: jnp.ndarray

    zero = jnp.zeros(())
    init = Carry(node_idle,
                 zero if releasing_empty else node_releasing,
                 node_pod_room,
                 zero if single_group_jobs else node_idle,
                 zero if (single_group_jobs or releasing_empty)
                 else node_releasing,
                 zero if single_group_jobs else node_pod_room,
                 jnp.array(-1, jnp.int32), jnp.array(False))

    def step(carry: Carry, g):
        j = group_job[g]
        new_job = j != carry.cur_job
        if single_group_jobs:
            idle, rel, room = carry.idle, carry.rel, carry.room
            ck_idle, ck_rel, ck_room = zero, zero, zero
            ok = job_allowed[j]
        else:
            keep = jnp.where(new_job & ~carry.cur_ok, False, True)
            idle = jnp.where(keep, carry.idle, carry.ck_idle)
            rel = jnp.where(keep, carry.rel, carry.ck_rel)
            room = jnp.where(keep, carry.room, carry.ck_room)
            ck_idle = jnp.where(new_job, idle, carry.ck_idle)
            ck_rel = jnp.where(new_job, rel, carry.ck_rel)
            ck_room = jnp.where(new_job, room, carry.ck_room)
            ok = jnp.where(new_job, job_allowed[j], carry.cur_ok)

        req = group_req[g]
        count = jnp.where(ok, group_count[g], 0.0)

        extra_row = group_extra[j] if group_extra is not None else None
        mask_row = group_mask[j] if group_mask is not None else None
        row_args = (node_allocatable, idle,
                    None if releasing_empty else rel,
                    node_labels, node_taints, room, req,
                    group_sel[g], group_tol[g], extra_row, mask_row)
        row_kw = dict(gpu_strategy=gpu_strategy,
                      cpu_strategy=cpu_strategy,
                      allow_pipeline=allow_pipeline,
                      pipeline_only=pipeline_only,
                      releasing_empty=releasing_empty,
                      pipe_items=pipe_items)
        if fused_mode == "pallas" and gpu_strategy == cpu_strategy:
            # (Pallas computes at f32 natively — f32_keys is a no-op
            # there; mixed per-axis strategies keep the two-axis
            # canonical scorer, which only the jnp row implements.)
            from .pallas_kernels import group_step_pallas
            (key_now, key_pipe, cap_now, cap_tot,
             levels, utype) = group_step_pallas(*row_args, **row_kw)
        else:
            (key_now, key_pipe, cap_now, cap_tot,
             levels, utype) = _fused_row(*row_args, f32_keys=f32_keys,
                                         **row_kw)
        cap_now = jnp.clip(cap_now, 0.0, count)
        if pipe_items:
            cap_rel = jnp.clip(cap_tot - cap_now, 0.0, count)
            key2 = jnp.stack([key_now, key_pipe], axis=1).reshape(-1)
            cap2 = jnp.stack([cap_now, cap_rel], axis=1).reshape(-1)
        else:
            # Releasing tier provably dead: items ARE nodes — same
            # ascending-index tie-break, half the fill width.
            key2, cap2 = key_now, cap_now
        take2 = jax.lax.cond(
            count > 0,
            lambda: _fill_by_score_descent(key2, levels, utype, cap2,
                                           count),
            lambda: jnp.zeros_like(cap2))

        if pipe_items:
            take_a = take2[0::2]
            take_b = take2[1::2]
        else:
            take_a, take_b = take2, None
        placed = take2.sum()

        if single_group_jobs:
            # A failed gang must leave no trace: zero its takes in-step
            # (there is no later boundary to roll back at).  Independent
            # groups (merged single-task jobs) keep partial placements:
            # each member job succeeds or fails on its own.
            gang_ok = group_indep[g] | (placed >= count)
            take_a = jnp.where(gang_ok, take_a, 0.0)
            take2 = jnp.where(gang_ok, take2, 0.0)
            if take_b is not None:
                take_b = jnp.where(gang_ok, take_b, 0.0)

        idle = idle - take_a[:, None] * req[None, :]
        if not releasing_empty:
            rel = rel - (take_b if take_b is not None
                         else jnp.zeros_like(take_a))[:, None] * req[None, :]
        room = room - take_a - (take_b if take_b is not None else 0.0)

        # Compact the items once: with pipe items interleaved, item
        # index -> (node, phase); without, items are node indices.
        items, counts2, seg_keys = _compact(take2, key2, K)
        if pipe_items:
            seg_nodes = jnp.where(items >= 0, items >> 1, -1)
            seg_pipe = (items >= 0) & (items & 1 == 1) & (counts2 > 0)
        else:
            seg_nodes = items
            seg_pipe = jnp.zeros(K, bool)
        seg_counts = counts2

        ok = ok & (placed >= count)
        return (Carry(idle, rel, room, ck_idle, ck_rel, ck_room,
                      j.astype(jnp.int32), ok),
                (seg_nodes, seg_counts, seg_pipe, seg_keys, placed))

    carry, (seg_nodes, seg_counts, seg_pipe, seg_keys,
            group_placed) = jax.lax.scan(step, init, jnp.arange(G))
    seg_nodes, seg_counts, seg_pipe = _order_segments(
        seg_nodes, seg_counts, seg_pipe, seg_keys)
    if single_group_jobs:
        idle, rel = carry.idle, carry.rel
    else:
        idle = jnp.where(carry.cur_ok, carry.idle, carry.ck_idle)
        rel = jnp.where(carry.cur_ok, carry.rel, carry.ck_rel)
    if releasing_empty:
        # The scan never touched releasing (cap_rel proven 0): the input
        # array IS the output, with no per-step carry copies paid.
        rel = node_releasing

    num_jobs = job_allowed.shape[0]
    placed_per_job = jax.ops.segment_sum(group_placed, group_job,
                                         num_segments=num_jobs)
    count_per_job = jax.ops.segment_sum(group_count, group_job,
                                        num_segments=num_jobs)
    job_success = (count_per_job > 0) & (placed_per_job >= count_per_job) \
        & job_allowed
    return (seg_nodes, seg_counts, seg_pipe, group_placed, job_success,
            idle, rel)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit,
                   static_argnames=("max_group", "t_pad", "gpu_strategy",
                                    "cpu_strategy", "allow_pipeline",
                                    "pipeline_only", "single_group_jobs",
                                    "fused_mode", "releasing_empty",
                                    "f32_keys"))
def _allocate_groups_packed(node_allocatable, node_idle, node_releasing,
                            node_labels, node_taints, node_pod_room,
                            group_req, group_sel, group_tol, group_count,
                            group_job, job_allowed, max_group: int,
                            t_pad: int, group_indep=None, **kw):
    """Kernel + DEVICE-SIDE per-task expansion + single-buffer packing.

    Every fetched buffer is a transfer the host waits on, so everything
    the host needs returns as ONE int32 array of length t_pad + J:
      [0:t_pad]   per-task encoding: -1 unplaced, node for allocated,
                  -(node+2) for pipelined;
      [t_pad:]    per-job success flags.
    Expanding segments to tasks on device replaces both the [G,K]x3
    segment fetch (12.6MB at the north-star shape) and the host-side
    Python per-group expansion loop with one [T] fetch.
    """
    G = group_req.shape[0]
    if group_indep is None:
        group_indep = jnp.zeros(G, bool)
    # G mirrors group_req's leading axis, an operand of this very call:
    # the caller already bucketed it, and the default group_indep can
    # mint no signature the kernel doesn't already key on.
    (seg_nodes, seg_counts, seg_pipe, _group_placed, job_success,
     idle, rel) = allocate_groups_kernel(  # kaijit: disable=KJT001
        node_allocatable, node_idle, node_releasing, node_labels,
        node_taints, node_pod_room, group_req, group_sel, group_tol,
        group_count, group_job, job_allowed, max_group,
        group_indep=group_indep, **kw)
    # A group expands only if it is independent (partial placements keep
    # task order: first jobs of a merged run win) or its gang succeeded.
    gate = group_indep | job_success[group_job]
    counts = jnp.where(gate[:, None], seg_counts, 0).astype(jnp.int32)
    enc = jnp.where(seg_pipe, -(seg_nodes + 2), seg_nodes)
    # Sentinel column per group: the unplaced tail of each group maps to
    # -1, keeping every group's tasks aligned at their original offsets;
    # one trailing sentinel absorbs the pad to t_pad.
    sentinel = (group_count.astype(jnp.int32)
                - counts.sum(axis=1))[:, None]
    flat_enc = jnp.concatenate([
        jnp.concatenate([enc, jnp.full((G, 1), -1, enc.dtype)],
                        axis=1).ravel(),
        jnp.array([-1], enc.dtype)])
    flat_counts = jnp.concatenate([
        jnp.concatenate([counts, sentinel], axis=1).ravel(),
        (t_pad - group_count.sum().astype(jnp.int32))[None]])
    per_task = jnp.repeat(flat_enc, flat_counts,
                          total_repeat_length=t_pad)
    packed = jnp.concatenate([per_task.astype(jnp.int32),
                              job_success.astype(jnp.int32)])
    return packed, idle, rel


def _resolve_fused_mode(requested: str | None, n_nodes: int) -> str:
    """Resolve the rung: an explicit request (tests, tools) wins, else
    ``auto``: the Pallas node-tile kernel on a TPU backend whose node
    bucket tiles evenly, the fused jnp formulation everywhere else."""
    mode = requested or "auto"
    if mode not in FUSED_MODES:
        raise ValueError(f"fused_mode must be one of {FUSED_MODES}, "
                         f"got {mode!r}")
    if mode == "auto":
        if jax.default_backend() == "tpu":
            from .pallas_kernels import NODE_TILE
            if n_nodes >= NODE_TILE and n_nodes % NODE_TILE == 0:
                return "pallas"
        return "jnp"
    if mode == "pallas":
        # An explicitly requested Pallas rung still needs a tileable node
        # bucket; downgrade one rung (loudly, via the downgrade counter)
        # instead of crashing mid-dispatch.
        from .pallas_kernels import NODE_TILE
        tile = min(NODE_TILE, max(n_nodes, 1))
        if not (n_nodes and n_nodes % tile == 0):
            from ..utils.metrics import METRICS
            METRICS.inc("allocate_fused_downgrade_total")
            return "jnp"
    return mode


def allocate_grouped(node_arrays, task_req, task_job, task_selector,
                     task_tolerations, job_allowed,
                     gpu_strategy: int = BINPACK,
                     cpu_strategy: int = BINPACK,
                     allow_pipeline: bool = True,
                     pipeline_only: bool = False,
                     independent_jobs=None,
                     extra_scores=None,
                     node_mask=None,
                     fused_mode: str | None = None,
                     has_releasing: bool | None = None,
                     f32_keys: bool = False) -> AllocationResult:
    """Host wrapper: group prep -> group-scan kernel (with on-device
    per-task expansion).

    Drop-in equivalent of ops.allocate.allocate_jobs_kernel for bin-pack
    strategies.  ``independent_jobs`` ([J] bool): single-task jobs whose
    placement is independent — identical adjacent ones merge into one
    group (one scan step for a whole burst wave), each member succeeding
    or failing on its own.

    ``extra_scores``: [J,N] additive per-JOB score rows (every task of a
    job shares one row — the common shape of topology/nominated boosts);
    values must be tier constants (multiples of 10) for exact parity —
    see allocate_groups_kernel.  ``node_mask``: [J,N] bool per-job hard
    feasibility rows.  Jobs with either disable group merging across job
    boundaries (rows differ) but still fill in one step per group.

    ``fused_mode``: pallas | jnp | auto (default auto — see
    ``_resolve_fused_mode``).
    ``has_releasing``: host-verified hint that the releasing pool has any
    nonzero entry; callers holding host mirrors (the session via the
    arena state cache) pass it so the no-releasing fused specialization
    engages without fetching resident device state.  ``None`` checks the
    array directly off-TPU and conservatively assumes releasing capacity
    on TPU (a hint fetch there would stall on the resident arena state
    the host mirrors exist to avoid reading back).
    """
    np_req = np.asarray(task_req)
    np_job = np.asarray(task_job)
    np_sel = np.asarray(task_selector)
    np_tol = np.asarray(task_tolerations)
    allowed_np = np.asarray(job_allowed)
    mergeable = None
    if independent_jobs is not None and extra_scores is None \
            and node_mask is None:
        # (Per-job extra/mask rows disable cross-job merging: a merged
        # group can only carry one row.)
        indep_np = np.asarray(independent_jobs)
        # Independence only holds for single-task jobs: partial placement
        # of a gang would silently break its atomicity.
        task_counts = np.bincount(np_job, minlength=len(indep_np))
        assert not (indep_np & (task_counts != 1)).any(), \
            "independent_jobs may only flag single-task jobs"
        # Merging may not cross an allowed/gated boundary: the kernel
        # gates a whole group by its first job's flag.
        mergeable = indep_np[np_job] & allowed_np[np_job]
    (group_of_task, g_req, g_sel, g_tol, g_count,
     g_job, g_indep) = group_tasks(np_req, np_job, np_sel, np_tol,
                                   mergeable)
    # Homogeneous gangs: one group per job lets the kernel drop its
    # checkpoint carries entirely.  Merged groups alias several jobs to
    # one group_job; that is only sound in this no-checkpoint mode, so
    # fall back to unmerged grouping otherwise.
    single = len(g_job) == len(set(g_job.tolist()))
    if not single and mergeable is not None and mergeable.any():
        (group_of_task, g_req, g_sel, g_tol, g_count,
         g_job, g_indep) = group_tasks(np_req, np_job, np_sel, np_tol)
        single = len(g_job) == len(set(g_job.tolist()))
    max_group = _next_pow2(int(g_count.max()) if len(g_count) else 1)

    # Pad the ragged group/job/task axes to power-of-two buckets: a steady
    # backlog whose pending count drifts by a few jobs per cycle must not
    # recompile the kernel every cycle (each distinct (G, J, T) is a fresh
    # XLA compilation — seconds per cycle at burst scale).  Padded groups
    # carry count 0 and point at padded jobs gated to False; padded jobs
    # keep group_job values distinct so single-group mode is preserved.
    n_real_groups = len(g_count)
    n_real_jobs = len(allowed_np)
    T = np_req.shape[0]
    t_pad = _next_pow2(max(T, 1))
    g_pad = _next_pow2(max(n_real_groups, 1)) - n_real_groups
    n_jobs_padded = _next_pow2(max(n_real_jobs + g_pad, 1))
    job_allowed_padded = np.zeros(n_jobs_padded, bool)
    job_allowed_padded[:n_real_jobs] = allowed_np
    if g_pad:
        g_req = np.concatenate([g_req, np.zeros((g_pad, g_req.shape[1]))])
        g_sel = np.concatenate(
            [g_sel, np.full((g_pad, g_sel.shape[1]), -1, g_sel.dtype)])
        g_tol = np.concatenate(
            [g_tol, np.full((g_pad, g_tol.shape[1]), -1, g_tol.dtype)])
        g_count = np.concatenate([g_count, np.zeros(g_pad)])
        g_job = np.concatenate([
            g_job, (n_real_jobs + np.arange(g_pad)).astype(np.int32)])
        g_indep = np.concatenate([g_indep, np.zeros(g_pad, bool)])
    kw = {}
    if extra_scores is not None or node_mask is not None:
        # Per-JOB rows, padded to the job axis; groups gather their job's
        # row on device (no [G,N] host expansion).  f32 is exact for tier
        # constants (multiples of 10 below 2^24).
        n_nodes = int(node_arrays[0].shape[0])
        if extra_scores is not None:
            j_extra = np.zeros((n_jobs_padded, n_nodes), np.float32)
            j_extra[:n_real_jobs] = np.asarray(extra_scores)
            kw["group_extra"] = jnp.asarray(j_extra)
        if node_mask is not None:
            j_mask = np.ones((n_jobs_padded, n_nodes), bool)
            j_mask[:n_real_jobs] = np.asarray(node_mask)
            kw["group_mask"] = jnp.asarray(j_mask)

    # Shape metadata only — never np.asarray a possibly-device-resident
    # tensor here (that is a full device->host fetch).
    n_nodes_padded = int(node_arrays[0].shape[0])
    mode = _resolve_fused_mode(fused_mode, n_nodes_padded)
    releasing_empty = False
    if not pipeline_only:
        if has_releasing is None:
            # Off-TPU the releasing array is host-adjacent (CPU backend)
            # so the hint is one cheap scan; on TPU assume releasing
            # capacity rather than fetch resident arena state for a hint.
            has_releasing = True if jax.default_backend() == "tpu" \
                else bool(np.asarray(node_arrays[2]).any())
        releasing_empty = not has_releasing

    from ..utils.metrics import METRICS
    METRICS.inc("allocate_fused_taken_total", mode=mode)
    TRACER.stamp("allocate_fused", mode=mode, groups=n_real_groups,
                 nodes=n_nodes_padded, releasing_empty=releasing_empty)
    packed, idle, rel = _allocate_groups_packed(
        *node_arrays, jnp.asarray(g_req), jnp.asarray(g_sel),
        jnp.asarray(g_tol), jnp.asarray(g_count), jnp.asarray(g_job),
        jnp.asarray(job_allowed_padded), max_group=max_group,
        t_pad=t_pad, group_indep=jnp.asarray(g_indep),
        gpu_strategy=gpu_strategy, cpu_strategy=cpu_strategy,
        allow_pipeline=allow_pipeline, pipeline_only=pipeline_only,
        single_group_jobs=single, fused_mode=mode,
        releasing_empty=releasing_empty, f32_keys=f32_keys, **kw)
    packed = np.asarray(packed)  # ONE device->host fetch
    enc = packed[:T]
    placements = np.where(enc >= -1, enc, -enc - 2).astype(np.int32)
    pipelined = enc < -1
    success = packed[t_pad:t_pad + n_real_jobs] > 0
    # Per-job success for merged independent jobs comes from their own
    # task's placement (the kernel's segment accounting aliases them to
    # the run's first job).  Mergeable jobs are single-task, so their
    # np_job values are unique: one vectorized assignment.
    if mergeable is not None and mergeable.any():
        success[np_job[mergeable]] = placements[mergeable] >= 0
    # All three outputs are host arrays derived from the ONE packed
    # fetch above — returning success as numpy keeps consumers from
    # paying an upload+fetch round trip to read it back.
    return AllocationResult(placements, pipelined, success, idle, rel)
