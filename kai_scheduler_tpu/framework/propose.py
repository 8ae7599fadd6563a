"""Propose: tasks in, kernel operands and a device program out.

The one module that knows how task chunks become the padded rows every
placement kernel takes (``task_operands``), in which form a chunk's extra
scores, hard masks, node subset and domain rows reach a kernel, which
device program places a call (``choose_program``), and how ``(placed,
piped, success)`` becomes ``Proposal``s.  ``Session.propose_placements``
and ``propose_placements_multi`` are one call, ``propose``, with one chunk
or several; the allocate action's bulk wave is ``place_wave``; the
scenario prescreen and the host-side score row take their task rows here
too.  Nothing outside this module imports an allocation kernel, and this
module imports nothing of ``session.py``: the ``Session`` is handed in.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..api.pod_status import PodStatus
from ..ops import allocate_grouped as ag
from ..ops.allocate import allocate_jobs_kernel
from ..ops.allocate_grouped import _next_pow2
from ..ops.scoring import BINPACK, SPREAD
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER


@dataclass
class Proposal:
    """A gang placement proposal from the device kernel."""
    success: bool
    placements: list  # [(task, node_name, pipelined)]


@dataclass
class TaskOperands:
    """The task-axis operands of a placement call, in the order every
    kernel takes them: ``t`` real rows in chunk order, padded to
    ``t_pad``.  Padding tasks ask for nothing and belong to job
    ``len(chunks)``, the first of the gated-out padding jobs that fill the
    job axis to ``j_pad``."""
    task_req: np.ndarray     # [t_pad, R] f64
    task_job: np.ndarray     # [t_pad] int32
    task_sel: np.ndarray     # [t_pad, L] int32, -1 = no term
    task_tol: np.ndarray     # [t_pad, Tl] int32, -1 = none
    job_allowed: np.ndarray  # [j_pad] bool
    t: int
    t_pad: int
    j_pad: int


def task_operands(ssn, chunks, job_allowed=None) -> "TaskOperands | None":
    """``[(job, tasks)]`` as padded kernel rows, or None when a task
    cannot be encoded (a selector key no node carries).

    Both axes are bucketed to powers of two (KJT001): an exact [T] or
    [J+1] would retrace the kernel per distinct live size.  Padding jobs
    are gated out and own only padding tasks, so nothing a kernel reads
    of them is used; consumers index ``success[j]`` for real jobs only.
    ``job_allowed`` gates the real jobs (default: all allowed)."""
    snap = ssn.snapshot
    n_jobs = len(chunks)
    t = sum(len(tasks) for _job, tasks in chunks)
    t_pad = _next_pow2(max(t, 1))
    j_pad = _next_pow2(n_jobs + 1)
    task_req = np.zeros((t_pad, snap.task_req.shape[1]))
    task_sel = np.full((t_pad, snap.task_selector.shape[1]), -1, np.int32)
    task_tol = np.full((t_pad, snap.task_tolerations.shape[1]), -1,
                       np.int32)
    task_job = np.full(t_pad, n_jobs, np.int32)
    row = 0
    for j, (_job, tasks) in enumerate(chunks):
        task_job[row:row + len(tasks)] = j
        for task in tasks:
            req, sel, tol = ssn._task_row(task)
            if req is None:
                return None
            task_req[row], task_sel[row, :len(sel)] = req, sel
            task_tol[row, :len(tol)] = tol
            row += 1
    allowed = np.zeros(j_pad, bool)
    allowed[:n_jobs] = True if job_allowed is None else job_allowed
    return TaskOperands(task_req, task_job, task_sel, task_tol, allowed,
                        t, t_pad, j_pad)


def _pad_rows(rows, t_pad: int, fill):
    """A per-task [t, ...] operand padded to the kernel's [t_pad, ...]
    with ``fill`` rows for the padding tasks; None stays None."""
    if rows is None or rows.shape[0] == t_pad:
        return rows
    out = np.full((t_pad,) + rows.shape[1:], fill, rows.dtype)
    out[:rows.shape[0]] = rows
    return out


def _allocation_shape_check(t_pad: int):
    """Device-guard validator for allocation results: the task axis must
    match what was dispatched (a truncated/garbled device answer — the
    ``badshape`` fault class — must read as a device failure, never be
    silently unpacked)."""
    def ok(result) -> bool:
        try:
            if result.placements.shape[0] < t_pad:
                return False
            packed = getattr(result, "packed", None)
            if packed is not None and \
                    packed.shape[0] != 2 * result.placements.shape[0] \
                    + result.job_success.shape[0]:
                # packed is placements ++ pipelined ++ job_success
                # ([T + T + J], ops/allocate.py AllocationResult).
                return False
            return True
        except Exception:
            return False
    return ok


def _stage(*operands):
    """The host operands of a kernel call as device arrays, in the order
    given (None stays None; a tuple or dict of arrays comes back one).

    Runs inside the dispatch thunk, on the guard's worker: ``jnp.asarray``
    converts a host array whose dtype the regime narrows (f64 to f32
    without x64) on the host, then enqueues the upload.  The one place
    host operands cross to the device, so the bytes are counted here:
    ``device_upload_bytes`` (device side, every operand) and
    ``host_convert_bytes`` (host side, the operands whose dtype changed)."""
    with TRACER.span("seam:stage", kind="seam") as sp:
        host = device = converted = count = 0

        def put(a):
            nonlocal host, device, converted, count
            if isinstance(a, jax.Array):
                return a
            out = jnp.asarray(a)
            a = np.asarray(a)
            count += 1
            host += a.nbytes
            device += out.nbytes
            if out.dtype != a.dtype:
                converted += a.nbytes
            return out

        staged = jax.tree_util.tree_map(put, operands)
        sp.set(bytes_host=host, bytes_device=device,
               bytes_converted=converted, operands=count)
    METRICS.inc("device_upload_bytes", device)
    METRICS.inc("host_convert_bytes", converted)
    return staged


# -- the batched forms, and why a call does not take one ---------------------
# ``batched_form_declined_total{form, reason}``: a call that one of the
# batched forms could not take, counted once a call where the form is
# chosen, never once a task.  ``wave``: the bulk wave of many jobs
# (``wave_filter``); ``grouped_fill``: the fill plan of one homogeneous
# chunk (``_grouped_fill_rows``).  Both place pods that claim idle, where
# spread round-robins as nodes fill, and are proven under bin-pack alone.
# ``prescreen_runs``: the scenario prescreen's run loop, which since PR 43
# lands a run by either strategy's key (ops/scenario_batch.py: nothing
# claims idle in a pipeline-only attempt) and declines nothing.  Nothing
# increments the series; it stays registered at 0 because the benchmark's
# ``strategy_declines`` (benchmark/layer_metrics/strategy_declines.json)
# reads it in every cell, and a series that vanishes reads null there.
# It goes when a ``benchmark`` PR retires that metric (ROADMAP S8).
DECLINES = (("wave", "strategy"),
            ("grouped_fill", "strategy"), ("grouped_fill", "domain_rows"),
            ("grouped_fill", "rows"), ("grouped_fill", "extras"),
            ("grouped_fill", "mask"), ("prescreen_runs", "strategy"),
            ("confirm", "podset-topology"), ("confirm", "victim-topology"))


def declined(form: str, reason: str, count: int = 1) -> None:
    METRICS.inc("batched_form_declined_total", count, form=form,
                reason=reason)


def register_declines() -> None:
    """Every series of the family at 0, when a session opens: a shard that
    never declines reads 0 and not absent."""
    for form, reason in DECLINES:
        declined(form, reason, 0)


def strategy_name(ssn) -> str:
    """The session's placement strategies as a span says them:
    ``binpack``, ``spread``, or ``mixed`` where the two axes differ."""
    if ssn.gpu_strategy != ssn.cpu_strategy:
        return "mixed"
    return "spread" if ssn.gpu_strategy == SPREAD else "binpack"


def _grouped_fill_rows(ssn, rows: TaskOperands, extra, mask, subset,
                       domain_rows: bool):
    """``(row_extra, row_mask)`` when ONE chunk can take the grouped
    fill-plan kernel (one scan step instead of one per task), else None
    with the first reason found counted (``declined``).

    The chunk must be homogeneous: tasks identical in request, selector
    and tolerations, bin-pack on both axes, no domain rows.  Extra score
    terms and hard masks ride along when per-job uniform (one [N] row
    for the whole chunk) — extras must be tier constants (multiples of
    10) for the fill plan's ordering invariance (allocate_groups_kernel);
    a node subset becomes a hard mask row."""
    t = rows.t
    if ssn.gpu_strategy != BINPACK or ssn.cpu_strategy != BINPACK:
        return declined("grouped_fill", "strategy")
    if domain_rows:
        return declined("grouped_fill", "domain_rows")
    if not (t > 1
            and (rows.task_req[1:t] == rows.task_req[0]).all()
            and (rows.task_sel[1:t] == rows.task_sel[0]).all()
            and (rows.task_tol[1:t] == rows.task_tol[0]).all()):
        return declined("grouped_fill", "rows")
    row_extra = row_mask = None
    if extra is not None and extra.any():
        row = extra if extra.ndim == 1 else extra[0]
        if not ((extra.ndim == 1 or (extra[1:] == row).all()) and bool(
                np.all(np.remainder(row, 10.0) == 0.0))):
            return declined("grouped_fill", "extras")
        row_extra = row[None, :]
    if mask is not None:
        if not (mask[1:] == mask[0]).all():
            return declined("grouped_fill", "mask")
        row_mask = mask[:1] if subset is None else mask[:1] & subset
    elif subset is not None:
        row_mask = subset[None, :]
    return row_extra, row_mask


def wave_filter(ssn):
    """``takes(pg, tasks) -> bool``: can the bulk wave place this job —
    many jobs in one grouped call that carries no extra score rows, no
    masks and no host-side state — or None when it can place none (the
    grouped kernel implements bin-pack only)."""
    if ssn.gpu_strategy != BINPACK or ssn.cpu_strategy != BINPACK:
        return declined("wave", "strategy")

    # Anti-affinity symmetry: existing pods' anti terms can repel incoming
    # pods the bulk kernel knows nothing about.  Collect the active terms
    # once and gate only jobs a term could actually match — a single guard
    # pod must not knock every labeled job off the fleet path.
    repeller_terms = [
        term for t in ssn.term_carriers if t.is_active_allocated()
        for term in t.anti_affinity_terms]

    def takes(pg, tasks) -> bool:
        host_side = (
            not tasks
            or any(t.is_fractional or t.resource_claims
                   or t.res_req.mig_resources for t in tasks)
            or any(ps.has_own_topology_constraint()
                   for ps in pg.pod_sets.values())
            or pg.required_topology_level or pg.preferred_topology_level
            # Nominated-node stickiness / affinity peers are extra score
            # terms the grouped kernel doesn't model.
            or any(t.status == PodStatus.PIPELINED
                   for t in pg.pods.values())
            or any(t.nominated_node or t.pod_affinity_peers
                   or t.pod_anti_affinity_peers for t in tasks)
            # Hard node masks (affinity terms, host ports, bound PVCs)
            # are enforced per-proposal; the bulk kernel doesn't model
            # them, so such jobs take the per-job path.
            or any(t.affinity_terms or t.anti_affinity_terms
                   or t.preferred_affinity_terms
                   or t.preferred_anti_affinity_terms
                   or t.node_affinity_required or t.node_affinity_preferred
                   or t.host_ports or t.pvc_names
                   or any(term.matches(t.labels, t.namespace)
                          for term in repeller_terms) for t in tasks))
        return not host_side
    return takes


# -- the choice of program --------------------------------------------------
def choose_program(ssn, kind: str, fill_rows=None, domain_rows=False,
                   pipeline_only=False, extras=False) -> tuple:
    """``(program, dispatch label)`` of a call, from what can be observed.
    The labels are read by name: benchmark/layer_metrics/dispatch_ms.json,
    chip_smoke.py's kernel_spans, the guard's metrics.

    ``grouped``: ops.allocate_grouped.allocate_grouped, the fill plan —
    a homogeneous single chunk (``fill_rows`` from ``_grouped_fill_rows``)
    or a bulk wave; ``sharded_grouped``: the wave on a mesh
    (parallel/sharded_grouped.py; bit-identical to one chip).
    ``sharded``: the exact scan with the node axis over the mesh
    (parallel/sharded.py), bit-identical tie-breaks; domain rows, extra
    score terms and pipeline-only proposals stay on one chip (unsupported
    under shard_map).  ``exact``: ops.allocate.allocate_jobs_kernel, which
    takes everything, and all a several-chunk call may take."""
    if kind == "wave":
        return ("grouped" if ssn.mesh is None else "sharded_grouped",
                "allocate_bulk")
    if kind == "multi":
        return "exact", "allocate_jobs_multi"
    if fill_rows is not None:
        return "grouped", "allocate_grouped"
    if ssn.mesh is not None and not domain_rows and not pipeline_only \
            and not extras:
        return "sharded", "allocate_jobs_sharded"
    return "exact", "allocate_jobs"


def _route_extras(rows: TaskOperands, chunks, extras, n_nodes: int):
    """``(job_extra [j_pad,N], task_extra [t_pad,N])`` of the exact kernel:
    a chunk's [N] row goes to its job's row, [len(tasks),N] to the chunk's
    task rows; None where no chunk brought that form.  The padding jobs'
    rows are read by the padding tasks and never used."""
    job_extra = task_extra = None
    row = 0
    for j, ((_job, tasks), extra) in enumerate(zip(chunks, extras)):
        if extra is not None and extra.ndim == 1:
            if job_extra is None:
                job_extra = np.zeros((rows.j_pad, n_nodes))
            job_extra[j] = extra
        elif extra is not None:
            if task_extra is None:
                task_extra = np.zeros((rows.t_pad, n_nodes))
            task_extra[row:row + len(tasks)] = extra
        row += len(tasks)
    return job_extra, task_extra


def _chunk_extras(ssn, chunks) -> list:
    """Each chunk's summed extra scores.  Consecutive chunks of ONE job (a
    victim a scenario places again, its gang chunk and then its surplus a
    pod a chunk) are scored by one call of the registered fns over all
    their tasks, and the answer cut by chunk: a fn scores a task by the
    task and its job, never by the tasks beside it in the chunk, so a row
    is the row the chunk's own call gives."""
    extras = []
    i = 0
    while i < len(chunks):
        job = chunks[i][0]
        j = i + 1
        while job is not None and j < len(chunks) and chunks[j][0] is job:
            j += 1
        if j == i + 1:
            extras.append(ssn._sum_extra_scores(chunks[i][1]))
        else:
            group = chunks[i:j]
            extra = ssn._sum_extra_scores(
                [t for _job, tasks in group for t in tasks])
            row = 0
            for _job, tasks in group:
                extras.append(extra if extra is None or extra.ndim == 1
                              else extra[row:row + len(tasks)])
                row += len(tasks)
        i = j
    return extras


def _first_rows(fns, tasks):
    """The first registered fn's domain rows for these tasks, or None."""
    for fn in fns:
        rows = fn(tasks)
        if rows is not None:
            return rows
    return None


def _pad_domain_rows(rows, t_pad: int):
    """Anti-affinity ``(doms, marks, avoids)`` or affinity ``(doms, marks,
    avoids, static_ok, boot)`` rows padded to ``t_pad``: a padding task
    sits in no domain, marks and avoids nothing, is fine anywhere."""
    if rows is None:
        return None
    fills = (-1, False, False, True, False)
    dtypes = (np.int32, bool, bool, bool, bool)
    return tuple(
        _pad_rows(np.asarray(a, dtype), t_pad, fill)
        for a, fill, dtype in zip(rows, fills, dtypes))


def _run_grouped(ssn, label: str, program: str, n_jobs: int,
                 rows: TaskOperands, fused_attrs: dict, **kernel_args):
    """One guarded call of the fill plan over the real rows (the wrappers
    group and pad for themselves).  ``fused_dispatch_span`` stamps the
    guard verdict on the cycle thread, the wrapper the rung it resolved;
    the sharded kernel has no rungs, so a mesh dispatch emits no
    ``allocate_fused`` span."""
    t = rows.t
    if program == "sharded_grouped":
        from ..parallel.sharded_grouped import sharded_allocate_grouped
        kernel = functools.partial(sharded_allocate_grouped, ssn.mesh)
        fused_span = contextlib.nullcontext()
    else:
        kernel = ag.allocate_grouped
        # Host-mirror releasing hint: engages the fused kernel's
        # no-releasing specialization without touching device state.
        kernel_args["has_releasing"] = ssn.has_releasing()
        fused_span = ag.fused_dispatch_span(**fused_attrs)
    node_arrays = ssn._device_arrays()
    with fused_span:
        result = ssn.dispatch_kernel(
            lambda: kernel(
                node_arrays, rows.task_req[:t], rows.task_job[:t],
                rows.task_sel[:t], rows.task_tol[:t],
                rows.job_allowed[:n_jobs],
                gpu_strategy=ssn.gpu_strategy,
                cpu_strategy=ssn.cpu_strategy, **kernel_args),
            label=label, validate=_allocation_shape_check(t))
    return (np.asarray(result.placements), np.asarray(result.pipelined),
            np.asarray(result.job_success))


def _proposals(ssn, chunks, placed, piped, success, subsets=None,
               reorder: bool = False) -> list:
    """One ``Proposal`` a chunk, in chunk order, from a kernel's answer.
    A chunk fails whole: its job gated out or rolled back, a task left
    unplaced, or a task outside its node subset (``subsets``: one a
    chunk, None where the chunk has none).  ``reorder``: the chunks
    may be rank-reordered (the caller proved them homogeneous, or the
    registered fns re-verify it, ops/rankplace.py)."""
    names = ssn.snapshot.node_names
    out = []
    row = 0
    for j, (_job, tasks) in enumerate(chunks):
        nodes = placed[row:row + len(tasks)]
        pipes = piped[row:row + len(tasks)]
        row += len(tasks)
        subset = None if subsets is None else subsets[j]
        if not bool(success[j]) or (nodes < 0).any() or (
                subset is not None and not subset[nodes].all()):
            out.append(Proposal(False, []))
            continue
        placements = [(task, names[node], pipe) for task, node, pipe
                      in zip(tasks, nodes.tolist(), pipes.tolist())]
        if reorder:
            placements = ssn.apply_rank_placement(tasks, placements)
        out.append(Proposal(True, placements))
    return out


def propose(ssn, chunks, kind: str, pipeline_only: bool,
            allow_pipeline: bool = True, node_subset=None):
    """Place ``[(job, tasks)]`` in ONE kernel call against the current
    (statement-mutated) node state: a ``Proposal`` a chunk, each with
    per-job gang atomicity (the kernels' per-job success gating).

    ``kind``: ``single`` (one chunk; takes a ``node_subset`` and domain
    rows, goes to any program) or ``multi`` (several chunks, the exact
    kernel only; a ``node_subset`` there holds the FIRST chunk alone, the
    scenario confirm's pending job in its topology domain, and the
    victims behind it go anywhere; consecutive chunks of ONE job there
    are a chain, each tried only where the one before it succeeded).
    None when a task cannot be encoded,
    or a ``multi`` call's tasks bring domain rows (per-job machinery the
    concatenated call cannot express)."""
    METRICS.inc("device_kernel_calls")
    all_tasks = [t for _job, tasks in chunks for t in tasks]
    t = len(all_tasks)
    if kind == "multi" and t == 0:
        return []
    n_nodes = ssn.node_idle.shape[0]
    t_pad = _next_pow2(max(t, 1))
    with TRACER.span("propose:operands", kind="propose", t=t, t_pad=t_pad,
                     nodes=n_nodes) as operands_span:
        # Self-anti-affinity domain rows (spread-one-per-domain gangs)
        # and in-gang required-affinity ones (co-locate gangs).
        anti_dom = _first_rows(ssn.anti_domain_fns, all_tasks)
        aff_dom = _first_rows(ssn.affinity_domain_fns, all_tasks)
        domain_rows = anti_dom is not None or aff_dom is not None
        if domain_rows and kind == "multi":
            return None
        rows = task_operands(ssn, chunks)
        if rows is None:
            return None

        # Each chunk's extra scores are its own job's: None, one [N] row
        # for the whole chunk, or [len(tasks), N].
        extras = _chunk_extras(ssn, chunks)
        # Hard per-task node masks (inter-pod affinity terms, upstream
        # predicate verdicts): False = infeasible, enforced in-kernel.
        mask = ssn.compute_hard_mask(all_tasks)
        # The topology node subset is a hard mask too (matching the
        # fractional/MIG handlers, which skip out-of-subset nodes
        # unconditionally): an out-of-subset node is infeasible, not
        # a soft last resort.  It is the job's row, ANDed with the
        # per-task mask wherever both exist, on every path.
        subset = (None if node_subset is None
                  else np.asarray(node_subset, bool))
        subsets = [subset] + [None if kind == "multi" else subset] * (
            len(chunks) - 1)
        # Said on the span, and counted: the form the score and hard-mask
        # operands took, none, row (one [N] row a job) or dense ([T,N]).
        ndims = {extra.ndim for extra in extras if extra is not None}
        forms = {"extras": "dense" if 2 in ndims else
                 "row" if ndims else "none",
                 "mask": "dense" if mask is not None else
                 "none" if subset is None else "row"}
        operands_span.set(strategy=strategy_name(ssn), **forms)
        METRICS.inc("propose_operand_form_total", **forms)

        fill_rows = (_grouped_fill_rows(ssn, rows, extras[0], mask, subset,
                                        domain_rows)
                     if kind == "single" else None)
        program, label = choose_program(ssn, kind, fill_rows, domain_rows,
                                        pipeline_only, extras=bool(ndims))
        operands_span.set(path="multi" if kind == "multi" else program)
        if mask is not None and program != "grouped":
            # What the [t_pad,N] bool mask uploads (the fill takes a row).
            operands_span.set(mask_bytes=t_pad * n_nodes)

        task_rows = (rows.task_req, rows.task_job, rows.task_sel,
                     rows.task_tol, rows.job_allowed)
        if program == "sharded":
            # The sharded kernel takes no job rows: its [T,N] mask is
            # built here, for this path alone.
            if subset is not None:
                mask = (np.broadcast_to(subset, (t, n_nodes))
                        if mask is None else mask & subset)
            named = {"task_node_mask": _pad_rows(mask, t_pad, True)}
            host = (*task_rows, named)
        elif program == "exact":
            job_extra, task_extra = _route_extras(rows, chunks, extras,
                                                  n_nodes)
            job_mask = None
            if subset is not None and kind == "single":
                job_mask = np.ones((rows.j_pad, n_nodes), bool)
                job_mask[:len(chunks)] = subset
            named = {"task_node_mask": _pad_rows(mask, t_pad, True),
                     "task_anti_domain": _pad_domain_rows(anti_dom, t_pad),
                     "task_aff_domain": _pad_domain_rows(aff_dom, t_pad),
                     "job_extra_scores": job_extra,
                     "job_node_mask": job_mask}
            # The next two named only where there is one (a call that
            # names a None is another program to jit).
            if subset is not None and kind == "multi":
                named["first_job_node_mask"] = subset
            follows = [j > 0 and chunks[j][0] is not None
                       and chunks[j][0] is chunks[j - 1][0]
                       for j in range(len(chunks))]
            if any(follows):
                named["job_follows"] = _pad_rows(
                    np.array(follows), rows.j_pad, False)
            host = (*task_rows, task_extra, named)

    if program == "grouped":
        row_extra, row_mask = fill_rows
        answer = _run_grouped(
            ssn, label, program, len(chunks), rows, fused_attrs={},
            allow_pipeline=allow_pipeline, pipeline_only=pipeline_only,
            extra_scores=row_extra, node_mask=row_mask)
    else:
        if program == "sharded":
            from ..parallel.sharded import sharded_allocate_jobs
        node_arrays = ssn._device_arrays()
        shared = {"gpu_strategy": ssn.gpu_strategy,
                  "cpu_strategy": ssn.cpu_strategy,
                  "allow_pipeline": allow_pipeline}

        def thunk():
            *positional, named = _stage(*host)
            with TRACER.span("seam:launch", kind="seam", kernel=label):
                if program == "sharded":
                    return sharded_allocate_jobs(
                        ssn.mesh, *node_arrays, *positional, **named,
                        **shared)
                return allocate_jobs_kernel(
                    *node_arrays, *positional, **named, **shared,
                    pipeline_only=pipeline_only)

        answer = ssn._dispatch_and_fetch(
            thunk, label=label, validate=_allocation_shape_check(t_pad), t=t)
    with TRACER.span("propose:unpack", kind="propose", t=t):
        # The grouped fill proved its chunk's tasks interchangeable — the
        # one precondition rank reorder needs.
        return _proposals(ssn, chunks, *answer, subsets=subsets,
                          reorder=program == "grouped")


def place_wave(ssn, chunks, job_allowed):
    """The allocate action's bulk wave: every chunk of ``[(job, tasks)]``
    in ONE grouped call, ``job_allowed`` gating each job.  A ``Proposal``
    a chunk, or None when the wave has no task or one that cannot be
    encoded."""
    rows = task_operands(ssn, chunks, job_allowed)
    if rows is None or rows.t == 0:
        return None
    program, label = choose_program(ssn, "wave")
    kernel_args = {}
    if program == "grouped":
        # Single-task chunks place independently: identical adjacent
        # ones merge into one scan step (burst waves of one-pod jobs
        # collapse from thousands of steps to a handful).
        kernel_args["independent_jobs"] = np.array(
            [len(tasks) == 1 for _job, tasks in chunks])
    answer = _run_grouped(ssn, label, program, len(chunks), rows,
                          fused_attrs={"bulk": True}, **kernel_args)
    # The registered rank fns re-verify interchangeability before
    # permuting, so heterogeneous wave chunks pass through untouched.
    return _proposals(ssn, chunks, *answer, reorder=True)


def run_on_nodes(ssn, kernel, operands, label: str, validate, named=None,
                 **static):
    """``kernel(*node arrays, *operands, **named, **static)`` through the
    guard, the host operands (``named``: those the kernel takes by name,
    None stays None) crossing at ``_stage`` like every other kernel's
    (the scenario prescreen's call, actions/solvers.py)."""
    node_arrays = ssn._device_arrays()

    def thunk():
        *positional, by_name = _stage(*operands, named or {})
        return kernel(*node_arrays, *positional, **by_name, **static)

    return ssn.dispatch_kernel(thunk, label=label, validate=validate)


def score_nodes(ssn, task) -> np.ndarray:
    """[N] score row for host-side paths (fractional GPU placement): the
    one-task chunk, which needs no padding."""
    from ..ops.predicates import feasibility_masks
    from ..ops.scoring import score_matrix
    n_nodes = ssn.node_idle.shape[0]
    rows = task_operands(ssn, [(None, [task])])
    if rows is None:
        return np.zeros(n_nodes)
    alloc, idle, rel, labels, taints, room = ssn._device_arrays()

    def score_thunk():
        # Fractional tasks: capacity-check the cpu/mem axes; GPU
        # device fit is decided host-side by the sharing-group logic.
        req = jnp.asarray(rows.task_req)
        fit_now, fit_future = feasibility_masks(
            idle, rel, labels, taints, room, req,
            jnp.asarray(rows.task_sel), jnp.asarray(rows.task_tol))
        score = score_matrix(
            alloc, idle, req, fit_now, fit_future,
            gpu_strategy=ssn.gpu_strategy,
            cpu_strategy=ssn.cpu_strategy)
        return np.asarray(score[0]).copy()

    out = ssn.dispatch_kernel(
        score_thunk, label="score_nodes",
        validate=lambda r: getattr(r, "shape", (0,))[0] == n_nodes)
    # Plugin score terms apply to host-side paths too: without them a
    # nominated (pipelined-last-cycle) fractional task loses its
    # sticky node and flaps between devices across cycles; preferred
    # node affinity would likewise be ignored.
    for fn in ssn.extra_score_fns:
        contrib = fn([task])
        if contrib is not None:
            contrib = np.asarray(contrib)
            # One [N] row for the chunk, or the one task's of [1,N].
            out += contrib if contrib.ndim == 1 else contrib[0]
    return out
