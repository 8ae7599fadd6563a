"""The generator ``spread_reclaim_gangs``: ``reclaim_gangs``' loop on a
shard whose ``placementStrategy`` is spread.

The client is ``reclaim_gangs``' (imported from the file beside this one,
nothing of it edited): a full fleet, a gang a cycle that reclaims its GPUs
and is bound a cycle later.  What a spread strategy changes belongs here:

- ``prime`` compiles the programs a spread shard dispatches.  The grouped
  fill is never among them (``framework/propose.py`` declines it under
  spread): the allocate action's bind, and its attempt that finds the fleet
  full, are the exact scan of one chunk; the solver's confirms and the
  prescreen are lowered with the strategy the configuration's ``scheduler``
  settings give.  A program compiled for bin-pack would leave the window to
  compile the spread one, and the compile watch refuses such a window.
- ``compare`` adds ``placements_not_reference`` to ``reclaim_gangs``' eleven
  counts: where pods land is what a strategy is, so every pod of every gang
  the window bound is held to the node the plain reference
  (``reference/spread_eviction.py``) gives it, pod by pod from the ledger
  before the bind.
- the byte counts of the rooflines: the prescreen's is ``reclaim_gangs``'
  (one ``[K,N,R]`` f32 pool written and read once: the same work whatever
  form answers it), the exact scan's counts the bind's steps beside the two
  confirms'.
"""

from __future__ import annotations

import copy
import os
import time

import numpy as np

from benchmark.harness import loop, spec

base = spec.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "reclaim_gangs.py"), "generator", "reclaim_gangs")


class Client(base.Client):
    """``reclaim_gangs``' client with the same sums in the occupier's
    department, and in the reclaimer's, on every seed.  Three leaf queues hold nothing (the reclaimer's and two more);
    the seed still says which, but the two lie in neither the occupier's
    nor the reclaimer's department, whose other leaves are the first to
    get their whole-node pods.  Left to the seed, one fleet in thirty has
    an occupier's department that asks under 2**53 bytes, whose queue
    roll-up the program counts where it otherwise walks (a cycle of 10.3 s
    among 12.0: my chip runs, PR 42, seed 4242300014), and two in five a
    reclaimer's department whose sum 32 bits happen to hold
    (``try_quota_at_width``)."""

    def _hold_whole_nodes(self, leaves: list) -> None:
        parent = self.ledger.queue_parent
        first = [parent[self.occupier], parent[self.reclaimer]]
        super()._hold_whole_nodes(sorted(
            leaves, key=lambda q: (first + [parent[q]]).index(parent[q])))


# The rooflines' byte counts, found here by name: ``reclaim_gangs``' own.
# The prescreen's is one ``[K,N,R]`` f32 pool written and read once, the
# same work whatever form answers it; the exact scan's is 48 bytes a node
# a real step (``file_shape`` says which steps are this cycle's).
prefix_feasibility_bytes = base.prefix_feasibility_bytes
exact_scan_bytes = base.exact_scan_bytes


# The trial's gang: the smallest PyTorchJob whose department's sum 32 bits
# round DOWN (a master beside three workers: the sum with one worker is a
# tie that rounds up, and a share rounded up refuses nobody).  The first
# victim job's four pods seat it in two steps, so the trial's solver
# considers four victims and asks no prescreen: a program that refuses
# every scenario has eight to refuse, not 2,048.
TRIAL_ROLE_COUNTS = (1, 3)
TRIAL_SETTINGS = {"max_victims_considered": 4, "scenario_prescreen_max": 0}


def try_quota_at_width(cell, seed: int) -> dict:
    """Two cycles of the deployment at its own width with the smallest
    gang, through the cell's own ``compare``, before the run's fleet is
    built: a gang that arrives inside its quota while the fleet is full
    has its GPUs reclaimed in the cycle it arrives in, and is bound in the
    next.

    What it guards is a sum, so it needs the width and 64 nodes will not
    do: with its three other leaves at their deserved share the
    reclaimer's department asks 3 x 6,144 x 32 cores and the gang's 20
    more, 589,844,000 milli-cores, which 32 bits hold as 589,843,968 (the
    cell's own gang: 590,852,000 and 590,851,968).  A program that takes
    the device's f32 fair share as it comes finds the department 32
    milli-cores over its share and refuses every scenario (as this repo's
    did before PR 42: no eviction in the cycle of arrival, two reclaims in
    the next, ``correct`` false, and 167 s spent refusing 2,048
    scenarios).  It stops here with status 1, soon: the trial's solver
    considers four victims."""
    trial = copy.copy(cell)
    trial.config = copy.deepcopy(cell.config)
    trial.traffic = copy.deepcopy(cell.traffic)
    trial.config["scheduler"].update(TRIAL_SETTINGS)
    for role, count in zip(trial.traffic["gang"]["roles"],
                           TRIAL_ROLE_COUNTS):
        role["count"] = count
    t0 = time.perf_counter()
    client = Client(trial, seed)
    ledger = client.ledger
    department = ledger.queue_parent[client.reclaimer]
    held = ledger.queue_used[department][0]
    for _ in range(2):
        client.cycle()
    asked = held + client.gangs[0].req[:, 0].sum()
    records = client.records
    client.close()
    verdict = compare(records, ledger, trial)
    if not verdict["correct"]:
        raise SystemExit(
            f"{cell.name}: this program cannot run the configuration "
            f"{cell.entry['config']}: a PyTorchJob of "
            f"{sum(TRIAL_ROLE_COUNTS)} pods that arrives inside its quota "
            f"in a department that asks {asked:,.0f} milli-cores "
            f"({np.float32(asked):,.0f} in 32 bits) is not reclaimed for in "
            f"its cycle and bound in the next; compared (value, limit): "
            f"{ {k: v for k, v in verdict['compared'].items() if v[0]} }")
    return {"seconds": round(time.perf_counter() - t0, 3),
            "department_asks_millicores": float(asked),
            "in_32_bits": float(np.float32(asked)),
            "evictions_per_cycle": verdict["run"]["evictions_per_cycle"]}


def build(cell, seed: int, counters: tuple = ()) -> Client:
    trial = try_quota_at_width(cell, seed)
    client = Client(cell, seed, counters)
    client.trial = trial
    return client


# -- the kernels of the cycle -------------------------------------------------
def strategies(cell) -> dict:
    """The two static strategy arguments of every kernel, from the
    operator's settings as ``NodePlacementPlugin`` reads them (anything but
    ``spread`` is bin-pack)."""
    from kai_scheduler_tpu.ops.scoring import BINPACK, SPREAD
    settings = loop.scheduler_config(cell.config, cell.config_path)
    return {f"{axis}_strategy":
            SPREAD if getattr(settings, f"{axis}_placement_strategy")
            == "spread" else BINPACK for axis in ("gpu", "cpu")}


def file_shape(cell) -> dict:
    """``reclaim_gangs``' shapes, and the exact scan's real steps a cycle:
    the two confirms' (the gang and the victims it would place again) and
    the bind's of last cycle's gang.  The attempt that finds the fleet full
    places nothing and is not counted as needed work; its time is in the
    kernel's time all the same.  None of these calls has a score row or a
    mask row.  Counted a pod, as every cell's exact scan is: a bind that
    places a run of identical pods in one pass over the fleet would need a
    recount by a ``benchmark`` PR first (ROADMAP S2 says the same of
    ``tas65k``'s)."""
    shape = base.file_shape(cell)
    shape["scan_steps"] = shape["confirm_steps"] + shape["t"]
    return shape


def _lower(sds, shape: dict, strategy: dict):
    """``batch_prefix_feasibility`` lowered as ``_prefix_prescreen``
    dispatches it on this shard."""
    from kai_scheduler_tpu.ops.scenario_batch import \
        batch_prefix_feasibility
    r, t, m = shape["resources"], shape["t_pad"], shape["rows"]
    f, i = np.float64, np.int32
    return batch_prefix_feasibility.lower(
        *base._node_tables(sds, shape),
        sds((m,), i), sds((m,), i), sds((m, r), f),
        sds((t, r), f), sds((t,), i), sds((t, shape["selector_cols"]), i),
        sds((t, shape["toleration_cols"]), i),
        num_prefixes=shape["prefixes"], **strategy)


def _lower_scan(sds, shape: dict, strategy: dict, t_pad: int, j_pad: int,
                pipeline_only: bool):
    """The exact scan lowered with no node-axis operand: as the solver's
    confirm dispatches it (several jobs, pipeline only), and as the
    allocate action does for one chunk that the grouped fill declined."""
    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
    f, i = np.float64, np.int32
    return allocate_jobs_kernel.lower(
        *base._node_tables(sds, shape),
        sds((t_pad, shape["resources"]), f), sds((t_pad,), i),
        sds((t_pad, shape["selector_cols"]), i),
        sds((t_pad, shape["toleration_cols"]), i), sds((j_pad,), bool), None,
        task_node_mask=None, task_anti_domain=None, task_aff_domain=None,
        job_extra_scores=None, job_node_mask=None, **strategy,
        allow_pipeline=True, pipeline_only=pipeline_only)


def prime(client: Client, watch: loop.CompileWatch) -> dict:
    """Compile the programs of the cycle, each at the shape and under the
    strategy the cycle dispatches it, before the first guarded dispatch
    (the device guard gives a dispatch 30 s, compile included): the
    prescreen, the exact scan of the allocate action's one chunk (the bind,
    and the attempt that finds the fleet full), and the exact scan of the
    solver's confirm in its two shapes."""
    cell = client.cell
    shape, strategy = file_shape(cell), strategies(cell)
    sds = loop.device_operand
    lowerings = {
        "batch_prefix_feasibility": lambda: _lower(sds, shape, strategy),
        f"allocate_jobs_kernel[{shape['t_pad']},2] bind":
        lambda: _lower_scan(sds, shape, strategy, shape["t_pad"], 2, False)}
    for t_pad, j_pad in shape["confirms"]:
        lowerings[f"allocate_jobs_kernel[{t_pad},{j_pad}]"] = \
            lambda t=t_pad, j=j_pad: _lower_scan(sds, shape, strategy, t, j,
                                                 True)
    before = watch.snapshot()
    t0 = time.perf_counter()
    seconds = {}
    for name, lower in lowerings.items():
        t = time.perf_counter()
        lower().compile()
        seconds[name] = round(time.perf_counter() - t, 3)
    client.primed = shape
    return {"seconds": round(time.perf_counter() - t0, 3),
            "kernel": "batch_prefix_feasibility", "kernels": seconds,
            **shape, "trial": getattr(client, "trial", None),
            "cache_misses": watch.since(before)["misses"]}


def kernel_shapes(client: Client) -> dict:
    shapes = base.kernel_shapes(client)
    shapes["exact_scan_bytes"]["steps"] = client.primed["scan_steps"]
    return shapes


def reckon(cell) -> dict:
    """What the cycle holds on the device, from the files.  Under a spread
    strategy the prescreen program is the exact scan vmapped over the
    prefixes: its carries are ``[K,N,R]`` f32 arrays (the scattered
    releases, their running sum, the pools, and the scan's idle, releasing
    and checkpoint states), each prefix another state of the fleet, each
    changed by every pod of the gang and read for the verdict."""
    out = base.reckon(cell)
    out["what"] += "; the vmapped exact scan's carries (spread)"
    return out


def compile_for(cell, sds):
    return _lower(sds, file_shape(cell), strategies(cell)).compile()


# -- the comparison ---------------------------------------------------------
# GPUs, milli-cores and bytes are whole in f32, and on this fleet two
# feasible nodes' free shares are equal or differ by at least 1/8: the
# reference's node is the program's or the program is wrong.
LIMITS = {**base.LIMITS, "placements_not_reference": 0}


def misplaced(records, ledger, ref) -> tuple:
    """(pods not on the reference's node, pods checked) over every gang the
    ``records`` bound whole, each placed by the reference from the ledger
    before its cycle, after the gangs the cycle bound before it."""
    wrong = checked = 0
    for rec in records:
        used, pods = rec.used_before.copy(), rec.pods_before.copy()
        for gang in rec.pending:
            bound = rec.bound.get(gang.uid, {})
            if len(bound) != len(gang.names):
                continue             # gangs_partly_bound's, or not bound
            nodes = np.array([bound[name] for name in gang.names])
            wrong += ref.placements_not_reference(
                ledger.capacity, used, pods, ledger.max_pods, gang.req,
                nodes)
            checked += len(nodes)
            np.add.at(used, nodes, gang.req)
            np.add.at(pods, nodes, 1)
    return wrong, checked


def compare(records, ledger, cell) -> dict:
    """``reclaim_gangs``' verdict on the window's ``records`` with the
    placements held to the reference."""
    out = base.compare(records, ledger, cell)
    wrong, checked = misplaced(records, ledger, cell.reference)
    compared = out["compared"]
    compared["placements_not_reference"] = [
        wrong, LIMITS["placements_not_reference"]]
    out["correct"] = all(v <= lim for v, lim in compared.values())
    out["run"]["placements_checked"] = checked
    out["run"]["gang_roles"] = [
        {"name": r["name"], "count": int(r["count"])}
        for r in cell.traffic["gang"]["roles"]]
    return out
