"""The scenario prescreen's two forms (``ops/scenario_batch.py``): a
gang of identical pods is counted in one pass over the prefix pools, a gang
of several runs of identical pods is stepped over run by run with the
grouped kernel's fill between two runs, keyed by the strategies the call is
compiled for; a ``task_node_mask`` row is part of what makes two rows one
pod and bounds where its run may land; and both give the bits of the exact
kernel scanned pod by pod over every prefix (``tests/prescreen_oracle.py``),
with a mask and without.  The choice is made from the task rows and the
mask's, on the device and, for the span and the counters, on the host."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.ops import scenario_batch as sb
from kai_scheduler_tpu.ops.scoring import BINPACK, SPREAD
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.utils.metrics import METRICS
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.fixtures import build_session, run_action
from tests.prescreen_oracle import scan_prefixes

N, K, M, T_PAD = 24, 16, 32, 8
POD = np.array([4000.0, 2.0 ** 35, 1.0])     # the benchmark's worker


def fleet(seed: int, oversize: bool = False):
    """A random fleet, K prefixes of release rows and one gang of
    identical pods padded to T_PAD under job 1.  Every quantity is a
    whole multiple of the pod's, so each comparison is exact."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 3, (N, 2)).astype(np.int32)
    taints = np.where(rng.random((N, 1)) < 0.3, 7, -1).astype(np.int32)
    room = rng.integers(0, 4, N).astype(float)
    idle = rng.integers(0, 2, (N, 3)) * POD
    rel = rng.integers(0, 2, (N, 3)) * POD
    alloc = np.tile(8 * POD, (N, 1))
    # Release rows land on any node, those the selector refuses too; the
    # padding rows carry step K and drop.
    step = rng.integers(0, K, M).astype(np.int32)
    step[rng.random(M) < 0.2] = K
    node = rng.integers(0, N, M).astype(np.int32)
    vec = rng.integers(0, 3, (M, 3)) * POD
    # A resource nobody asks for: zero-request columns in some fleets.
    req = POD * (rng.random(3) < 0.75)
    sel = np.where(rng.random(2) < 0.5, rng.integers(0, 3, 2), -1)
    tol = np.array([7 if rng.random() < 0.5 else -1])
    gang = N * 4 + 1 if oversize else int(rng.integers(1, T_PAD + 1))
    t_pad = max(T_PAD, 1 << (gang - 1).bit_length())
    task_job = np.where(np.arange(t_pad) < gang, 0, 1).astype(np.int32)
    real = (task_job == 0)[:, None]
    task_req = np.where(real, req, 0.0)
    task_sel = np.where(real, sel, -1).astype(np.int32)
    task_tol = np.where(real, tol, -1).astype(np.int32)
    return ((alloc, idle, rel, labels, taints, room), (step, node, vec),
            (task_req, task_job, task_sel, task_tol))


def pools(rel, step, node, vec, k=K):
    """[K,N,R] prefix pools, in numpy: the scatter and the running sum."""
    delta = np.zeros((k + 1,) + rel.shape)
    np.add.at(delta, (np.minimum(step, k), node), vec)
    return rel[None] + np.cumsum(delta[:k], axis=0)


@jax.jit
def counted(pool, nodes, tasks, mask=None):
    _alloc, idle, _rel, labels, taints, room = nodes
    return sb.count_prefixes(pool, idle, labels, taints, room, *tasks, mask)


# (gpu_strategy, cpu_strategy), as the kernels take them.
PAIRS = ((BINPACK, BINPACK), (SPREAD, SPREAD), (SPREAD, BINPACK),
         (BINPACK, SPREAD))
PAIR_IDS = ("binpack", "spread", "spread-gpus", "spread-cpus")


@functools.partial(jax.jit, static_argnames=("pair",))
def scanned(pool, nodes, tasks, mask=None, pair=PAIRS[0]):
    alloc, idle, _rel, labels, taints, room = nodes
    return scan_prefixes(pool, alloc, idle, labels, taints, room, *tasks,
                         mask, *pair)


@functools.partial(jax.jit, static_argnames=("pair", "f32_keys"))
def grouped(pool, nodes, tasks, pair=PAIRS[0], f32_keys=True, mask=None):
    """By default, keys at the chip's precision (``_score_keys``
    ``force_f32``)."""
    alloc, idle, _rel, labels, taints, room = nodes
    return sb.group_prefixes(pool, alloc, idle, labels, taints, room, *tasks,
                             mask, gpu_strategy=pair[0],
                             cpu_strategy=pair[1], f32_keys=f32_keys)


def whole(nodes, release, tasks, k=K, mask=None, pair=PAIRS[0]):
    return np.asarray(sb.batch_prefix_feasibility(
        *nodes, *release, *tasks, num_prefixes=k, task_node_mask=mask,
        gpu_strategy=pair[0], cpu_strategy=pair[1]))


def traced_forms(monkeypatch):
    """The list that the two forms' kernels add their names to as
    ``batch_prefix_feasibility`` traces them (clear its cache around the
    call, so that it traces)."""
    traced = []
    for name in ("count_prefixes", "group_prefixes"):
        kernel = getattr(sb, name)
        monkeypatch.setattr(sb, name, lambda *a, _n=name, _f=kernel, **kw: (
            traced.append(_n), _f(*a, **kw))[1])
    return traced


def form_of(tasks, mask=None):
    return sb.dispatched_form(*tasks, mask)


MASKS = ("one-row", "row-a-run", "split", "all-true", "no-node")


def gang_mask(kind: str, tasks, seed: int):
    """``(mask, runs_more)``: a static ``[t_pad,N]`` bool mask for the gang
    of ``tasks``, all-true on the padding rows as the solver pads it, and
    the runs it adds to the gang's.  ``one-row``: every pod the same random
    row (one template's required affinity); ``row-a-run``: each run of
    identical pods its own; ``split``: one row, but for the first run of
    two pods or more, whose pods after the first carry another, which
    must open a run; ``all-true`` and ``no-node``; ``none``: no mask."""
    if kind == "none":
        return None, 0
    real = tasks[1] == 0
    opens = np.r_[real[:1], real[1:] & ~sb.continues_run(*tasks)]
    run_of = np.cumsum(opens) - 1
    rows = np.random.default_rng([seed, 45]).random(
        (int(opens.sum()) + 1, N)) < 0.7
    assert len({row.tobytes() for row in rows}) == len(rows)
    mask = np.ones((len(real), N), bool)
    if kind == "one-row":
        mask[real] = rows[-1]
    elif kind == "row-a-run":
        mask[real] = rows[run_of[real]]
    elif kind == "split":
        mask[real] = rows[-1]
        sizes = np.bincount(run_of[real])
        first = int(np.flatnonzero(sizes >= 2)[0])
        mask[real & (run_of == first) & ~opens] = rows[first]
        return mask, 1
    elif kind == "no-node":
        mask[real] = False
    return mask, 0


def mask_cases(seeds, masked_seeds, no_split=()):
    """``(seed, mask kind)``: every seed unmasked, as before PR 45, and
    ``masked_seeds`` under each kind of ``MASKS``; the seeds of
    ``no_split`` have no run of two pods to split."""
    return [(seed, "none") for seed in seeds] + [
        (seed, kind) for seed in masked_seeds for kind in MASKS
        if kind != "split" or seed not in no_split]


# Gangs of one pod.
@pytest.mark.parametrize("seed, kind", mask_cases(
    range(24), range(24), no_split=(5, 13, 21)))
def test_counted_equals_scanned_on_random_fleets(seed, kind):
    """A gang of identical pods is counted, under any mask that gives
    every pod the same row; a row that changes inside it makes two runs.
    Either way the bits are the exact scan's under that mask."""
    nodes, release, tasks = fleet(seed)
    pool = pools(nodes[2], *release)
    mask, more = gang_mask(kind, tasks, seed)
    device_mask = None if mask is None else jnp.asarray(mask)
    want = np.asarray(scanned(pool, nodes, tasks, device_mask))
    uniform = bool(sb.uniform_gang(*map(jnp.asarray, tasks), device_mask))
    assert uniform == (more == 0)
    if uniform:
        assert form_of(tasks, mask) == ("counted", 0)
        assert np.asarray(counted(pool, nodes, tasks,
                                  device_mask)).tolist() == want.tolist()
    else:
        assert form_of(tasks, mask) == ("grouped", 2)
        assert np.asarray(grouped(pool, nodes, tasks,
                                  mask=device_mask)).tolist() \
            == want.tolist()
    assert whole(nodes, release, tasks,
                 mask=device_mask).tolist() == want.tolist()
    if kind == "all-true":
        assert want.tolist() == np.asarray(
            scanned(pool, nodes, tasks)).tolist()
    if kind == "no-node":
        assert not want.any()


def test_the_random_fleets_hold_both_answers():
    """The fleets above are no vacuous agreement: both bits occur."""
    bits = []
    for seed in range(24):
        nodes, release, tasks = fleet(seed)
        bits += np.asarray(counted(pools(nodes[2], *release), nodes,
                                   tasks)).tolist()
    assert 0.2 < np.mean(bits) < 0.8


@pytest.mark.parametrize("seed", range(4))
def test_a_gang_larger_than_the_fleet_can_hold_never_fits(seed):
    nodes, release, tasks = fleet(100 + seed, oversize=True)
    pool = pools(nodes[2], *release)
    got = np.asarray(counted(pool, nodes, tasks))
    assert not got.any()
    assert got.tolist() == np.asarray(scanned(pool, nodes, tasks)).tolist()


def test_a_gang_of_no_pods_fits_nowhere():
    """All rows padding: the exact kernel reports a job of no tasks as not
    placed, and so does the count."""
    nodes, release, tasks = fleet(0)
    task_req, task_job, task_sel, task_tol = tasks
    tasks = (task_req * 0, np.ones_like(task_job), task_sel, task_tol)
    pool = pools(nodes[2], *release)
    assert not np.asarray(counted(pool, nodes, tasks)).any()
    assert not np.asarray(scanned(pool, nodes, tasks)).any()


@pytest.mark.parametrize("off", (-1, 0, 1), ids=("low", "exact", "high"))
@pytest.mark.parametrize("req", (4000.0, 2.0 ** 35, 1.0),
                         ids=("4000", "2**35", "1"))
def test_the_count_survives_a_quotient_one_off(req, off):
    """TPU f32 division hands ``floor(k * req / req)`` back one low
    (ROADMAP D12): fed such a quotient, in f32, the count is exact."""
    k = jnp.arange(1, 513, dtype=jnp.float32)
    req = jnp.float32(req)
    total = k * req
    got = sb.corrected_count(k + off, req, total)
    assert got.dtype == jnp.float32
    assert np.asarray(got).tolist() == np.asarray(k).tolist()
    # A remainder short of one more pod changes nothing; less than
    # nothing counts as none.
    part = sb.corrected_count(k + off, req, total + req / 2)
    assert np.asarray(part).tolist() == np.asarray(k).tolist()
    assert float(sb.corrected_count(jnp.float32(off), req, -req)) == 0.0


def mixed_fleet():
    """Four nodes of room for one pod each, a GPU releasing on node 0; the
    gang is a master of two GPUs beside two one-GPU workers, and prefix k
    releases a GPU on node k.  Counting the master's row for all three
    would ask two GPUs a node, which node 0 alone ever has."""
    n, k = 4, 4
    unit = np.array([1000.0, 2.0 ** 30, 1.0])
    rel = np.zeros((n, 3))
    rel[0] = unit
    nodes = (np.tile(8 * unit, (n, 1)), np.zeros((n, 3)), rel,
             np.full((n, 1), -1, np.int32),
             np.full((n, 1), -1, np.int32), np.ones(n))
    step = np.arange(k, dtype=np.int32)
    release = (step, step, np.tile(unit, (k, 1)))
    task_req = np.array([2 * unit, unit, unit, 0 * unit])
    task_job = np.array([0, 0, 0, 1], np.int32)
    none = np.full((4, 1), -1, np.int32)
    return nodes, release, (task_req, task_job, none, none), k


def test_a_gang_of_two_distinct_rows_is_scanned():
    """Since PR 39 by the grouped form: two runs, two steps, and the exact
    scan's bits."""
    nodes, release, tasks, k = mixed_fleet()
    pool = pools(nodes[2], *release, k=k)
    want = np.asarray(scanned(pool, nodes, tasks)).tolist()
    assert want == [False, False, True, True]
    assert not bool(sb.uniform_gang(*map(jnp.asarray, tasks)))
    assert form_of(tasks) == ("grouped", 2)
    assert np.asarray(grouped(pool, nodes, tasks)).tolist() == want
    assert whole(nodes, release, tasks, k=k).tolist() == want
    # The count would have answered for three masters.
    assert np.asarray(counted(pool, nodes, tasks)).tolist() != want


def test_a_call_with_a_task_node_mask_is_counted_under_its_row():
    """Scanned until PR 45.  One row for every pod leaves the gang uniform:
    the count among the nodes the row admits, and the exact scan's bits."""
    nodes, release, tasks, k = mixed_fleet()
    task_req, task_job, none, _ = tasks
    tasks = (np.where((task_job == 0)[:, None], task_req[1], 0.0), task_job,
             none, none)
    assert bool(sb.uniform_gang(*map(jnp.asarray, tasks)))
    # Identical workers, but none may use node 2.
    mask = np.ones((4, 4), bool)
    mask[:, 2] = False
    assert bool(sb.uniform_gang(*map(jnp.asarray, tasks),
                                jnp.asarray(mask)))
    assert form_of(tasks, mask) == ("counted", 0)
    pool = pools(nodes[2], *release, k=k)
    want = np.asarray(scanned(pool, nodes, tasks,
                              jnp.asarray(mask))).tolist()
    assert want == [False, False, False, True]
    assert whole(nodes, release, tasks, k=k,
                 mask=jnp.asarray(mask)).tolist() == want
    assert np.asarray(counted(pool, nodes, tasks,
                              jnp.asarray(mask))).tolist() == want
    assert np.asarray(counted(pool, nodes, tasks)).tolist() \
        == [False, False, True, True]
    # The last worker alone may use node 2 as well: another pod, a second
    # run, and the scan's bits still.
    mask[2, 2] = True
    assert not bool(sb.uniform_gang(*map(jnp.asarray, tasks),
                                    jnp.asarray(mask)))
    assert form_of(tasks, mask) == ("grouped", 2)
    want = np.asarray(scanned(pool, nodes, tasks,
                              jnp.asarray(mask))).tolist()
    assert want == [False, False, True, True]
    assert whole(nodes, release, tasks, k=k,
                 mask=jnp.asarray(mask)).tolist() == want


def counted_under_a_row(nodes, release, tasks, row):
    """[K] bool in numpy for a gang of identical pods that all carry the
    same mask ``row`` [N] (a required node affinity of one template): the
    count's closed form among the nodes the row admits, with the label
    and taint compare of ``hard_row`` spelled out."""
    _alloc, idle, rel, labels, taints, room = nodes
    task_req, task_job, task_sel, task_tol = tasks
    need = int((task_job == 0).sum())
    req, sel, tol = task_req[0], task_sel[0], task_tol[0]
    tolerated = (taints[:, :, None] == tol[None, None, :]).any(axis=-1)
    hard = (np.all((sel == -1) | (sel == labels), axis=1)
            & np.all((taints == -1) | tolerated, axis=1) & (room >= 1) & row)
    out = []
    for pool in pools(rel, *release):
        seats = np.floor(room)
        for res in np.flatnonzero(req > 0):
            seats = np.minimum(seats, np.floor(
                (idle[:, res] + pool[:, res] + 1e-9) / req[res]))
        out.append(need > 0 and float(
            np.minimum(np.where(hard, seats, 0), need).sum()) >= need)
    return out


@pytest.mark.parametrize("pair", PAIRS[:2], ids=PAIR_IDS[:2])
@pytest.mark.parametrize("seed", range(24))
def test_a_masked_gang_is_the_count_among_the_admitted_nodes(seed, pair):
    """One static row for every pod (PR 44: what the solver sends for a
    gang with a required node affinity), on fleets whose label and taint
    tables are not empty: the verdict is the numpy count among the admitted
    nodes, under either strategy, and the host labels the call ``counted``
    (``scanned`` with ``t_pad`` steps until PR 45)."""
    nodes, release, tasks = fleet(seed)
    rng = np.random.default_rng([seed, 44])
    row = rng.random(N) < 0.6
    t_pad = len(tasks[0])
    mask = np.ones((t_pad, N), bool)
    mask[tasks[1] == 0] = row
    want = counted_under_a_row(nodes, release, tasks, row)
    got = whole(nodes, release, tasks, mask=jnp.asarray(mask), pair=pair)
    assert got.tolist() == want
    assert form_of(tasks, mask) == ("counted", 0)
    pool = pools(nodes[2], *release)
    assert np.asarray(scanned(pool, nodes, tasks, jnp.asarray(mask),
                              pair=pair)).tolist() == want
    # An all-true mask is the unmasked count.
    assert whole(nodes, release, tasks, pair=pair).tolist() \
        == counted_under_a_row(nodes, release, tasks, np.ones(N, bool))


def test_the_masked_fleets_hold_both_answers_and_the_mask_matters():
    flipped = fits = fails = 0
    for seed in range(24):
        nodes, release, tasks = fleet(seed)
        row = np.random.default_rng([seed, 44]).random(N) < 0.6
        masked = counted_under_a_row(nodes, release, tasks, row)
        plain = counted_under_a_row(nodes, release, tasks,
                                    np.ones(N, bool))
        fits += sum(masked)
        fails += len(masked) - sum(masked)
        flipped += sum(a != b for a, b in zip(masked, plain))
    assert fits > 20 and fails > 20 and flipped > 10


PATTERNS = {2: ("MW", "WM", "WM"), 3: ("MWX", "WMW", "WXM"),
            4: ("MWXW", "WMXW", "WXWM")}
DIFFERS = ("request", "selector", "tolerations", "any")


def mixed_gang(seed: int, oversize: bool = False):
    """``fleet(seed)``'s nodes and release rows under a gang of 2 to 4
    runs: workers W, one master M that differs from them in the request
    alone, the selector alone, the tolerations alone or anyhow (by
    ``seed % 4``), a third pod X; the master first, in the middle or last
    (by ``seed // 4 % 3``).  Padding rows under job 1."""
    (alloc, _, rel, labels, taints, room), release, _ = fleet(seed)
    rng = np.random.default_rng(1000 + seed)
    differs = DIFFERS[seed % 4]
    # More levels of free capacity than ``fleet`` has, for the score to
    # order, and some nodes with no GPU at all.
    idle = rng.integers(0, 4, (N, 3)) * POD
    bare = rng.random(N) < 0.2
    alloc, idle, rel = (np.where(bare[:, None] & (np.arange(3) == 2), 0.0, a)
                        for a in (alloc, idle, rel))
    nodes = (alloc, idle, rel, labels, taints, room)

    def pod():
        # Zero-request columns in most pods; never a pod of nothing.
        req = POD * rng.integers(0, 3, 3)
        if not req.any():
            col = rng.integers(0, 3)
            req[col] = POD[col]
        return (req, np.where(rng.random(2) < 0.4, rng.integers(0, 3, 2),
                              -1),
                np.array([7 if rng.random() < 0.5 else -1]))

    def another(*others):
        while True:
            new = pod()
            if not any(all((a == b).all() for a, b in zip(new, other))
                       for other in others):
                return new

    worker = pod()
    master = list(another(worker) if differs == "any" else worker)
    if differs == "request":
        master[0] = worker[0] + POD * np.eye(3)[rng.integers(0, 3)]
    elif differs == "selector":
        master[1] = np.where(np.arange(2) == rng.integers(0, 2),
                             (worker[1] + 2) % 3, worker[1])
    elif differs == "tolerations":
        master[2] = np.array([-1 if worker[2][0] == 7 else 7])
    third = another(worker, master)
    pattern = PATTERNS[int(rng.integers(2, 5))][seed // 4 % 3]
    rows = []
    for letter in pattern:
        size = 1 if letter == "M" else int(rng.integers(1, 5))
        if oversize and letter == "W":
            size, oversize = N * 4 + 1, False
        rows += [{"M": master, "W": worker, "X": third}[letter]] * size
    t_pad = max(2 * T_PAD, 1 << len(rows).bit_length())
    pad = t_pad - len(rows)
    task_req = np.vstack([r[0] for r in rows] + [np.zeros((pad, 3))])
    task_sel = np.vstack([r[1] for r in rows]
                         + [np.full((pad, 2), -1)]).astype(np.int32)
    task_tol = np.vstack([r[2] for r in rows]
                         + [np.full((pad, 1), -1)]).astype(np.int32)
    task_job = (np.arange(t_pad) >= len(rows)).astype(np.int32)
    return nodes, release, (task_req, task_job, task_sel, task_tol), pattern


def runs_alone(pool, nodes, tasks):
    """A count without the fill: every run counted against the untouched
    pool, as if no other run had landed."""
    task_req, task_job, task_sel, task_tol = tasks
    real = task_job == 0
    starts = np.flatnonzero(np.r_[real[:1], real[1:] & ~sb.continues_run(
        *tasks)])
    out = np.ones(len(pool), bool)
    for lo, hi in zip(starts, np.r_[starts[1:], real.sum()]):
        job = np.where((np.arange(len(real)) >= lo)
                       & (np.arange(len(real)) < hi), 0, 1).astype(np.int32)
        order = np.argsort(job, kind="stable")
        out &= np.asarray(counted(pool, nodes, (
            task_req[order], job[order], task_sel[order], task_tol[order])))
    return out


MIXED_SEEDS = range(36)
NO_SPLIT = (33,)          # every run of its gang is one pod


def grouped_equals_scanned(nodes, release, tasks, runs, kind, seed,
                           pair=PAIRS[0], f32_keys=True):
    """The run loop and the whole call against the exact scan under the
    same mask (``gang_mask``); the scan's bits."""
    pool = pools(nodes[2], *release)
    mask, more = gang_mask(kind, tasks, seed)
    device_mask = None if mask is None else jnp.asarray(mask)
    want = np.asarray(scanned(pool, nodes, tasks, device_mask,
                              pair=pair)).tolist()
    assert form_of(tasks, mask) == ("grouped", runs + more)
    assert int(sb.gang_runs(*map(jnp.asarray, tasks),
                            device_mask)) == runs + more
    assert np.asarray(grouped(pool, nodes, tasks, pair, f32_keys,
                              device_mask)).tolist() == want
    if f32_keys:
        assert whole(nodes, release, tasks, mask=device_mask,
                     pair=pair).tolist() == want
    if kind == "all-true":
        assert want == np.asarray(scanned(pool, nodes, tasks,
                                          pair=pair)).tolist()
    if kind == "no-node":
        assert not any(want)
    return want, device_mask


@pytest.mark.parametrize("seed, kind", mask_cases(
    MIXED_SEEDS, MIXED_SEEDS, NO_SPLIT))
def test_grouped_equals_scanned_on_random_fleets(seed, kind):
    nodes, release, tasks, pattern = mixed_gang(seed)
    grouped_equals_scanned(nodes, release, tasks, len(pattern), kind, seed)


def test_the_mixed_fleets_hold_both_answers_and_need_the_fill():
    """No vacuous agreement: both bits occur, and counting the runs with
    no landing between them answers some of these prefixes wrongly."""
    bits, unlike = [], 0
    for seed in MIXED_SEEDS:
        nodes, release, tasks, _ = mixed_gang(seed)
        pool = pools(nodes[2], *release)
        got = np.asarray(grouped(pool, nodes, tasks))
        bits += got.tolist()
        alone = runs_alone(pool, nodes, tasks)
        # Without the landing a run sees more room, never less.
        assert not (got & ~alone).any()
        unlike += int((alone != got).sum())
    assert 0.15 < np.mean(bits) < 0.85
    assert unlike >= 10


def test_the_masked_gangs_hold_both_answers_and_every_row_matters():
    """No vacuous agreement under a mask either: both bits occur, the mask
    moves bits, and landing a split run whole under its first pod's row
    (the row change ignored) answers otherwise."""
    bits, moved, unsplit = [], 0, 0
    for seed in MIXED_SEEDS:
        nodes, release, tasks, _ = mixed_gang(seed)
        pool = pools(nodes[2], *release)
        plain = np.asarray(grouped(pool, nodes, tasks))
        under = {}
        for kind in ("one-row", "row-a-run", "split"):
            if kind == "split" and seed in NO_SPLIT:
                continue
            mask, _ = gang_mask(kind, tasks, seed)
            under[kind] = np.asarray(grouped(pool, nodes, tasks,
                                             mask=jnp.asarray(mask)))
            bits += under[kind].tolist()
            moved += int((under[kind] != plain).sum())
        if "split" in under:
            unsplit += int((under["split"] != under["one-row"]).sum())
    assert 0.2 < np.mean(bits) < 0.8
    assert moved >= 100 and unsplit >= 20


@pytest.mark.parametrize("seed", range(4))
def test_a_run_larger_than_the_fleet_can_hold_never_fits(seed):
    nodes, release, tasks, _ = mixed_gang(200 + seed, oversize=True)
    pool = pools(nodes[2], *release)
    got = np.asarray(grouped(pool, nodes, tasks))
    assert not got.any()
    assert got.tolist() == np.asarray(scanned(pool, nodes, tasks)).tolist()
    assert whole(nodes, release, tasks).tolist() == got.tolist()


def test_a_mixed_gang_of_no_pods_fits_nowhere():
    nodes, release, tasks, _ = mixed_gang(0)
    task_req, task_job, task_sel, task_tol = tasks
    tasks = (task_req, np.ones_like(task_job), task_sel, task_tol)
    pool = pools(nodes[2], *release)
    assert int(sb.gang_runs(*map(jnp.asarray, tasks))) == 0
    assert not np.asarray(grouped(pool, nodes, tasks)).any()
    assert not np.asarray(scanned(pool, nodes, tasks)).any()


def two_nodes(idle_gpus, releasing_gpus, late_release_on):
    """Two nodes of room for two pods each; a one-GPU worker, then a
    two-GPU master; prefix 1 releases one GPU more on one node."""
    unit = np.array([1000.0, 2.0 ** 30, 1.0])
    none = np.full((2, 1), -1, np.int32)
    nodes = (np.tile(8 * unit, (2, 1)), np.outer(idle_gpus, unit),
             np.outer(releasing_gpus, unit), none, none, np.full(2, 2.0))
    release = (np.array([1], np.int32),
               np.array([late_release_on], np.int32), unit[None])
    task_req = np.array([unit, 2 * unit, 0 * unit, 0 * unit])
    task_job = np.array([0, 0, 1, 1], np.int32)
    none = np.full((4, 1), -1, np.int32)
    return nodes, release, (task_req, task_job, none, none)


def test_the_first_runs_landing_can_take_the_second_runs_only_node():
    """Node 0 releases two GPUs, node 1 one; equal scores, so the worker
    lands on node 0 and leaves the master one GPU on either: the verdict
    is False, where each run counted alone fits.  A second GPU on node 1
    seats the master there."""
    nodes, release, tasks = two_nodes([0, 0], [2, 1], late_release_on=1)
    pool = pools(nodes[2], *release, k=2)
    want = np.asarray(scanned(pool, nodes, tasks)).tolist()
    assert want == [False, True]
    assert np.asarray(grouped(pool, nodes, tasks)).tolist() == want
    assert whole(nodes, release, tasks, k=2).tolist() == want
    assert runs_alone(pool, nodes, tasks).tolist() == [True, True]


def test_the_first_run_can_land_where_the_second_never_could():
    """Node 0 has an idle GPU and releases one, node 1 releases its only
    free one: bin-pack sends the worker to node 1, the fuller, where no
    master ever fits, and the master has node 0: True.  A landing in index
    order would have put the worker on node 0 and answered False."""
    nodes, release, tasks = two_nodes([1, 0], [1, 1], late_release_on=1)
    pool = pools(nodes[2], *release, k=2)
    want = np.asarray(scanned(pool, nodes, tasks)).tolist()
    assert want == [True, True]
    assert np.asarray(grouped(pool, nodes, tasks)).tolist() == want
    assert whole(nodes, release, tasks, k=2).tolist() == want
    assert first_fit_verdict(nodes, release, tasks, 2) == [False, True]


@pytest.mark.parametrize("exact", ("spread", "mask"))
def test_neither_a_mask_nor_a_spread_strategy_keeps_the_exact_scan(
        monkeypatch, exact):
    """A spread strategy (scanned until PR 43) and a masked call (scanned
    until PR 45) both trace the count and the run loop, and the program has
    no exact scan to trace.  Either way the answer is the exact scan's
    under that strategy and that mask."""
    assert not hasattr(sb, "scan_prefixes")
    assert not hasattr(sb, "allocate_jobs_kernel")
    traced = traced_forms(monkeypatch)
    nodes, release, tasks, pattern = mixed_gang(5)
    pool = pools(nodes[2], *release)
    sb.batch_prefix_feasibility.clear_cache()
    try:
        if exact == "spread":
            got = whole(nodes, release, tasks, pair=(SPREAD, SPREAD))
            want = scanned(pool, nodes, tasks, pair=(SPREAD, SPREAD))
            assert form_of(tasks) == ("grouped", len(pattern))
        else:
            mask = np.ones((len(tasks[0]), N), bool)
            mask[:, ::3] = False
            got = whole(nodes, release, tasks, mask=jnp.asarray(mask))
            want = scanned(pool, nodes, tasks, jnp.asarray(mask))
            assert form_of(tasks, mask) == ("grouped", len(pattern))
        assert sorted(traced) == ["count_prefixes", "group_prefixes"]
        assert got.tolist() == np.asarray(want).tolist()
        traced.clear()
        whole(nodes, release, tasks)
        assert sorted(traced) == ["count_prefixes", "group_prefixes"]
    finally:
        sb.batch_prefix_feasibility.clear_cache()


# -- the run loop under each pair of strategies (PR 43) ----------------------
def other(pair):
    """The pair that keys every run by the strategy ``pair`` does not."""
    return tuple(SPREAD + BINPACK - axis for axis in pair)


def strategy_fleet(seed: int):
    """``mixed_gang(seed)`` (score ties, nodes of no GPU, nodes of no room,
    pods of no GPU beside pods of some) on nodes of three sizes, so that
    spread's free share orders them otherwise than bin-pack's free amount
    does.  Every share is a ratio of small whole numbers: two that differ
    differ in f32 too."""
    (alloc, *state), release, tasks, pattern = mixed_gang(seed)
    size = np.random.default_rng(5000 + seed).choice([4.0, 8.0, 16.0], N)
    alloc = np.where(alloc > 0, size[:, None] * POD, 0.0)
    return (alloc, *state), release, tasks, pattern


STRATEGY_SEEDS = range(24)


@pytest.mark.parametrize("f32_keys", (False, True), ids=("f64", "f32"))
@pytest.mark.parametrize("pair", PAIRS, ids=PAIR_IDS)
@pytest.mark.parametrize("seed, kind",
                         mask_cases(STRATEGY_SEEDS, STRATEGY_SEEDS[:8]))
def test_grouped_equals_scanned_under_each_pair_of_strategies(seed, kind,
                                                              pair, f32_keys):
    """A pipeline-only attempt never writes idle, so a run of identical
    pods lands by its first score under either strategy, among the nodes
    its mask row admits: the run loop keyed by the call's strategies
    answers as the exact scan under them and that mask does, in 64 bits
    and, the whole call, in 32 (as the chip runs it)."""
    nodes, release, tasks, pattern = strategy_fleet(seed)
    want, mask = grouped_equals_scanned(nodes, release, tasks, len(pattern),
                                        kind, seed, pair, f32_keys)
    if f32_keys and kind != "none":
        with jax.enable_x64(False):
            narrow = whole(nodes, release, tasks, mask=mask, pair=pair)
        assert narrow.tolist() == want


@pytest.mark.parametrize("pair", PAIRS, ids=PAIR_IDS)
def test_the_strategy_fleets_tell_the_pairs_apart(pair):
    """No vacuous agreement under any pair: both bits occur, a run's
    landing decides a later run's fit (counting the runs alone answers
    otherwise), landing by the other strategy's key answers otherwise, and
    so does changing the strategy of either axis alone."""
    bits, unlike, by_other, by_axis = [], 0, 0, [0, 0]
    for seed in STRATEGY_SEEDS:
        nodes, release, tasks, _ = strategy_fleet(seed)
        pool = pools(nodes[2], *release)
        got = np.asarray(grouped(pool, nodes, tasks, pair))
        bits += got.tolist()
        alone = runs_alone(pool, nodes, tasks)
        assert not (got & ~alone).any()
        unlike += int((alone != got).sum())
        by_other += int((np.asarray(
            grouped(pool, nodes, tasks, other(pair))) != got).sum())
        for axis in (0, 1):
            one = tuple(other(pair)[a] if a == axis else pair[a]
                        for a in (0, 1))
            by_axis[axis] += int((np.asarray(
                grouped(pool, nodes, tasks, one)) != got).sum())
    assert 0.15 < np.mean(bits) < 0.85
    assert unlike >= 10
    assert by_other >= 5 and min(by_axis) >= 2


def two_sized_nodes(axis: str):
    """``two_nodes([1, 0], [1, 1], 1)`` with node 1 half the size, asked by
    a gang of GPU pods (``axis`` "gpu") or of pods of no GPU, the same
    numbers in cpu ("cpu"): a worker of one unit, then a master of two.
    Bin-pack sends the worker to node 1, the fuller, and the master has
    node 0's two units: [True, True].  Spread sends it to node 0, the
    freer share, and each node is left one unit until prefix 1 releases a
    second on node 1: [False, True]."""
    unit = np.array([1000.0, 2.0 ** 30, 1.0])
    ask = unit * (1.0 if axis == "gpu" else np.array([1.0, 1.0, 0.0]))
    none = np.full((2, 1), -1, np.int32)
    nodes = (np.outer([8.0, 4.0], unit), np.outer([1.0, 0.0], unit),
             np.outer([1.0, 1.0], unit), none, none, np.full(2, 2.0))
    release = (np.array([1], np.int32), np.array([1], np.int32), unit[None])
    task_req = np.array([ask, 2 * ask, 0 * ask, 0 * ask])
    task_job = np.array([0, 0, 1, 1], np.int32)
    none = np.full((4, 1), -1, np.int32)
    return nodes, release, (task_req, task_job, none, none)


@pytest.mark.parametrize("pair", PAIRS, ids=PAIR_IDS)
def test_landing_by_the_other_strategys_key_flips_a_bit(pair):
    """The control of the comparison above, an axis at a time: on these
    two nodes the exact scan's verdict under ``pair`` is the run loop's
    under ``pair`` and is NOT the run loop's under the other key, for the
    gang that the GPU strategy scores and for the one the CPU's does."""
    by_strategy = {BINPACK: [True, True], SPREAD: [False, True]}
    for axis, strategy in zip(("gpu", "cpu"), pair):
        nodes, release, tasks = two_sized_nodes(axis)
        pool = pools(nodes[2], *release, k=2)
        want = np.asarray(scanned(pool, nodes, tasks, pair=pair)).tolist()
        assert want == by_strategy[strategy]
        for f32_keys in (False, True):
            assert np.asarray(grouped(pool, nodes, tasks, pair,
                                         f32_keys)).tolist() == want
            wrong = np.asarray(grouped(pool, nodes, tasks, other(pair),
                                          f32_keys)).tolist()
            assert wrong == by_strategy[other(pair)[axis == "cpu"]] != want
        assert whole(nodes, release, tasks, k=2, pair=pair).tolist() == want


@pytest.mark.parametrize("pair", PAIRS, ids=PAIR_IDS)
@pytest.mark.parametrize("rows", ("uniform", "mixed", "no-pods"))
def test_no_rows_are_scanned_with_a_mask_or_without(monkeypatch, rows, pair):
    """Whatever the rows, the host reads ``counted`` or ``grouped``, of a
    call whose mask tells no two pods apart as of an unmasked one, and the
    program traced under any pair of strategies holds the two forms."""
    nodes, release, tasks = fleet(3) if rows == "uniform" \
        else mixed_gang(3)[:3]
    if rows == "no-pods":
        tasks = (tasks[0], np.ones_like(tasks[1]), *tasks[2:])
    form, steps = form_of(tasks)
    # No pod differs from row 0 where there is none.
    assert form == {"uniform": "counted", "mixed": "grouped",
                    "no-pods": "counted"}[rows]
    assert steps == int(sb.gang_runs(*map(jnp.asarray, tasks))) \
        * (form == "grouped")
    mask = np.ones((len(tasks[0]), N), bool)
    assert form_of(tasks, mask) == (form, steps)
    traced = traced_forms(monkeypatch)
    sb.batch_prefix_feasibility.clear_cache()
    try:
        plain = whole(nodes, release, tasks, pair=pair)
        assert sorted(traced) == ["count_prefixes", "group_prefixes"]
        traced.clear()
        assert whole(nodes, release, tasks, pair=pair,
                     mask=jnp.asarray(mask)).tolist() == plain.tolist()
    finally:
        sb.batch_prefix_feasibility.clear_cache()
    assert sorted(traced) == ["count_prefixes", "group_prefixes"]


def lowered(nodes, release, tasks, mask=None):
    return sb.batch_prefix_feasibility.lower(
        *nodes, *release, *tasks, num_prefixes=K, task_node_mask=mask)


def test_an_unmasked_lowering_has_no_mask_operand():
    """``task_node_mask=None`` is a trace-time fact: the program lowered
    without a mask takes no ``[T,N]`` predicate (three cells run that
    program, and it is the parent's), where the masked one takes exactly
    that operand more."""
    nodes, release, tasks, _ = mixed_gang(5)
    t_pad = len(tasks[0])
    operands = [[(a.shape, a.dtype) for a in
                 jax.tree_util.tree_leaves(low.in_avals)]
                for low in (lowered(nodes, release, tasks),
                            lowered(nodes, release, tasks,
                                    np.ones((t_pad, N), bool)))]
    assert not [o for o in operands[0] if o[1] == bool]
    assert operands[1] == operands[0] + [((t_pad, N), np.dtype(bool))]


def defrag_fleet(seed: int):
    """The consolidation cell's gang at a width the CPU holds: one master
    row {36 cpu, 288 Gi, 8 GPU} and 127 worker rows {32 cpu, 256 Gi,
    8 GPU}, all of job 0, no padding row; 200 nodes of which 150 hold one
    job of two one-GPU pods, 20 hold four such jobs and 30 a whole-node
    pod; prefix k releases the jobs of a random order up to its k-th."""
    rng = np.random.default_rng(seed)
    n, k = 200, 192
    cap = np.array([64000.0, 512 * 2.0 ** 30, 8.0])
    jobs_on = np.array([1] * 150 + [4] * 20 + [0] * 30)
    rng.shuffle(jobs_on)
    used = np.where(jobs_on[:, None] > 0, 2 * jobs_on[:, None] * POD, cap)
    none = np.full((n, 1), -1, np.int32)
    nodes = (np.tile(cap, (n, 1)), cap - used, np.zeros((n, 3)), none, none,
             110.0 - np.where(jobs_on > 0, 2 * jobs_on, 1))
    order = rng.permutation(np.repeat(np.arange(n), jobs_on))[:k]
    m = 2 * k
    step = np.full(512, k, np.int32)
    step[:m] = np.repeat(np.arange(k), 2)
    node = np.zeros(512, np.int32)
    node[:m] = np.repeat(order, 2)
    vec = np.zeros((512, 3))
    vec[:m] = POD
    master = np.array([36000.0, 288 * 2.0 ** 30, 8.0])
    worker = np.array([32000.0, 256 * 2.0 ** 30, 8.0])
    task_req = np.vstack([master[None], np.tile(worker, (127, 1))])
    tasks = (task_req, np.zeros(128, np.int32),
             np.full((128, 1), -1, np.int32), np.full((128, 1), -1, np.int32))
    return nodes, (step, node, vec), tasks, k


def first_fit_verdict(nodes, release, tasks, k):
    """[K] bool by a numpy loop over the prefixes: the gang's pods, the
    master first, each onto the first node whose idle and releasing
    resources hold it (exact for pods that take a node each)."""
    _alloc, idle, rel, _labels, _taints, room = nodes
    out = []
    for pool in pools(rel, *release, k=k):
        free, left = idle + pool, room.copy()
        ok = True
        for req in tasks[0]:
            fit = np.flatnonzero(np.all(free >= req, axis=1) & (left > 0))
            if not fit.size:
                ok = False
                break
            free[fit[0]] -= req
            left[fit[0]] -= 1
        out.append(ok)
    return out


@pytest.mark.parametrize("seed", range(3))
def test_the_consolidation_cells_gang_is_scanned(seed):
    """A master beside its workers is no uniform gang: the program steps
    over its two runs (since PR 39; pod by pod before), and its verdict is
    the numpy loop's, bit for bit."""
    nodes, release, tasks, k = defrag_fleet(seed)
    assert not bool(sb.uniform_gang(*map(jnp.asarray, tasks)))
    assert form_of(tasks) == ("grouped", 2)
    assert int(sb.gang_runs(*map(jnp.asarray, tasks))) == 2
    want = first_fit_verdict(nodes, release, tasks, k)
    # The gang is seated from the prefix that empties its 128th node on.
    assert 0 < sum(want) < k and want == sorted(want)
    assert whole(nodes, release, tasks, k=k).tolist() == want
    pool = pools(nodes[2], *release, k=k)
    assert np.asarray(scanned(pool, nodes, tasks)).tolist() == want
    assert np.asarray(grouped(pool, nodes, tasks)).tolist() == want
    # Counted as 128 masters the same fleet reads the same here (a node
    # that holds a worker holds a master), which is why the form is chosen
    # from the rows and never from the answer.
    assert np.asarray(counted(pool, nodes, tasks)).tolist() == want


def reclaim_spec(claimer_tasks):
    jobs = {f"v{i}": {"queue": "b", "tasks": [
        {"gpu": 1, "status": "RUNNING", "node": "n1"}]} for i in range(8)}
    jobs["claimer"] = {"queue": "a", "tasks": claimer_tasks,
                       "min_available": len(claimer_tasks)}
    return {"nodes": {"n1": {"gpu": 8}},
            "queues": {"a": {"deserved": {"gpu": 4}},
                       "b": {"deserved": {"gpu": 4}}},
            "jobs": jobs}


SPREAD_GPUS = SchedulerConfig(gpu_placement_strategy="spread")


@pytest.mark.parametrize("form, claimer_tasks, config", [
    ("counted", [{"gpu": 4}], None),
    ("counted", [{"gpu": 1}] * 3, None),
    ("grouped", [{"gpu": 3}, {"gpu": 1}], None),
    ("grouped", [{"gpu": 2, "cpu": "2"}, {"gpu": 2, "cpu": "1"}], None),
    ("grouped", [{"gpu": 1}, {"gpu": 2}, {"gpu": 1}], None),
    ("grouped", [{"gpu": 3}, {"gpu": 1}], SPREAD_GPUS),
    ("counted", [{"gpu": 1}] * 3, SPREAD_GPUS),
], ids=("one-pod", "three-alike-and-a-pad", "master-and-worker",
        "cpu-differs", "master-between-workers", "spread-gpus",
        "three-alike-spread"))
def test_host_and_device_name_the_same_form(monkeypatch, form,
                                            claimer_tasks, config):
    """The span's ``form`` and ``runs`` and the two counters are the
    host's reading of the rows it sends; the kernel's ``cond`` and its run
    loop read the same rows by the same predicates."""
    from kai_scheduler_tpu.actions import solvers
    sent = {}
    run_on_nodes = solvers.propose.run_on_nodes

    def spy(ssn, kernel, operands, **kw):
        sent["rows"] = operands[3:]
        sent["verdict"] = run_on_nodes(ssn, kernel, operands, **kw)
        return sent["verdict"]

    monkeypatch.setattr(solvers.propose, "run_on_nodes", spy)
    ssn = build_session(reclaim_spec(claimer_tasks), config)
    families = ("scenario_prescreen_counted_total",
                "scenario_prescreen_scan_steps_total")
    before = [METRICS.counters.get(f, 0) for f in families]
    TRACER.begin_cycle(1)
    run_action(ssn, "reclaim")
    trace = TRACER.end_cycle()
    # Present after any dispatch, whichever form it took.
    counted_moved, steps_moved = (METRICS.counters[f] - b
                                  for f, b in zip(families, before))
    (span,) = [s for s in trace.spans if s.name == "solve:prescreen"
               and "declined" not in s.attrs]
    assert span.attrs["form"] == form
    assert counted_moved == (1 if form == "counted" else 0)
    rows = tuple(map(jnp.asarray, sent["rows"]))
    assert bool(sb.uniform_gang(*rows)) == (form == "counted")
    assert len(sent["rows"][0]) == span.attrs["t_pad"]
    runs = len({(i, str(t)) for i, t in enumerate(claimer_tasks)
                if i == 0 or t != claimer_tasks[i - 1]})
    assert int(sb.gang_runs(*rows)) == runs
    assert ("runs" in span.attrs) == (form == "grouped")
    if form == "grouped":
        assert span.attrs["runs"] == runs > 1
    assert steps_moved == {"counted": 0, "grouped": runs}[form]
