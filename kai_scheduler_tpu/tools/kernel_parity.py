"""Kernel-parity smoke: diff the grouped fill's two rungs against the
reference in one command.

For each seed, a randomized gang workload runs through:

- the fused-jnp rung (``fused_mode="jnp"``),
- the Pallas rung in interpreter mode (``fused_mode="pallas"``),
- the exact per-task kernel (``ops/allocate.allocate_jobs_kernel``), the
  reference,

and both rungs must agree with the reference bit-for-bit on placements,
pipelined flags and job success.  This is the ci_check.sh gate that
catches a fused-path drift WITHOUT waiting for the full pytest ring; at
`--seeds N` it doubles as a longer offline sweep.

Usage (ci_check.sh runs --smoke):

    JAX_PLATFORMS=cpu python -m kai_scheduler_tpu.tools.kernel_parity \
        [--smoke | --seeds N] [--nodes N]
"""

from __future__ import annotations

import argparse
import sys
import time


def _instance(seed: int, n_nodes: int, n_jobs: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    alloc = np.tile([8000.0, 64e9, 8.0], (n_nodes, 1))
    idle = alloc.copy()
    idle[:, 2] -= rng.integers(0, 6, n_nodes)
    rel = np.zeros((n_nodes, 3))
    rel[:, 2] = rng.integers(0, 3, n_nodes)
    labels = np.full((n_nodes, 1), -1, np.int32)
    labels[: n_nodes // 2, 0] = 0
    taints = np.full((n_nodes, 1), -1, np.int32)
    room = np.full(n_nodes, 110.0)
    reqs, jobs, sels = [], [], []
    for j in range(n_jobs):
        gang = int(rng.integers(1, 6))
        gpu = float(rng.integers(0, 4))
        s = 0 if rng.random() < 0.3 else -1
        for _ in range(gang):
            reqs.append([1000.0, 1e9, gpu])
            jobs.append(j)
            sels.append(s)
    allowed = np.ones(n_jobs, bool)
    if n_jobs > 2:
        allowed[int(rng.integers(n_jobs))] = False
    return (alloc, idle, rel, labels, taints, room, np.array(reqs),
            np.array(jobs, np.int32), np.array(sels, np.int32)[:, None],
            np.full((len(reqs), 1), -1, np.int32), allowed)


def run_seed(seed: int, n_nodes: int, n_jobs: int) -> list[str]:
    """One seed through every rung; returns mismatch descriptions."""
    import jax.numpy as jnp
    import numpy as np

    from ..ops.allocate import allocate_jobs_kernel
    from ..ops.allocate_grouped import allocate_grouped

    (alloc, idle, rel, labels, taints, room, req, job, sel, tol,
     allowed) = _instance(seed, n_nodes, n_jobs)
    nodes = tuple(map(jnp.asarray,
                      (alloc, idle, rel, labels, taints, room)))
    outs = {
        # kailint: disable=KAI004 — offline parity sweep, no Session to dispatch through
        mode: allocate_grouped(nodes, req, job, sel, tol, allowed,
                               fused_mode=mode)
        for mode in ("jnp", "pallas")
    }
    # kailint: disable=KAI004 — offline parity sweep, no Session to dispatch through
    exact = allocate_jobs_kernel(*nodes, jnp.asarray(req),
                                 jnp.asarray(job), jnp.asarray(sel),
                                 jnp.asarray(tol), jnp.asarray(allowed))
    problems = []
    for mode, out in outs.items():
        for field in ("placements", "pipelined", "job_success"):
            a = np.asarray(getattr(exact, field))
            b = np.asarray(getattr(out, field))
            if not (a == b).all():
                problems.append(
                    f"seed {seed}: {mode} != exact kernel on {field} "
                    f"({int((a != b).sum())} rows)")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("kai-kernel-parity")
    ap.add_argument("--seeds", type=int, default=6,
                    help="number of randomized workloads to sweep")
    ap.add_argument("--nodes", type=int, default=24)
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--smoke", action="store_true",
                    help="2-seed quick gate (the ci_check.sh step)")
    args = ap.parse_args(argv)
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    seeds = range(2 if args.smoke else args.seeds)

    failures = []
    t0 = time.perf_counter()
    for seed in seeds:
        problems = run_seed(seed, args.nodes, args.jobs)
        status = "ok  " if not problems else "FAIL"
        print(f"{status} seed {seed}  (jnp/pallas/exact agree)"
              if not problems else f"{status} seed {seed}", flush=True)
        for p in problems:
            print("     " + p, flush=True)
        failures += problems
    dt = time.perf_counter() - t0
    if failures:
        print(f"kernel parity: FAILED ({len(failures)} mismatch(es) "
              f"in {dt:.1f}s)")
        return 1
    print(f"kernel parity: both rungs bit-identical to the exact kernel "
          f"over {len(list(seeds))} seed(s) in {dt:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
