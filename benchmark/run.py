"""Run one cell of the benchmark: ``--workload --seed --seconds --trace``.

One run is one process: build the fleet from the seed, prime the cell's
kernel, warm cycles, the measured window, the comparison that decides
``correct``, and one JSON object as the last line of standard output.
What belongs to the cell (the client of the loop, the prime, the
comparison, its kernels' shapes) is the generator's that the cell's traffic
file names; this file keeps what every cell shares.  See
``benchmark/README.md`` for the run's anatomy.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Cycles of the window that a --trace 1 run records with the profiler.
TRACE_CYCLES = 1
TRACE_DIR = os.path.join(ROOT, "benchmark", ".trace")


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform == "cpu"
                         or len(devices) < chips):
        log(f"no accelerator for this cell: platform "
            f"{devices[0].platform}, {len(devices)} device(s), "
            f"{chips} needed")
        raise SystemExit(3)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def device_memory(stats: dict | None) -> dict:
    """One device's figure and its parts, from ONE ``memory_stats()`` call.

    This runtime keeps two books (PERF.md section 6, PR 34).  A client's
    buffers (operands, results, anything ``jnp.asarray`` made) count under
    ``bytes_in_use``; what a loaded program reserves for its temporaries
    counts under ``bytes_reserved``, never under ``bytes_in_use``, and is
    carved out of what the buffers leave free, so the two never overlap.
    The peaks of both are high-water marks of the whole process and need
    not have fallen at one instant, so they are never added.  The figure
    is the largest of three amounts the chip did hold at some instant:
    the peak of buffers, the peak of reservations, and buffers plus
    reservation as this one call reads them.  It cannot overstate; where
    buffers and a reservation peaked together earlier than this call and
    apart from each other's peak, it understates.  A backend without a key
    reads 0 there (the CPU's ``memory_stats()`` is None: all 0)."""
    stats = stats or {}
    in_use = int(stats.get("peak_bytes_in_use", 0))
    reserved = int(stats.get("peak_bytes_reserved", 0))
    at_read = (int(stats.get("bytes_in_use", 0))
               + int(stats.get("bytes_reserved", 0)))
    return {"memory_peak_bytes": max(in_use, reserved, at_read),
            "memory_in_use_peak_bytes": in_use,
            "memory_reserved_peak_bytes": reserved,
            "memory_at_read_bytes": at_read}


def memory_peak(chips: int) -> dict:
    """``device_memory`` of the fullest of the cell's ``chips`` devices:
    ``memory_peak_bytes``, which the driver reads, and its parts."""
    import jax
    return max((device_memory(d.memory_stats())
                for d in jax.local_devices()[:max(1, chips)]),
               key=lambda m: m["memory_peak_bytes"])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, root: str = ROOT) -> dict:
    """Everything a run does; returns the result object."""
    try:
        from kai_scheduler_tpu.utils.compile_cache import \
            enable_compile_cache
    except ImportError as exc:
        log(f"the scheduler is not in this checkout: {exc}")
        raise SystemExit(4)
    from benchmark.harness import loop, readers, spec
    from benchmark.harness import trace as tr

    cell = spec.Cell(spec.load_benchmark(root), workload, root)
    gen = cell.generator
    device = device_info(cell.chips, require_chip)
    cache_dir = enable_compile_cache()
    watch = loop.CompileWatch()
    wall = {"import_s": time.perf_counter() - T_PROCESS}

    t = time.perf_counter()
    client = gen.build(cell, seed,
                       counters=readers.counters_wanted(cell.per_layer))
    wall["build_s"] = time.perf_counter() - t

    t = time.perf_counter()
    primed = gen.prime(client, watch)
    wall["prime_s"] = time.perf_counter() - t

    guard0 = loop.guard_counters()
    t = time.perf_counter()
    warm = []
    for _ in range(int(cell.traffic.get("warm_cycles", 1))):
        c0 = watch.snapshot()
        client.cycle()
        warm.append(watch.since(c0))
    wall["warm_s"] = time.perf_counter() - t
    guard_warm = loop.guard_counters()

    compiles0 = watch.snapshot()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    t_window = time.perf_counter()
    setup_s = t_window - T_PROCESS
    window = loop.measure(client, seconds,
                          trace_cycles=TRACE_CYCLES if trace else 0,
                          trace_dir=TRACE_DIR if trace else None)
    wall["window_s"] = window["elapsed_s"]
    in_window = watch.since(compiles0)
    guard1 = loop.guard_counters()
    device.update(memory_peak(cell.chips))

    records = client.records[window["first"]:]
    ledger = client.ledger
    kernel_shapes = gen.kernel_shapes(client)
    client.close()

    t = time.perf_counter()
    verdict = gen.compare(records, ledger, cell)
    wall["compare_s"] = time.perf_counter() - t

    cycles = len(records)
    attempted, failed = verdict["attempted"], verdict["failed"]
    guard_moved = loop.moved(guard0, guard1)
    window_compiles = in_window["compiles"]
    if guard_moved or window_compiles:
        # The guard fell back, timed out or refused a result, or something
        # compiled inside the window: nothing this run attempted counts.
        failed = attempted
    elapsed = window["elapsed_s"]

    result = {"correct": bool(verdict["correct"]), "attempted": attempted,
              "failed": failed}
    run = {"records": records, "device_kind": device["kind"],
           "traced_cycles": window["traced_cycles"],
           "generator": gen, "kernel_shapes": kernel_shapes}
    if trace:
        t = time.perf_counter()
        path = tr.find_xplane(TRACE_DIR)
        raw = tr.read(path) if path else {"devices": [], "annotations": []}
        reduced = tr.reduce(raw, window["traced_s"])
        wall["trace_reduce_s"] = time.perf_counter() - t
        trace_bytes = os.path.getsize(path) if path else 0
        run["reduced"] = reduced
        result["metrics"] = readers.read_all(cell.per_layer, run)
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": tr.top(reduced["ops"] or reduced["programs"]),
                "idle_gaps": tr.name_gaps(
                    reduced, raw, spans_on_trace_clock(records, raw))}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        values = {
            "cycle_ms": 1e3 * elapsed / cycles,
            "pods_bound_per_s": verdict["bound_pods"] / elapsed,
            "setup_s": setup_s,
        }
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    result["device"] = device
    wall["total_s"] = time.perf_counter() - T_PROCESS
    result["run"] = {
        "wall_s": {k: round(v, 3) for k, v in wall.items()},
        "cycles_in_window": cycles, "cycle_s": window["cycle_s"],
        "compile_cache": cache_dir,
        "generator": cell.traffic["generator"],
        "reference": cell.config["reference"],
        "scheduler": cell.config["scheduler"],
        "primed": primed, "warm_cycles": warm,
        "window_compiles": window_compiles,
        "window_compiled": in_window["compiled"],
        "guard_moved_in_warm": loop.moved(guard0, guard_warm),
        "guard_moved": guard_moved, **verdict["run"]}
    if trace:
        result["run"]["trace_bytes"] = trace_bytes
    result["compared"] = verdict["compared"]
    return result


def spans_on_trace_clock(records, raw):
    """For the annotation at index i of the trace: if it is a cycle's
    ``bench:run_once``, the program's flight-recorder spans of that cycle
    as (name, start_ns, end_ns) on the profiler's clock.  Both clocks are
    read at ``run_once``'s entry, which ties them."""
    ann = raw["annotations"]

    def spans_of(i: int):
        if ann[i][0] != "bench:run_once":
            return []
        cycle = sum(1 for a in ann[:i] if a[0] == "bench:run_once")
        if cycle >= len(records):
            return []
        rec = records[cycle]
        origin = ann[i][1] + (rec.trace_t0 - rec.t_sched) * 1e9
        return [(name, origin + start * 1e9, origin + (start + dur) * 1e9)
                for name, _k, _sid, _p, start, dur in rec.spans
                if name != "cycle"]
    return spans_of


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps({"run": result["run"]}), flush=True)
    compared = result["compared"]
    log("compared (value, limit): " + ", ".join(
        f"{k}={v[0]:g} (<= {v[1]:g})" for k, v in compared.items()))
    log(f"correct={result['correct']} attempted={result['attempted']} "
        f"failed={result['failed']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
