"""Reckon every cell's device bytes before any run, on the CPU sandbox.

The driver refuses a NEW cell whose fullest device peaks under a quarter
of a chip's memory (4.0 GiB of a v5e's 16), and a chip run that finds that
out has already been paid for.  So before a cell is asked for, its two
kinds of device memory are printed apart, as ``run.py``'s ``memory_peak``
will read them on the chip (``device_memory`` there says why they are two):

- the client's buffers: what the cell's generator reckons, from the
  configuration and traffic files, that its cycle keeps in arrays on the
  device (``reckon``: ``bytes``);
- the program's reservation: where the TPU compiler can describe a
  ``v5e:2x2`` chip here, the cycle's kernel is compiled at its timed shape
  (``compile_for``) and its ``memory_analysis()`` read (``reserved_bytes``):
  the smaller of ``temp_size_in_bytes``, which adds temporaries that never
  live together (7.69 GiB for the prescreen at 98,304 nodes, where the
  chip reserves 6.47), and ``peak_memory_in_bytes`` less the arguments and
  outputs (6.47 GiB there, but 19 MB for the exact scan, which reserves
  0.85).  The sum is printed beside it.  With ``--no-compile`` it is what
  the generator reckons (``program_bytes``).

A cell is judged by the larger of the two, which is ``memory_peak``'s
rule where it cannot see one instant: it never says "over" for a cell the
chip would read under.  Costs no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/preflight.py [--no-compile]
        [--workload <cell> ...] [--root <dir with BENCHMARK.json>]

Every cell is printed.  The exit code judges the cells named with
``--workload``, which is how the author of a new cell calls it: non-zero
where one of them is under the floor.  An admitted cell is not held to
the floor, so with none named the exit code is 0.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GIB = 2.0 ** 30
FLOOR_BYTES = 4.0 * GIB


def described_chip():
    """``sds(shape, dtype)`` making 32-bit operands on one described v5e
    chip; None where the topology cannot be described here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler here: say so, do not guess
        print(f"  (no v5e:2x2 topology can be described here: {exc})")
        return None
    chip = SingleDeviceSharding(topo.devices[0])
    narrow = {np.dtype(np.float64): np.float32, np.dtype(np.int64): np.int32}

    def sds(shape, dtype):
        dtype = np.dtype(dtype)
        return jax.ShapeDtypeStruct(shape, narrow.get(dtype, dtype),
                                    sharding=chip)
    return sds


def reserved_bytes(m) -> float:
    """What the runtime reserves for a program whose ``memory_analysis()``
    is ``m``, at the most: the smaller of its temporaries summed and its
    peak less its arguments and outputs.  On the chip (PR 34) the prescreen
    at 98,304 nodes reserved 6,946,799,616 bytes (summed 8,256,042,496,
    peak less operands 6,946,176,512) and the exact scan at 65,536 nodes
    851,968 (summed 1,097,216, peak less operands 18,980,864)."""
    peak = getattr(m, "peak_memory_in_bytes", 0)
    summed = float(m.temp_size_in_bytes)
    if not peak:
        return summed
    return min(summed, float(peak - m.argument_size_in_bytes
                             - m.output_size_in_bytes))


def judged_bytes(client: float, program: float) -> float:
    """``memory_peak``'s rule on the two parts: the larger, never the sum
    (their peaks need not fall at one instant)."""
    return max(client, program)


def size(b: float) -> str:
    return f"{b / GIB:.2f} GiB" if b >= 0.01 * GIB else f"{b / 1e6:.1f} MB"


def main(argv=None) -> int:
    from benchmark.harness import spec
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--workload", action="append", default=[],
                    help="a cell the exit code judges (may repeat)")
    ap.add_argument("--root", default=ROOT,
                    help="the directory that holds BENCHMARK.json")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark(args.root)
    names = [w["name"] for w in bench["workloads"]]
    for name in args.workload:
        if name not in names:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json "
                             f"has {sorted(names)}")
    sds = None if args.no_compile else described_chip()
    bad = 0
    for name in names:
        cell = spec.Cell(bench, name, args.root)
        reck = cell.generator.reckon(cell)
        line = (f"{name}: {reck['what']}: client's buffers reckoned "
                f"{size(reck['bytes'])}")
        if sds is not None:
            m = cell.generator.compile_for(cell, sds).memory_analysis()
            program = reserved_bytes(m)
            line += (f"; program compiled for v5e reserves {size(program)} "
                     f"(temporaries summed {size(m.temp_size_in_bytes)})")
        else:
            program = float(reck.get("program_bytes", 0.0))
            line += f"; program's temporaries reckoned {size(program)}"
        if judged_bytes(reck["bytes"], program) < FLOOR_BYTES:
            judged = name in args.workload
            line += ("  UNDER THE 4.00 GiB FLOOR" if judged else
                     "  (under the 4.00 GiB floor for a new cell, which "
                     "an admitted cell is not held to)")
            bad += judged
        print(line)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
