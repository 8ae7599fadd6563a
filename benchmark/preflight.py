"""Reckon every cell's device bytes before any run, on the CPU sandbox.

The driver refuses a cell whose fullest device peaks under a quarter of a
chip's memory (4.0 GiB of a v5e's 16), and a chip run that finds that out
has already been paid for.  So before a cell is asked for: reckon what its
kernel holds on the device from the configuration and traffic files, and,
where the TPU compiler can describe a ``v5e:2x2`` chip here, compile the
kernel at its timed shape and read ``memory_analysis()``.  Exits non-zero
where a cell is under the floor.  Costs no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/preflight.py [--no-compile]
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


def cell_shape(cell) -> dict:
    from benchmark.harness import cluster as gen
    t = gen.gang_size(cell.traffic)
    return {"t": t, "t_pad": gen.padded(t),
            "nodes": int(cell.config["nodes"]["count"]),
            "has_mask": bool(cell.traffic["gang"].get("topology"))}


def reckoned_bytes(shape: dict) -> float:
    """The exact kernel's dense matrices over the gang's own rows (the
    rows that padding to a power of two adds hold nothing and are not
    counted): the f32 score matrix, the bool hard mask where the gang has
    a node subset, and the node tables."""
    cells = shape["t"] * shape["nodes"]
    return cells * 4 + (cells if shape["has_mask"] else 0) \
        + shape["nodes"] * 4 * (3 * 3 + 3)


def compiled_bytes(shape: dict):
    """arguments + outputs + temporaries of ``allocate_jobs_kernel`` at
    the cell's shape, compiled for a described v5e chip; None where the
    topology cannot be described here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler here: say so, do not guess
        print(f"  (no v5e:2x2 topology can be described here: {exc})")
        return None
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape_, dtype):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=chip)

    n, t = shape["nodes"], shape["t_pad"]
    f32, i32 = jnp.float32, jnp.int32
    compiled = allocate_jobs_kernel.lower(
        sds((n, 3), f32), sds((n, 3), f32), sds((n, 3), f32),
        sds((n, 1), i32), sds((n, 1), i32), sds((n,), f32),
        sds((t, 3), f32), sds((t,), i32), sds((t, 1), i32), sds((t, 1), i32),
        sds((2,), jnp.bool_), sds((t, n), f32),
        task_node_mask=sds((t, n), jnp.bool_) if shape["has_mask"] else None,
        gpu_strategy=0, cpu_strategy=0, allow_pipeline=True,
        pipeline_only=False).compile()
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def main(argv=None) -> int:
    from benchmark.harness import spec
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    bad = 0
    for w in bench["workloads"]:
        cell = spec.Cell(bench, w["name"])
        shape = cell_shape(cell)
        reck = reckoned_bytes(shape)
        line = (f"{w['name']}: allocate_jobs_kernel {shape['t_pad']} x "
                f"{shape['nodes']} ({shape['t']} rows live), "
                f"mask={shape['has_mask']}: reckoned live "
                f"{reck / GIB:.2f} GiB")
        smallest = reck
        if not args.no_compile:
            comp = compiled_bytes(shape)
            if comp is not None:
                line += f", compiled for v5e {comp / GIB:.2f} GiB"
                smallest = min(smallest, comp)
        ok = smallest >= FLOOR_BYTES
        print(line + ("" if ok else "  UNDER THE 4.00 GiB FLOOR"))
        bad += not ok
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
