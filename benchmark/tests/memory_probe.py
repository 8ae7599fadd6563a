"""Controls for ``run.py``'s ``memory_peak``, on the chip (one chip; not
tier-1, not collected by pytest):

    python3 benchmark/tests/memory_probe.py [--seed N] [--out DIR]

Each control runs in a process of its own (a process's peaks never fall
again), reads the chip through ``run.memory_peak`` and is held to a range:

  a  a 4 GiB client buffer alive                   4 GiB within 1 %
  b  a program of 512 bytes of arguments whose     8 GiB .. its temporaries
     temporaries are 8 GiB, after it ran           + what buffers held
  c  a's buffer deleted, then b, in one process    under 9 GiB (not 12)
  d  batch_prefix_feasibility at K = 1,024,        what ``preflight.py`` says
     N = 98,304, t_pad 256, R = 3, rows 2,048      the program reserves,
     (the reclaim prescreen at the north star's    within 2 %
     width)
  e  d beside a live 3 GiB buffer                  3 GiB + d, within 2 %:
                                                   both at one instant

Beside each figure it prints the parts, the compiler's ``memory_analysis()``
of the program as compiled on the chip, and the largest ``bytes_in_use +
bytes_reserved`` that a thread polling ``memory_stats()`` saw while the
program ran: what the chip really held, which the figure may not pass.
The parent never touches JAX.  Exit code 0 where every control is inside
its range, 1 where one is not, 3 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GIB = 2 ** 30
CONTROLS = ("a", "b", "c", "d", "e")
# The prescreen's shape at the north star's width (PERF.md section 7).
PRESCREEN = {"prefixes": 1024, "rows": 2048, "nodes": 98304, "resources": 3,
             "t_pad": 256, "gang": 128}


def analysis(compiled) -> dict:
    """The compiler's ``memory_analysis()``, and what ``preflight.py``
    says the runtime will reserve for it."""
    from benchmark import preflight
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "peak_memory_in_bytes") if hasattr(m, k)}
    out["preflight_reserved_bytes"] = int(preflight.reserved_bytes(m))
    return out


def sampled(device, thunk):
    """``thunk()``'s result, and the most the chip held while it ran by a
    thread that polls ``memory_stats()``."""
    from benchmark import run
    most, stop = [0], threading.Event()

    def poll():
        while not stop.is_set():
            most[0] = max(most[0], run.device_memory(
                device.memory_stats())["memory_at_read_bytes"])
            time.sleep(0.02)
    thread = threading.Thread(target=poll, daemon=True)
    thread.start()
    try:
        out = thunk()
    finally:
        stop.set()
        thread.join()
    return out, most[0]


def client_buffer(gib: int, seed: int):
    """A client's buffer of ``gib`` GiB of f32, made on the device."""
    import jax
    import jax.numpy as jnp
    x = jnp.full((gib * GIB // 4,), float(seed % 97 + 1), jnp.float32)
    return jax.block_until_ready(x)


def temporaries_program(seed: int):
    """512 bytes in, 512 bytes out, and between them a 4 GiB scatter and
    its running sum: 8 GiB of temporaries.  (compiled, operands)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def control(idx):
        z = jnp.zeros((GIB,), jnp.float32).at[idx].set(1.0)
        return jnp.cumsum(z)[idx]
    idx = np.random.default_rng(seed).choice(GIB, 128, replace=False)
    idx = jnp.asarray(np.sort(idx).astype(np.int32))
    return jax.jit(control).lower(idx).compile(), (idx,)


def prescreen_program(seed: int):
    """The scenario prescreen as ``_prefix_prescreen`` dispatches it, on a
    full fleet of the KWOK node shape: step k releases two one-GPU pods on
    nodes drawn from the seed, and a gang of ``gang`` one-GPU pods fits
    from the prefix that holds ``gang`` releases on.  (compiled, operands,
    the number of feasible prefixes)."""
    import jax.numpy as jnp
    import numpy as np
    from kai_scheduler_tpu.ops.scenario_batch import \
        batch_prefix_feasibility
    from kai_scheduler_tpu.ops.scoring import BINPACK
    s = PRESCREEN
    n, r, t, m, k = (s["nodes"], s["resources"], s["t_pad"], s["rows"],
                     s["prefixes"])
    f, i = np.float32, np.int32
    pod = np.array([4000.0, 32.0 * GIB, 1.0], f)
    node = np.array([64000.0, 512.0 * GIB, 8.0], f)
    rng = np.random.default_rng(seed)
    task_req = np.zeros((t, r), f)
    task_req[:s["gang"]] = pod
    task_job = np.ones(t, i)
    task_job[:s["gang"]] = 0
    operands = [
        np.tile(node, (n, 1)), np.zeros((n, r), f), np.zeros((n, r), f),
        np.full((n, 1), -1, i), np.full((n, 1), -1, i), np.full(n, 102.0, f),
        (np.arange(m) // 2).astype(i),
        rng.choice(n, m, replace=False).astype(i), np.tile(pod, (m, 1)),
        task_req, task_job, np.full((t, 1), -1, i), np.full((t, 1), -1, i)]
    operands = [jnp.asarray(x) for x in operands]
    compiled = batch_prefix_feasibility.lower(
        *operands, num_prefixes=k, gpu_strategy=BINPACK,
        cpu_strategy=BINPACK).compile()
    return compiled, operands, k - (s["gang"] // 2 - 1)


def child(control: str, seed: int) -> int:
    import jax
    import numpy as np
    from benchmark import run
    from kai_scheduler_tpu.utils.compile_cache import enable_compile_cache
    device = jax.local_devices()[0]
    if device.platform == "cpu":
        print("no accelerator: the controls read a chip's memory",
              file=sys.stderr)
        return 3
    enable_compile_cache()
    out = {"control": control, "seed": seed, "kind": device.device_kind}
    buf = None                       # alive until the figure is read
    if control in ("a", "c"):
        buf = client_buffer(4, seed)
    if control == "e":
        buf = client_buffer(3, seed)
    if control == "c":
        out["with_the_buffer"] = run.memory_peak(1)
        buf.delete()
        buf = None
    if control in ("b", "c"):
        compiled, operands = temporaries_program(seed)
    elif control in ("d", "e"):
        compiled, operands, out["feasible_expected"] = \
            prescreen_program(seed)
    if control != "a":
        out["compiled"] = analysis(compiled)
        out["before"] = run.memory_peak(1)
        t0 = time.perf_counter()
        result, out["held_while_running_bytes"] = sampled(
            device, lambda: np.asarray(compiled(*operands)))
        out["seconds"] = round(time.perf_counter() - t0, 3)
        if "feasible_expected" in out:
            out["feasible"] = int(result.sum())
    out["figure"] = run.memory_peak(1)
    out["memory_stats"] = {k: int(v) for k, v in
                           (device.memory_stats() or {}).items()}
    print(json.dumps(out), flush=True)
    return 0


def judge(results: dict) -> dict:
    """{control: [figure, low, high]} for the controls that ran."""
    def fig(c):
        return results[c]["figure"]["memory_peak_bytes"]

    def reserved(c):
        return results[c]["compiled"]["preflight_reserved_bytes"]
    ranges = {}
    if "a" in results:
        ranges["a"] = [fig("a"), 0.99 * 4 * GIB, 1.01 * 4 * GIB]
    if "b" in results:
        in_use = results["b"]["figure"]["memory_in_use_peak_bytes"]
        ranges["b"] = [fig("b"), 8 * GIB, in_use
                       + results["b"]["compiled"]["temp_size_in_bytes"]]
    if "c" in results:
        ranges["c"] = [fig("c"), 8 * GIB, 9 * GIB]
    if "d" in results:
        ranges["d"] = [fig("d"), 0.98 * reserved("d"), 1.02 * reserved("d")]
    if "e" in results:
        both = 3 * GIB + reserved("e")
        ranges["e"] = [fig("e"), 0.98 * both, 1.02 * both]
    return ranges


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=3434000001)
    ap.add_argument("--control", choices=CONTROLS, action="append")
    ap.add_argument("--child", choices=CONTROLS, help=argparse.SUPPRESS)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "memory_probe"))
    args = ap.parse_args(argv)
    if args.child:
        return child(args.child, args.seed)
    os.makedirs(args.out, exist_ok=True)
    results = {}
    for control in args.control or CONTROLS:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", control,
             "--seed", str(args.seed)],
            cwd=ROOT, capture_output=True, text=True, timeout=600)
        if proc.returncode == 3:
            sys.stderr.write(proc.stderr[-2000:])
            return 3
        if proc.returncode != 0 or not proc.stdout.strip():
            sys.stderr.write(proc.stderr[-4000:])
            print(f"control {control}: exit code {proc.returncode}")
            results[control] = None
            continue
        results[control] = json.loads(proc.stdout.strip().splitlines()[-1])
    ran = {c: r for c, r in results.items() if r}
    ranges = judge(ran)
    bad = [c for c in results if c not in ranges]
    for c, (value, low, high) in ranges.items():
        r = ran[c]
        ok = low <= value <= high
        if "feasible" in r and r["feasible"] != r["feasible_expected"]:
            ok = False
        # The figure may never pass what the chip held (1 MiB of slack for
        # what the runtime allocates between the poll and the read).
        seen = max(r.get("held_while_running_bytes", 0),
                   r["figure"]["memory_at_read_bytes"],
                   r["figure"]["memory_in_use_peak_bytes"])
        if value > seen + 2 ** 20:
            ok = False
        bad += [] if ok else [c]
        print(f"control {c}: {value / GIB:.4f} GiB "
              f"(range {low / GIB:.4f} .. {high / GIB:.4f}) "
              f"{'ok' if ok else 'OUTSIDE'}  parts: " + ", ".join(
                  f"{k}={v:,}" for k, v in r["figure"].items())
              + (f"; held while running {r['held_while_running_bytes']:,}"
                 f"; compiled {r['compiled']}; {r['seconds']} s"
                 if "compiled" in r else "")
              + (f"; feasible {r['feasible']} of expected "
                 f"{r['feasible_expected']}" if "feasible" in r else ""))
    with open(os.path.join(args.out, f"memory_probe_{args.seed}.json"),
              "w") as f:
        json.dump({"results": results, "ranges": ranges, "outside": bad},
                  f, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
