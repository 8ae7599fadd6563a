"""Record the small TPU trace that ``test_reduction.py`` reads.

Run on the chip (``chiprun -- python3 benchmark/tests/record_fixture.py``):
it traces two annotated calls of a tiny jitted scan with the harness's own
profiler options and writes the ``.xplane.pb`` to ``chiprun_out/``, from
where it is copied to ``benchmark/tests/data/tiny_tpu.xplane.pb``.
"""

from __future__ import annotations

import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.harness import trace as tr

    @jax.jit
    def tiny_scan(x):
        return jax.lax.scan(lambda c, row: (c * 0.999 + row, c.sum()),
                            x[0], x)

    x = jnp.ones((8, 512))
    jax.block_until_ready(tiny_scan(x))
    out = os.path.join(ROOT, "chiprun_out", "fixture_trace")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out, profiler_options=tr.profile_options())
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench:run_once"):
            jax.block_until_ready(tiny_scan(x))
    jax.profiler.stop_trace()
    path = tr.find_xplane(out)
    shutil.copy(path, os.path.join(ROOT, "chiprun_out",
                                   "tiny_tpu.xplane.pb"))
    raw = tr.read(path)
    print({"bytes": os.path.getsize(path),
           "devices": [(d["name"], len(d["modules"]), len(d["ops"]))
                       for d in raw["devices"]],
           "annotations": raw["annotations"],
           "modules": raw["devices"][0]["modules"] if raw["devices"] else []})
    return 0


if __name__ == "__main__":
    sys.exit(main())
