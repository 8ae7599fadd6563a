"""What a kernel has to move, from its shapes, and the chip's peaks.

The exact allocate kernel (``allocate_jobs_kernel``) is a scan of one step
per pod.  A step needs, whatever the implementation: the pod's row of the
score matrix and of the hard mask, and the node state it tests and
updates.  It does a few comparisons and a subtraction per node, so memory
traffic bounds it, not arithmetic.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json; "
            f"add it with its source, do not default it")
    return table[device_kind]


def exact_scan_bytes(steps: int, nodes: int, resources: int = 3,
                     has_mask: bool = True, label_cols: int = 0,
                     taint_cols: int = 0) -> float:
    """Bytes one call of the exact kernel must move, over ``steps`` real
    pods (the padding steps place nothing and are not counted as needed
    work; their time is in the kernel's time all the same).

    Per step, with f32 state: read the extra-score row [N] f32 and the mask
    row [N] bool; read allocatable, idle and releasing [N,R]; read pod room
    [N]; read the label and taint tables; the winner's update writes O(1).
    """
    per_step = nodes * 4                       # extra-score row
    per_step += nodes * 1 if has_mask else 0   # hard-mask row
    per_step += 3 * nodes * resources * 4      # allocatable, idle, releasing
    per_step += nodes * 4                      # pod room
    per_step += nodes * 4 * (label_cols + taint_cols)
    return float(steps) * per_step
