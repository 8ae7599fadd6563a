"""Continuous whole-fleet host profiler: folded stacks, ring-bounded.

The device path is instrumented to death (flight recorder spans, arena
pack stats) but the FLEET cycle — watch drain, pod re-parse, grouper and
status churn, binder round trips — burns its milliseconds in plain
Python between the spans.  This sampler answers "where do the host
milliseconds live" across *whole fleet cycles*, not just inside
``run_once``: a daemon thread walks every live thread's stack at a fixed
rate (default ~67Hz — deliberately off 100Hz so it never phase-locks
with 10ms-period work) and aggregates **collapsed stacks** (pprof folded
format, flamegraph.pl / speedscope ready).

The daemon's one sampling profiler (``--stackprof``,
``--enable-profiler``, ``KAI_STACKPROF=1``):

- frames are ``file.py:function`` WITHOUT line numbers — line-level
  frames explode one logical stack into dozens of series and defeat
  flame-graph aggregation;
- the table of distinct stacks is RING-BOUNDED (``KAI_STACKPROF_STACKS``,
  default 8192): a novel stack past the cap folds into a synthetic
  ``<stack-table-full>`` bucket and counts
  ``stackprof_dropped_stacks_total`` — a pathological workload degrades
  the profile's tail, never the daemon's memory;
- it is env-armable (``KAI_STACKPROF=1``) so bench children and chaos
  iterations profile without plumbing flags, and dump-on-stop
  (``KAI_STACKPROF_DIR``) writes the folded file where the ROADMAP's
  before/after comparisons want it.

Sampling is sigprof-free (pure ``threading`` + ``sys._current_frames``):
safe under JAX's C extensions where signal-based profilers misfire.

Served at ``GET /debug/flame`` (server.py); smoke-tested by
``python -m kai_scheduler_tpu.utils.stackprof --smoke`` (ci_check.sh),
which profiles a short embedded fleet burst and fails on empty output.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from .metrics import METRICS

OVERFLOW_STACK = "<stack-table-full>"


def _env_num(name: str, default: float, lo: float, hi: float) -> float:
    try:
        v = float(os.environ.get(name, default))
    except ValueError:
        return default
    return v if lo <= v <= hi else default


class StackProfiler:
    """Bounded collapsed-stack wall-clock sampler over all live threads."""

    def __init__(self, hz: float | None = None,
                 max_stacks: int | None = None, max_depth: int = 48,
                 clock=time.monotonic):
        self.hz = hz if hz is not None else \
            _env_num("KAI_STACKPROF_HZ", 67.0, 1.0, 1000.0)
        self.max_stacks = int(max_stacks) if max_stacks is not None else \
            int(_env_num("KAI_STACKPROF_STACKS", 8192, 16, 1 << 20))
        self.max_depth = max_depth
        self.clock = clock
        self.samples: dict[str, int] = {}
        self.total_samples = 0
        self.dropped_stacks = 0
        self.started_at = 0.0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._thread is not None

    def start(self) -> "StackProfiler":
        if self._thread is not None:
            return self
        self.started_at = self.clock()
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="stackprof")
        self._thread.start()
        return self

    def stop(self, dump: bool = True) -> None:
        """Stop sampling; when ``KAI_STACKPROF_DIR`` is set (and ``dump``)
        the folded profile is written there before the thread state
        clears."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        if dump:
            self.maybe_dump()

    # -- sampling ----------------------------------------------------------
    def _run(self) -> None:
        me = threading.get_ident()
        interval = 1.0 / self.hz
        while not self._stop.wait(interval):
            frames = sys._current_frames()
            new = 0
            with self._lock:
                for tid, frame in frames.items():
                    if tid == me:
                        continue
                    stack = []
                    depth = 0
                    while frame is not None and depth < self.max_depth:
                        code = frame.f_code
                        stack.append(
                            f"{code.co_filename.rsplit('/', 1)[-1]}:"
                            f"{code.co_name}")
                        frame = frame.f_back
                        depth += 1
                    if not stack:
                        continue
                    key = ";".join(reversed(stack))
                    if key not in self.samples \
                            and len(self.samples) >= self.max_stacks:
                        key = OVERFLOW_STACK
                        self.dropped_stacks += 1
                    self.samples[key] = self.samples.get(key, 0) + 1
                    self.total_samples += 1
                    new += 1
            if new:
                METRICS.inc("stackprof_samples_total", new)
            if self.dropped_stacks:
                METRICS.set_gauge("stackprof_dropped_stacks",
                                  float(self.dropped_stacks))

    # -- reporting ---------------------------------------------------------
    def folded(self, top: int = 5000) -> str:
        """pprof collapsed format: ``frame;frame;... count`` per line,
        heaviest first — pipe into flamegraph.pl or drop into
        speedscope.app."""
        with self._lock:
            rows = sorted(self.samples.items(), key=lambda kv: -kv[1])
        return "\n".join(f"{stack} {count}"
                         for stack, count in rows[:top])

    # Leaves that mean "a thread parked waiting", not work: pool workers
    # blocking on their queues and accept loops would otherwise dominate
    # every leaf aggregation and hide the actual bottleneck.
    IDLE_LEAVES = frozenset((
        "threading.py:wait", "threading.py:_wait_for_tstate_lock",
        "queue.py:get", "selectors.py:select",
        "socketserver.py:serve_forever", "socketserver.py:get_request"))

    def top_frames(self, top: int = 10,
                   exclude_idle: bool = True) -> list[dict]:
        """Leaf-frame aggregation — the "what is the fleet bottleneck"
        one-liner bench.py embeds next to the latency numbers.  Shares
        are of ALL samples, so busy leaves still read small on a mostly
        idle fleet."""
        leaves: dict[str, int] = {}
        with self._lock:
            for stack, count in self.samples.items():
                leaf = stack.rsplit(";", 1)[-1]
                if exclude_idle and leaf in self.IDLE_LEAVES:
                    continue
                leaves[leaf] = leaves.get(leaf, 0) + count
            total = self.total_samples
        return [{"frame": frame, "samples": count,
                 "share": round(count / total, 4) if total else 0.0}
                for frame, count in sorted(leaves.items(),
                                           key=lambda kv: -kv[1])[:top]]

    def status(self) -> dict:
        with self._lock:
            return {"running": self.running,
                    "hz": self.hz,
                    "samples": self.total_samples,
                    "distinct_stacks": len(self.samples),
                    "stack_cap": self.max_stacks,
                    "dropped_stacks": self.dropped_stacks,
                    "running_seconds": round(
                        self.clock() - self.started_at, 1)
                    if self.started_at else 0.0}

    def maybe_dump(self, out_dir: str | None = None) -> str | None:
        """Write the folded profile to ``out_dir`` (default
        ``KAI_STACKPROF_DIR``); returns the path, or None when no dir is
        armed.  IO happens outside the sample lock."""
        out_dir = out_dir or os.environ.get("KAI_STACKPROF_DIR")
        if not out_dir:
            return None
        body = self.folded(top=self.max_stacks)
        try:
            os.makedirs(out_dir, exist_ok=True)
            path = os.path.join(out_dir,
                                f"stackprof_{os.getpid()}.folded")
            with open(path, "w") as fh:
                fh.write(body + "\n")
            return path
        except OSError:
            METRICS.inc("stackprof_dump_errors_total")
            return None

    def reset(self) -> None:
        with self._lock:
            self.samples.clear()
            self.total_samples = 0
            self.dropped_stacks = 0


# Process-wide profiler, like METRICS/TRACER/LIFECYCLE: the server, the
# bench fleet phase, and env arming all share one instance so /debug/flame
# always shows whatever is currently collected.
STACKPROF = StackProfiler()


def ensure_started_from_env() -> bool:
    """Arm the shared profiler when ``KAI_STACKPROF`` is truthy (1/true/
    yes/on); returns whether it is running afterwards."""
    val = (os.environ.get("KAI_STACKPROF") or "").strip().lower()
    if val in ("1", "true", "yes", "on"):
        STACKPROF.start()
    return STACKPROF.running


def _smoke() -> int:
    """Profile a short embedded fleet burst and assert a non-empty folded
    profile whose frames include the scheduler pipeline — the CI gate
    that keeps the profiler able to see the fleet loop."""
    from ..controllers import System, SystemConfig, make_pod
    from ..controllers.podgrouper import POD_GROUP_LABEL

    prof = StackProfiler(hz=250.0, max_stacks=4096)
    prof.start()
    system = System(SystemConfig())
    for i in range(60):
        system.api.create({
            "kind": "Node", "metadata": {"name": f"n{i:03d}"}, "spec": {},
            "status": {"allocatable": {"cpu": "32", "memory": "256Gi",
                                       "nvidia.com/gpu": 8, "pods": 110}}})
    system.api.create({"kind": "Queue", "metadata": {"name": "q"},
                       "spec": {}})
    for j in range(4):
        system.api.create({"kind": "PodGroup",
                           "metadata": {"name": f"pg{j}"},
                           "spec": {"queue": "q", "minMember": 20}})
        for k in range(20):
            system.api.create(make_pod(
                f"p{j}-{k:03d}", labels={POD_GROUP_LABEL: f"pg{j}"},
                gpu=1 if j % 2 == 0 else 0))
    for _ in range(3):
        system.run_cycle()
    prof.stop(dump=False)
    body = prof.folded()
    ok = bool(body.strip()) and prof.total_samples > 0
    print(f"stackprof smoke: {prof.total_samples} samples, "
          f"{len(prof.samples)} stacks "
          f"({'OK' if ok else 'EMPTY PROFILE'})")
    for row in prof.top_frames(5):
        print(f"  {row['share']:6.1%}  {row['frame']}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(_smoke() if "--smoke" in sys.argv else 0)
