"""Device-guard: fault-tolerant dispatch of device-kernel calls.

The scheduler's latency-critical cycle puts a JAX/XLA device in the middle
of every placement decision — and a hung PJRT client blocks in C where no
in-process alarm can interrupt it.  Production AI-cluster schedulers
treat accelerator-path
failure as a first-class *degraded mode*, not a crash.  This module gives
the fleet that property:

- **Watchdog deadlines**: every guarded call runs on a worker thread; the
  calling (cycle) thread waits at most ``deadline_s`` and abandons the
  worker on expiry, so a hung XLA call can never block a cycle.
- **Bounded retry** with exponential backoff + deterministic jitter for
  transient device errors.
- **Circuit breaker**: after ``breaker_threshold`` consecutive failures the
  guard trips OPEN and routes calls straight to the CPU fallback path
  (re-running the same computation pinned to the host backend).  After
  ``breaker_cooloff_s`` it half-open-probes one call back through the
  device; success closes the breaker, failure re-opens it.
- **Deterministic fault injection** (``KAI_FAULT_INJECT`` env or the
  daemon's ``--fault-inject`` flag): ``hang``, ``slow:<ms>``, ``error``,
  ``flaky:<p>``, ``badshape`` — so all of the above is unit-testable
  without a real TPU (the chaos ring, tests/test_device_guard.py).

Observability: counters ``device_guard_{timeouts,retries,trips,probes,
fallback_calls,bad_results}`` and the gauge ``device_guard_state``
(0=closed, 1=half-open, 2=open) land in utils.metrics; state is exposed on
the daemon's ``/healthz`` (degraded, not dead).  See docs/DEGRADATION.md
for the full degraded-mode contract.
"""

from __future__ import annotations

import os
import queue
import random
import threading
import time

from .logging import LOG
from .metrics import METRICS

CLOSED, HALF_OPEN, OPEN = "closed", "half-open", "open"
_STATE_CODE = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}


class DeviceGuardError(RuntimeError):
    """A guarded call failed on the device AND no fallback succeeded."""


class DeviceTimeout(DeviceGuardError):
    """The watchdog deadline expired before the device call returned."""


class DeviceBadResult(DeviceGuardError):
    """The device returned a result the caller's validator rejected."""


class CycleDeadlineExceeded(DeviceGuardError):
    """The whole-cycle deadline expired; the dispatch was not attempted."""


class _Cancelled(Exception):
    """Internal: an abandoned worker noticed its cancel event."""


# -- watchdog primitives ------------------------------------------------------

class Watchdog:
    """Arm ``callback`` to fire once after ``seconds`` unless cancelled.

    The reusable deadline primitive behind the guard (and the bench
    orchestrator's child budgets): a daemon timer thread, a ``fired``
    flag, and idempotent ``cancel``."""

    def __init__(self, seconds: float, callback, reason: str = ""):
        self.reason = reason
        self.fired = False
        self._lock = threading.Lock()

        def fire():
            with self._lock:
                if self.fired:
                    return
                self.fired = True
            callback()

        self._timer = threading.Timer(max(0.001, seconds), fire)
        self._timer.daemon = True

    def start(self) -> "Watchdog":
        self._timer.start()
        return self

    def cancel(self) -> None:
        with self._lock:
            self.fired = True  # too late to fire now
        self._timer.cancel()


class _Worker:
    """A reusable watchdog worker: one daemon thread, one-job inbox.

    Spawning a thread per dispatch would put ~0.1ms of pure overhead on
    every kernel call of the <100ms-p99 scheduling hot path; instead
    healthy workers are parked in ``_IDLE`` and reused.  A worker whose
    call outlived its deadline is simply never returned to the pool —
    when (if) the hung call finally finishes, the thread parks on its
    empty inbox forever, which leaks no more than the abandoned
    per-call thread did."""

    def __init__(self):
        self.inbox: queue.Queue = queue.Queue(maxsize=1)
        threading.Thread(target=self._loop, daemon=True,
                         name="deviceguard-worker").start()

    def _loop(self):
        while True:
            job = self.inbox.get()
            if job is None:  # retired: the idle pool was already full
                return
            fn, box, done, cancel = job
            try:
                try:
                    box.append(("ok", fn(cancel=cancel)))
                except TypeError as exc:
                    # fn doesn't take the cancel kwarg; plain call.  Only
                    # the signature mismatch is retried — a TypeError
                    # raised from inside fn(cancel=...) must not run fn
                    # twice.
                    if "cancel" not in str(exc):
                        raise
                    box.append(("ok", fn()))
            except _Cancelled:
                pass  # abandoned worker exiting quietly
            except BaseException as exc:  # noqa: BLE001 — relayed
                box.append(("err", exc))
            finally:
                done.set()


_IDLE: list = []
_IDLE_LOCK = threading.Lock()
_MAX_IDLE = 4


def run_with_deadline(fn, deadline_s: float | None, label: str = "device"):
    """Run ``fn()`` on a watchdog worker, waiting at most ``deadline_s``.

    On expiry the worker is ABANDONED (daemon thread; a cooperative
    cancel event is set so injection-driven hangs exit promptly) and
    DeviceTimeout is raised — the caller's thread is never blocked past
    the deadline.  ``deadline_s`` None or <= 0 runs inline (no watchdog
    thread, no overhead).  ``fn`` may optionally accept a ``cancel``
    threading.Event keyword to observe abandonment."""
    if not deadline_s or deadline_s <= 0:
        return fn()
    box: list = []
    cancel = threading.Event()
    done = threading.Event()
    with _IDLE_LOCK:
        worker = _IDLE.pop() if _IDLE else None
    if worker is None:
        worker = _Worker()
    worker.inbox.put((fn, box, done, cancel))
    if not done.wait(deadline_s):
        cancel.set()
        raise DeviceTimeout(
            f"{label}: device call exceeded {deadline_s:.3g}s deadline")
    with _IDLE_LOCK:
        if len(_IDLE) < _MAX_IDLE:
            _IDLE.append(worker)
            worker = None
    if worker is not None:
        worker.inbox.put(None)  # pool full: let the thread exit
    kind, payload = box[0]
    if kind == "err":
        raise payload
    return payload


# -- deterministic fault injection -------------------------------------------

# Control-plane fault modes (injected OUTSIDE the device guard — in the
# apiserver watch stream, the HTTP client, and statement commit).  The
# device-path FaultInjector skips these; components query them with
# control_fault() below.  Specs compose comma-separated:
#   KAI_FAULT_INJECT="flaky:0.2,watchdrop:3"
#
# Wire modes (PR 15, docs/DEGRADATION.md "wire faults"): the lying-wire
# family, injected at the transport seams —
#   wire-truncate:<n>   apiserver watch stream: after <n> frames, write
#                       HALF of the next frame's bytes and close — the
#                       client must reconnect from its cursor, losing
#                       nothing.
#   wire-corrupt:<n>    apiserver watch stream: corrupt every <n>th
#                       frame's payload bytes (framing stays valid) —
#                       an unparseable line must drop the stream, never
#                       poison the store mirror.
#   wire-stall:<ms>     apiserver watch stream: sleep <ms> before every
#                       batch write — a stalled watcher may overrun the
#                       ring and must get an explicit GONE.
#   wire-reset:<n>      apiserver request path: every <n>th mutating
#                       request is APPLIED, then the connection is
#                       closed before any response bytes — the
#                       mid-bulk-POST reset (ambiguous outcome).
#   wire-storm:<n>      apiserver request path: answer the first <n>
#                       requests 429/503 (alternating, Retry-After set,
#                       store untouched) — the throttle storm.
#   wire-gone:<n>       apiserver watch connects: the first <n> streams
#                       answer 410 GONE regardless of cursor — the
#                       compaction storm (client re-list backoff test).
#   wire-drop:<n>       HTTP client shim: every <n>th mutating request
#                       is sent, then the response is discarded and the
#                       connection dropped (URLError) — "did my wave
#                       land?" without killing the server.
CONTROL_FAULT_MODES = ("watchdrop", "partition", "crash-after-journal",
                       "wire-truncate", "wire-corrupt", "wire-stall",
                       "wire-reset", "wire-storm", "wire-gone",
                       "wire-drop")


def control_fault(mode: str, env=None) -> str | None:
    """Return the argument of the control-plane ``KAI_FAULT_INJECT`` spec
    for ``mode`` (empty string when the mode has no argument), or None
    when the mode is not armed.  ``watchdrop[:<n>]`` drops the apiserver
    watch stream after <n> lines, ``partition:<ms>`` fails client
    requests for a window, ``crash-after-journal`` raises SimulatedCrash
    between the journal append and the API commit."""
    env = os.environ if env is None else env
    for part in (env.get("KAI_FAULT_INJECT") or "").split(","):
        m, _, arg = part.strip().partition(":")
        if m.lower() == mode:
            return arg
    return None


class FaultInjector:
    """Parse and apply a ``KAI_FAULT_INJECT`` spec.

    Modes: ``hang`` (block until the watchdog abandons the worker),
    ``slow:<ms>`` (delay every call), ``error`` (raise a transient
    RuntimeError), ``flaky:<p>`` (error with probability p from a seeded
    stream — deterministic across runs), ``badshape`` (return a result
    whose leading array axes are truncated, the XLA wrong-shape failure
    mode).  Injection applies ONLY to the device attempt; the CPU
    fallback path always runs clean, which is exactly the degraded-mode
    contract under test.

    Comma-separated specs compose with the control-plane modes
    (CONTROL_FAULT_MODES): the injector uses the first device-path spec
    and ignores control-plane ones, so one env var drives both planes."""

    def __init__(self, spec: str | None, seed: int = 0):
        parts = [p.strip() for p in (spec or "").split(",") if p.strip()]
        device_parts = [
            p for p in parts
            if p.partition(":")[0].lower() not in CONTROL_FAULT_MODES]
        self.spec = device_parts[0] if device_parts else ""
        self.mode, _, arg = self.spec.partition(":")
        self.mode = self.mode.lower()
        if self.mode not in ("", "hang", "slow", "error", "flaky",
                             "badshape"):
            raise ValueError(f"unknown fault-inject mode {self.mode!r} "
                             "(hang|slow:<ms>|error|flaky:<p>|badshape)")
        self.slow_ms = self.flaky_p = 0.0
        if self.mode in ("slow", "flaky"):
            try:
                val = float(arg)
            except ValueError:
                raise ValueError(
                    f"fault-inject mode {self.mode!r} needs a numeric "
                    f"argument — {self.mode}:<"
                    f"{'ms' if self.mode == 'slow' else 'p'}>, got "
                    f"{self.spec!r}") from None
            if self.mode == "slow":
                self.slow_ms = val
            else:
                self.flaky_p = val
        self._rng = random.Random(seed)

    @property
    def active(self) -> bool:
        return bool(self.mode)

    def before(self, label: str, cancel: threading.Event) -> None:
        """Pre-call fault: runs on the worker thread, before the kernel."""
        if self.mode == "hang":
            cancel.wait(3600.0)  # released the moment the guard abandons
            raise _Cancelled()
        if self.mode == "slow":
            time.sleep(self.slow_ms / 1000.0)
        elif self.mode == "error":
            raise RuntimeError(f"injected device error ({label})")
        elif self.mode == "flaky" and self._rng.random() < self.flaky_p:
            raise RuntimeError(f"injected flaky device error ({label})")

    def transform(self, result):
        """Post-call fault: corrupt the result (badshape mode).  A bare
        array result is truncated directly; container results (NamedTuple
        and friends) get the attribute-truncating proxy; scalars pass
        through — there is no shape to corrupt, and proxying them would
        crash formatting in callers instead of simulating a device
        fault."""
        if self.mode == "badshape":
            if hasattr(result, "shape") and getattr(result, "ndim", 0) >= 1:
                return result[:1]
            if getattr(result, "ndim", None) == 0 or \
                    isinstance(result, (bool, int, float, complex, str,
                                        bytes, type(None))):
                return result  # scalars: no shape to corrupt
            return _BadShapeProxy(result)
        return result


class _BadShapeProxy:
    """Wraps a kernel result so every array attribute comes back with its
    leading axis truncated — what a miscompiled/garbled device answer
    looks like to the host.  Callers' shape validators must catch it."""

    def __init__(self, wrapped):
        object.__setattr__(self, "_wrapped", wrapped)

    def __getattr__(self, name):
        value = getattr(object.__getattribute__(self, "_wrapped"), name)
        if hasattr(value, "shape") and getattr(value, "ndim", 0) >= 1:
            return value[:1]
        return value


# -- circuit breaker ----------------------------------------------------------

class CircuitBreaker:
    """CLOSED -> (threshold consecutive failures) -> OPEN -> (cooloff)
    -> HALF_OPEN probe -> CLOSED on success / OPEN on failure."""

    def __init__(self, threshold: int = 3, cooloff_s: float = 30.0,
                 clock=time.monotonic):
        self.threshold = max(1, int(threshold))
        self.cooloff_s = cooloff_s
        self.clock = clock
        self._lock = threading.Lock()
        self.state = CLOSED
        self.consecutive_failures = 0
        self.trips = 0
        self.opened_at = 0.0
        self.last_error = ""
        self._publish_state()

    def _publish_state(self) -> None:
        METRICS.set_gauge("device_guard_state", _STATE_CODE[self.state])

    def allow_device(self) -> bool:
        """May the next call attempt the device path?  Transitions
        OPEN -> HALF_OPEN once the cooloff elapsed; while HALF_OPEN only
        the probing call (the one that saw the transition, or raced into
        HALF_OPEN) attempts the device — concurrent calls during an open
        window go straight to the fallback."""
        with self._lock:
            if self.state == CLOSED:
                return True
            if self.state == OPEN and \
                    self.clock() - self.opened_at >= self.cooloff_s:
                self.state = HALF_OPEN
                self._publish_state()
                METRICS.inc("device_guard_probes")
                return True
            return False

    def record_success(self) -> bool:
        """Returns True when this success CLOSED a tripped breaker."""
        with self._lock:
            recovered = self.state != CLOSED
            self.state = CLOSED
            self.consecutive_failures = 0
            self.last_error = ""
            self._publish_state()
            return recovered

    def record_failure(self, error: str) -> bool:
        """Returns True when this failure TRIPPED the breaker open."""
        with self._lock:
            self.consecutive_failures += 1
            self.last_error = error[:300]
            was_open = self.state == OPEN
            if (self.state == HALF_OPEN
                    or self.consecutive_failures >= self.threshold):
                self.state = OPEN
                self.opened_at = self.clock()
                self._publish_state()
                if not was_open:
                    self.trips += 1
                    METRICS.inc("device_guard_trips")
                    return True
            return False

    def snapshot(self) -> dict:
        with self._lock:
            return {"state": self.state,
                    "consecutive_failures": self.consecutive_failures,
                    "trips": self.trips,
                    "threshold": self.threshold,
                    "cooloff_s": self.cooloff_s,
                    "last_error": self.last_error}


# -- the guard ----------------------------------------------------------------

def _materialize(result):
    """Force device completion INSIDE the watchdog window: a jitted call
    returns lazily, so without this the hang would surface later at the
    (unguarded) host fetch.  Walks common result containers."""
    import jax
    if result is None:
        return result
    if hasattr(result, "block_until_ready"):
        return result.block_until_ready()
    fields = getattr(result, "_fields", None)
    values = ([getattr(result, f) for f in fields] if fields
              else result if isinstance(result, (tuple, list))
              else [result])
    for v in values:
        # jax.block_until_ready passes non-array leaves through
        # untouched, so anything it raises IS a device failure — it must
        # propagate to the guard, not be swallowed into a "success" that
        # detonates later at the unguarded host fetch.
        jax.block_until_ready(v)
    return result


class DeviceGuard:
    def __init__(self, deadline_s: float | None = None,
                 retries: int | None = None,
                 backoff_base_s: float = 0.05,
                 breaker_threshold: int | None = None,
                 breaker_cooloff_s: float | None = None,
                 fault: str | None = None,
                 fault_seed: int | None = None,
                 fallback_enabled: bool = True,
                 clock=time.monotonic,
                 name: str = "device"):
        env = os.environ
        if deadline_s is None:
            deadline_s = _env_float(env, "KAI_DEVICE_DEADLINE_S", 30.0)
        if retries is None:
            retries = int(_env_float(env, "KAI_DEVICE_RETRIES", 2))
        if breaker_threshold is None:
            breaker_threshold = int(
                _env_float(env, "KAI_BREAKER_THRESHOLD", 3))
        if breaker_cooloff_s is None:
            breaker_cooloff_s = _env_float(env, "KAI_BREAKER_COOLOFF_S",
                                           30.0)
        if fault is None:
            fault = env.get("KAI_FAULT_INJECT", "")
        if fault_seed is None:
            fault_seed = int(_env_float(env, "KAI_FAULT_SEED", 0))
        self.name = name
        self.deadline_s = deadline_s
        self.retries = max(0, int(retries))
        self.backoff_base_s = backoff_base_s
        self.fallback_enabled = fallback_enabled
        self.clock = clock
        self.injector = FaultInjector(fault, seed=fault_seed)
        self.breaker = CircuitBreaker(breaker_threshold, breaker_cooloff_s,
                                      clock=clock)
        self._jitter = random.Random(fault_seed + 1)
        self.timeouts = 0
        self.retried = 0
        self.bad_results = 0
        self.fallback_calls = 0
        # Event dedup: while the breaker stays open, only the FIRST
        # skipped call emits a degraded event (a contended cycle makes
        # hundreds of guarded calls; one event per state change is signal,
        # one per call is spam).
        self._announced_open = False

    # -- fault control (tests / the daemon's --fault-inject flag) ---------
    def set_fault(self, spec: str | None, seed: int = 0) -> None:
        self.injector = FaultInjector(spec, seed=seed)

    def clear_fault(self) -> None:
        self.injector = FaultInjector(None)

    # -- the guarded dispatch ---------------------------------------------
    def call(self, thunk, label: str = "kernel", validate=None,
             record_event=None, deadline_s: float | None = None,
             cycle_deadline_at: float | None = None,
             materialize: bool = True):
        """Run ``thunk`` (a zero-arg device dispatch) under the full
        guard: watchdog deadline, bounded retry, breaker, CPU fallback.

        ``validate``: optional result predicate; a False verdict is a
        device failure (the badshape class of fault).  ``record_event``:
        optional (kind, message) sink — breaker trips and degraded calls
        surface as scheduler events.  ``cycle_deadline_at``: absolute
        clock() value; past it the dispatch aborts immediately with
        CycleDeadlineExceeded (the scheduler's whole-cycle budget).
        ``materialize=False`` is the pipelined-dispatch mode: the call
        returns as soon as the kernel is ENQUEUED (no block_until_ready),
        letting the host overlap work with device execution; validators
        must then judge metadata only (shapes are known pre-completion),
        and an asynchronous device failure surfaces at the caller's later
        guarded fetch, not here.  The CPU fallback path always
        materializes — there is nothing to overlap with."""
        deadline = self.deadline_s if deadline_s is None else deadline_s
        if cycle_deadline_at is not None:
            # The in-flight watchdog must respect the cycle budget too:
            # without this clamp a hang starting just before the cycle
            # deadline could overrun it by the full device deadline.  An
            # exhausted budget must RAISE, never clamp to <= 0 — which
            # run_with_deadline would read as "no deadline, run inline".
            cycle_left = cycle_deadline_at - self.clock()
            if cycle_left <= 0:
                raise CycleDeadlineExceeded(
                    f"{label}: cycle deadline reached before dispatch")
            deadline = (min(deadline, cycle_left)
                        if deadline and deadline > 0 else cycle_left)
        if self.breaker.allow_device():
            error = None
            for attempt in range(self.retries + 1):
                try:
                    result = self._device_attempt(thunk, label, deadline,
                                                  materialize=materialize)
                    if validate is not None and not validate(result):
                        self.bad_results += 1
                        METRICS.inc("device_guard_bad_results")
                        raise DeviceBadResult(
                            f"{label}: result failed shape/validity check")
                    if self.breaker.record_success():
                        self._announced_open = False
                        LOG.info("device-guard %s: breaker closed after "
                                 "successful probe (%s)", self.name, label)
                        self._event(record_event, "DeviceGuardRecovered",
                                    f"{label}: device path recovered; "
                                    "breaker closed")
                    return result
                except DeviceTimeout as exc:
                    # A hang is persistent at the timescale of one call:
                    # retrying would burn deadline * retries of cycle
                    # budget for the same stall.  Straight to failure.
                    self.timeouts += 1
                    METRICS.inc("device_guard_timeouts")
                    error = exc
                    break
                except DeviceBadResult as exc:
                    # Deterministic corruption — retry is wasted work.
                    error = exc
                    break
                except Exception as exc:  # transient device error class
                    error = exc
                    if attempt < self.retries:
                        self.retried += 1
                        METRICS.inc("device_guard_retries")
                        time.sleep(self.backoff_base_s * (2 ** attempt)
                                   * (1.0 + self._jitter.random()))
            if self.breaker.record_failure(repr(error)):
                LOG.warning("device-guard %s: breaker OPEN after %d "
                            "consecutive failures (last: %r)", self.name,
                            self.breaker.consecutive_failures, error)
                self._event(record_event, "DeviceGuardTripped",
                            f"{label}: breaker open after "
                            f"{self.breaker.consecutive_failures} "
                            f"consecutive device failures: {error!r:.200}")
            announce = True
        else:
            error = DeviceGuardError(
                f"{label}: breaker {self.breaker.state}; device path "
                "skipped")
            announce = not self._announced_open
            self._announced_open = True
        return self._fallback(thunk, label, error, validate,
                              record_event if announce else None,
                              cycle_deadline_at=cycle_deadline_at)

    def _device_attempt(self, thunk, label: str, deadline: float | None,
                        materialize: bool = True):
        injector = self.injector

        def attempt(cancel=None):
            if injector.active:
                injector.before(label, cancel or threading.Event())
            result = thunk()
            if materialize:
                result = _materialize(result)
            return injector.transform(result)

        return run_with_deadline(attempt, deadline, label=label)

    def _fallback(self, thunk, label, error, validate, record_event,
                  cycle_deadline_at: float | None = None):
        if not self.fallback_enabled:
            raise error if isinstance(error, DeviceGuardError) else \
                DeviceGuardError(f"{label}: device path failed "
                                 f"({error!r}) and fallback is disabled")
        if cycle_deadline_at is not None and \
                self.clock() >= cycle_deadline_at:
            # The device attempt consumed the rest of the cycle budget:
            # the degraded path must not overrun it either — the cycle
            # driver rolls back and moves on.
            raise CycleDeadlineExceeded(
                f"{label}: cycle deadline reached before CPU fallback "
                f"(device path: {error!r})")
        self.fallback_calls += 1
        METRICS.inc("device_guard_fallback_calls")
        self._event(record_event, "DeviceGuardDegraded",
                    f"{label}: degraded to CPU fallback ({error!r:.200})")
        import jax
        try:
            cpu = jax.devices("cpu")[0]

            def on_host(cancel=None):
                # Clean re-execution on the host backend: no injection,
                # arrays not already committed to a device compile for
                # CPU.  (Committed device arrays keep their placement —
                # acceptable: the deterministic-injection environments
                # this protects are host-backed already, and a genuinely
                # dead device surfaces here as a loud error, not a hang.)
                with jax.default_device(cpu):
                    return _materialize(thunk())

            # The fallback gets a generous-but-bounded watchdog too: the
            # degraded path must also never wedge the cycle.  Floor of
            # 60s: the first fallback call legitimately pays an XLA
            # compile for the host backend, which a short device deadline
            # must not bound.  The cycle budget caps it regardless.
            fb_deadline = (max(60.0, self.deadline_s * 4)
                           if self.deadline_s else None)
            if cycle_deadline_at is not None:
                cycle_left = cycle_deadline_at - self.clock()
                if cycle_left <= 0:
                    # Budget ran out between the entry check and here
                    # (metrics/event/import overhead): raising keeps the
                    # contract — a clamp to <= 0 would run the fallback
                    # INLINE with no watchdog at all.
                    raise CycleDeadlineExceeded(
                        f"{label}: cycle deadline reached before CPU "
                        f"fallback (device path: {error!r})")
                fb_deadline = (min(fb_deadline, cycle_left)
                               if fb_deadline else cycle_left)
            result = run_with_deadline(on_host, fb_deadline,
                                       label=f"{label}@cpu-fallback")
            if validate is not None and not validate(result):
                raise DeviceBadResult(
                    f"{label}: CPU fallback result failed validation")
            return result
        except DeviceGuardError:
            raise
        except Exception as exc:
            raise DeviceGuardError(
                f"{label}: device path failed ({error!r}) and CPU "
                f"fallback also failed ({exc!r})") from exc

    @staticmethod
    def _event(record_event, kind: str, message: str) -> None:
        if record_event is None:
            return
        try:
            record_event(kind, message)
        except Exception:  # event sinks must never break scheduling
            LOG.debug("device-guard event sink failed", exc_info=True)

    def status(self) -> dict:
        """Structured state for /healthz and bench result details."""
        out = self.breaker.snapshot()
        out.update({"deadline_s": self.deadline_s,
                    "retries": self.retries,
                    "timeouts": self.timeouts,
                    "retried": self.retried,
                    "bad_results": self.bad_results,
                    "fallback_calls": self.fallback_calls,
                    "fault_inject": self.injector.spec or None})
        return out

    @property
    def degraded(self) -> bool:
        return self.breaker.state != CLOSED


def _env_float(env, name: str, default: float) -> float:
    try:
        return float(env.get(name, default))
    except (TypeError, ValueError):
        return default


# -- module singleton ---------------------------------------------------------

_GUARD: DeviceGuard | None = None
_GUARD_LOCK = threading.Lock()


def device_guard() -> DeviceGuard:
    """The process-wide guard every kernel dispatch routes through.
    Configured from the KAI_* environment on first use."""
    global _GUARD
    if _GUARD is None:
        with _GUARD_LOCK:
            if _GUARD is None:
                _GUARD = DeviceGuard()
    return _GUARD


def configure_device_guard(**kwargs) -> DeviceGuard:
    """Install a freshly-configured singleton (daemon flags, tests)."""
    global _GUARD
    with _GUARD_LOCK:
        _GUARD = DeviceGuard(**kwargs)
    return _GUARD


def reset_device_guard() -> None:
    """Drop the singleton so the next use re-reads the environment."""
    global _GUARD
    with _GUARD_LOCK:
        _GUARD = None
