"""kailint: the PR1/PR2 safety contracts, machine-enforced (tier-1).

Three layers of coverage:

1. per-rule fixtures — every rule has at least one seeded violation that
   FIRES and one clean/suppressed case that stays silent, so a rule
   regression (stops firing) and a precision regression (starts
   over-firing) both fail this file;
2. engine mechanics — suppressions, baseline drift (a baselined finding
   passes, a new one fails), CLI exit codes and JSON output;
3. the package gate — the analyzer runs over the real
   ``kai_scheduler_tpu/`` tree with the committed baseline and must
   report ZERO new findings, with the baseline capped at 10 entries.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from kai_scheduler_tpu.tools.kailint import Engine, default_rules
from kai_scheduler_tpu.tools.kailint.cli import main as kailint_main
from kai_scheduler_tpu.tools.kailint.engine import (load_baseline,
                                                    write_baseline)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "kai_scheduler_tpu")
BASELINE = os.path.join(REPO_ROOT, ".kailint-baseline.json")


def lint(*modules: tuple[str, str], select: set | None = None):
    """Run the full pipeline over inline fixture modules."""
    report = Engine(default_rules(), select=select).run_modules(
        list(modules))
    assert not report.errors, report.errors
    return report.findings


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# KAI001 trace-safety
# ---------------------------------------------------------------------------

class TestKAI001TraceSafety:
    def test_fires_on_host_control_flow_in_jitted_fn(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@jax.jit\n"
            "def kernel(x):\n"
            "    if x > 0:\n"
            "        return jnp.sum(x)\n"
            "    return x\n")
        findings = lint(("kai_scheduler_tpu/ops/fix.py", src))
        assert any(f.rule == "KAI001" and "`if`" in f.message
                   for f in findings)

    def test_fires_on_item_and_numpy_in_jit_reachable_helper(self):
        # _helper is reachable from the jitted root -> traced too.
        src = (
            "import functools, jax\n"
            "import jax.numpy as jnp\n"
            "import numpy as np\n"
            "def _helper(x):\n"
            "    n = x.item()\n"
            "    return np.sum(x)\n"
            "@functools.partial(jax.jit, static_argnames=('k',))\n"
            "def kernel(x, k):\n"
            "    return _helper(x)\n")
        findings = lint(("kai_scheduler_tpu/ops/fix.py", src))
        msgs = [f.message for f in findings if f.rule == "KAI001"]
        assert any(".item()" in m for m in msgs)
        assert any("np.sum" in m for m in msgs)

    def test_fires_on_float_cast_of_traced_value(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def kernel(x):\n"
            "    return float(x)\n")
        findings = lint(("kai_scheduler_tpu/parallel/fix.py", src))
        assert any(f.rule == "KAI001" and "float" in f.message
                   for f in findings)

    def test_clean_static_patterns_do_not_fire(self):
        # None-staging, static_argnames branches, shape math, host
        # helpers never called from jit: all legitimate.
        src = (
            "import functools, jax\n"
            "import jax.numpy as jnp\n"
            "import numpy as np\n"
            "def host_prep(rows):\n"  # not jit-reachable
            "    if len(rows) == 0:\n"
            "        return np.zeros(0)\n"
            "    return np.stack(rows)\n"
            "@functools.partial(jax.jit, static_argnames=('mode',))\n"
            "def kernel(x, extra=None, mode=0):\n"
            "    if extra is None:\n"
            "        extra = jnp.zeros(x.shape[0])\n"
            "    if mode:\n"
            "        extra = extra + 1\n"
            "    n = int(x.shape[0])\n"
            "    if jax.default_backend() != 'tpu':\n"
            "        extra = extra * 2\n"
            "    return x + extra\n")
        findings = lint(("kai_scheduler_tpu/ops/fix.py", src))
        assert [f for f in findings if f.rule == "KAI001"] == []

    def test_out_of_scope_module_ignored(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def kernel(x):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert [f for f in findings if f.rule == "KAI001"] == []


# ---------------------------------------------------------------------------
# KAI002 host-sync-in-hot-path
# ---------------------------------------------------------------------------

class TestKAI002HostSync:
    def test_fires_on_block_until_ready_outside_guard(self):
        src = ("def f(result):\n"
               "    return result.block_until_ready()\n")
        findings = lint(("kai_scheduler_tpu/actions/fix.py", src))
        assert any(f.rule == "KAI002" for f in findings)

    def test_fires_on_print_in_hot_path(self):
        src = ("def f(x):\n"
               "    print(x)\n"
               "    return x\n")
        findings = lint(("kai_scheduler_tpu/ops/fix.py", src))
        assert any(f.rule == "KAI002" and "print" in f.message
                   for f in findings)

    def test_device_guard_commit_point_allowlisted(self):
        src = ("def _sync(result):\n"
               "    return result.block_until_ready()\n")
        findings = lint(("kai_scheduler_tpu/utils/deviceguard.py", src))
        assert [f for f in findings if f.rule == "KAI002"] == []

    def test_print_outside_hot_path_allowed(self):
        src = ("def main():\n"
               "    print('kai-apiserver listening')\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert [f for f in findings if f.rule == "KAI002"] == []


# ---------------------------------------------------------------------------
# KAI003 wall-clock-discipline
# ---------------------------------------------------------------------------

class TestKAI003WallClock:
    def test_fires_on_time_time_call(self):
        src = ("import time\n"
               "def backoff():\n"
               "    return time.time() + 5\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI003" for f in findings)

    def test_fires_on_datetime_now(self):
        src = ("import datetime\n"
               "def stamp():\n"
               "    return datetime.datetime.now()\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert any(f.rule == "KAI003" for f in findings)

    def test_injection_default_is_sanctioned(self):
        # `clock=time.time` references without calling: the injection
        # point pattern leaderelect/binder use.
        src = ("import time\n"
               "class Elector:\n"
               "    def __init__(self, clock=time.time):\n"
               "        self.clock = clock\n"
               "    def now(self):\n"
               "        return self.clock()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI003"] == []

    def test_suppression_with_reason(self):
        src = ("import time\n"
               "def journal_stamp():\n"
               "    return time.time()  "
               "# kailint: disable=KAI003 — wall-clock intentional\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI003"] == []

    def test_out_of_scope_module_ignored(self):
        src = ("import time\n"
               "def t():\n"
               "    return time.time()\n")
        findings = lint(("kai_scheduler_tpu/ops/fix.py", src))
        assert [f for f in findings if f.rule == "KAI003"] == []

    def test_from_import_aliases_cannot_evade(self):
        # `from time import time` and `from datetime import datetime as
        # dt` spell the same wall-clock calls differently.
        src = ("from time import time\n"
               "from datetime import datetime as dt\n"
               "def deadline():\n"
               "    return time() + 30\n"
               "def stamp():\n"
               "    return dt.now()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert len([f for f in findings if f.rule == "KAI003"]) == 2

    def test_from_time_import_monotonic_is_clean(self):
        src = ("from time import monotonic\n"
               "def deadline():\n"
               "    return monotonic() + 30\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI003"] == []

    def test_module_import_aliases_cannot_evade(self):
        src = ("import time as clk\n"
               "import datetime as d8\n"
               "def deadline():\n"
               "    return clk.time() + 30\n"
               "def stamp():\n"
               "    return d8.datetime.now()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert len([f for f in findings if f.rule == "KAI003"]) == 2


# ---------------------------------------------------------------------------
# KAI004 unguarded-dispatch
# ---------------------------------------------------------------------------

OPS_MODULE = (
    "kai_scheduler_tpu/ops/kern.py",
    "import functools, jax\n"
    "@functools.partial(jax.jit, static_argnames=('k',))\n"
    "def fast_kernel(x, k=1):\n"
    "    return x * k\n"
    "def wrapper(x):\n"            # host wrapper -> still dispatches
    "    return fast_kernel(x, k=2)\n"
    "def host_prep(rows):\n"       # no kernel call -> not a kernel
    "    return list(rows)\n")


class TestKAI004UnguardedDispatch:
    def test_fires_on_direct_kernel_call(self):
        action = ("from ..ops.kern import fast_kernel\n"
                  "def run(ssn, x):\n"
                  "    return fast_kernel(x)\n")
        findings = lint(OPS_MODULE,
                        ("kai_scheduler_tpu/actions/fix.py", action))
        assert any(f.rule == "KAI004" and "fast_kernel" in f.message
                   for f in findings)

    def test_fires_on_host_wrapper_and_module_alias(self):
        action = ("from ..ops import kern as k\n"
                  "def run(ssn, x):\n"
                  "    return k.wrapper(x)\n")
        findings = lint(OPS_MODULE,
                        ("kai_scheduler_tpu/actions/fix.py", action))
        assert any(f.rule == "KAI004" and "k.wrapper" in f.message
                   for f in findings)

    def test_lambda_thunk_is_guarded(self):
        action = ("from ..ops.kern import fast_kernel\n"
                  "def run(ssn, x):\n"
                  "    return ssn.dispatch_kernel(\n"
                  "        lambda: fast_kernel(x), label='x')\n")
        findings = lint(OPS_MODULE,
                        ("kai_scheduler_tpu/actions/fix.py", action))
        assert [f for f in findings if f.rule == "KAI004"] == []

    @pytest.mark.parametrize("entry", ("dispatch_kernel",
                                       "_dispatch_and_fetch"))
    def test_named_thunk_is_guarded(self, entry):
        """Blocking or pipelined: both entries run the thunk under the
        guard."""
        action = ("from ..ops.kern import fast_kernel\n"
                  "def run(ssn, x):\n"
                  "    def thunk():\n"
                  "        return fast_kernel(x)\n"
                  f"    return ssn.{entry}(thunk, label='x')\n")
        findings = lint(OPS_MODULE,
                        ("kai_scheduler_tpu/actions/fix.py", action))
        assert [f for f in findings if f.rule == "KAI004"] == []

    def test_host_helper_call_not_flagged(self):
        action = ("from ..ops.kern import host_prep\n"
                  "def run(rows):\n"
                  "    return host_prep(rows)\n")
        findings = lint(OPS_MODULE,
                        ("kai_scheduler_tpu/actions/fix.py", action))
        assert [f for f in findings if f.rule == "KAI004"] == []

    def test_ops_layer_composes_kernels_freely(self):
        other = ("from .kern import fast_kernel\n"
                 "def fused(x):\n"
                 "    return fast_kernel(x) + 1\n")
        findings = lint(OPS_MODULE,
                        ("kai_scheduler_tpu/ops/other.py", other))
        assert [f for f in findings if f.rule == "KAI004"] == []


# ---------------------------------------------------------------------------
# KAI005 unfenced-write
# ---------------------------------------------------------------------------

class TestKAI005UnfencedWrite:
    PATH = "kai_scheduler_tpu/controllers/cache_builder.py"

    def test_fires_on_unfenced_bindrequest_delete(self):
        src = ("class C:\n"
               "    def gc(self):\n"
               "        self.api.delete('BindRequest', 'b', 'ns')\n")
        findings = lint((self.PATH, src))
        assert any(f.rule == "KAI005" for f in findings)

    def test_fires_on_unfenced_tracked_dict_create(self):
        src = ("class C:\n"
               "    def bind(self):\n"
               "        obj = {'kind': 'BindRequest', 'spec': {}}\n"
               "        self.api.create(obj)\n")
        findings = lint((self.PATH, src))
        assert any(f.rule == "KAI005" and "create" in f.message
                   for f in findings)

    def test_fires_on_unfenced_evict_write(self):
        src = ("class C:\n"
               "    def evict(self, task):\n"
               "        self.api.delete('Pod', task.name, task.namespace)\n")
        findings = lint((self.PATH, src))
        assert any(f.rule == "KAI005" for f in findings)

    def test_fence_kwargs_splat_is_clean(self):
        src = ("class C:\n"
               "    def gc(self):\n"
               "        fk = self._fence_kwargs()\n"
               "        self.api.delete('BindRequest', 'b', 'ns', **fk)\n"
               "    def bind(self):\n"
               "        obj = {'kind': 'BindRequest'}\n"
               "        self.api.create(obj, epoch=3, fence='kai')\n")
        findings = lint((self.PATH, src))
        assert [f for f in findings if f.rule == "KAI005"] == []

    def test_unrelated_splat_does_not_count_as_fence(self):
        # `**retry_opts` is a splat but not a fence — the gate must not
        # accept any ** as proof the epoch rides along.
        src = ("class C:\n"
               "    def gc(self, retry_opts):\n"
               "        self.api.delete('BindRequest', 'b', 'ns',\n"
               "                        **retry_opts)\n")
        findings = lint((self.PATH, src))
        assert any(f.rule == "KAI005" for f in findings)

    def test_fence_local_splat_is_clean(self):
        src = ("class C:\n"
               "    def gc(self):\n"
               "        fk = self._fence_kwargs()\n"
               "        self.api.delete('BindRequest', 'b', 'ns', **fk)\n"
               "    def gc2(self):\n"
               "        self.api.delete('BindRequest', 'b', 'ns',\n"
               "                        **self._fence_kwargs())\n")
        findings = lint((self.PATH, src))
        assert [f for f in findings if f.rule == "KAI005"] == []

    def test_non_write_path_module_out_of_scope(self):
        src = ("class C:\n"
               "    def gc(self):\n"
               "        self.api.delete('BindRequest', 'b', 'ns')\n")
        findings = lint(("kai_scheduler_tpu/controllers/binder.py", src))
        assert [f for f in findings if f.rule == "KAI005"] == []


# ---------------------------------------------------------------------------
# KAI006 lock-discipline
# ---------------------------------------------------------------------------

class TestKAI006LockDiscipline:
    def test_fires_on_bare_acquire(self):
        src = ("class C:\n"
               "    def f(self):\n"
               "        self._lock.acquire()\n"
               "        self.n += 1\n"
               "        self._lock.release()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI006" and "acquire" in f.message
                   for f in findings)

    def test_fires_on_discarded_timeout_acquire(self):
        # Discarding acquire(timeout=...)'s result is worse than the
        # bare form: on timeout the code proceeds without the lock.
        src = ("class C:\n"
               "    def f(self):\n"
               "        self._lock.acquire(timeout=1)\n"
               "        self.n += 1\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI006" and "acquire" in f.message
                   for f in findings)

    def test_fires_on_blocking_call_under_lock(self):
        src = ("import os\n"
               "class C:\n"
               "    def f(self, fh):\n"
               "        with self._lock:\n"
               "            os.fsync(fh.fileno())\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI006" and "fsync" in f.message
                   for f in findings)

    def test_nested_locks_yield_one_finding_per_defect(self):
        src = ("import os\n"
               "class C:\n"
               "    def f(self, fh):\n"
               "        with self._lock:\n"
               "            with self._journal_lock:\n"
               "                os.fsync(fh.fileno())\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert len([f for f in findings if f.rule == "KAI006"]) == 1

    def test_callback_defined_under_lock_is_clean(self):
        # Code merely DEFINED under the lock doesn't run while it is
        # held — a stored lambda/closure must not be flagged.
        src = ("import os\n"
               "class C:\n"
               "    def f(self, fd):\n"
               "        with self._lock:\n"
               "            self._flush = lambda: os.fsync(fd)\n"
               "            def cb():\n"
               "                os.fsync(fd)\n"
               "            self._cb = cb\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI006"] == []

    def test_with_lock_and_trylock_are_clean(self):
        src = ("class C:\n"
               "    def f(self):\n"
               "        with self._lock:\n"
               "            self.n += 1\n"
               "    def g(self):\n"
               "        got = self._lock.acquire(timeout=1)\n"
               "        return got\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI006"] == []

    def test_clock_is_not_a_lock(self):
        # "clock" contains "lock" but is not one — whole-word matching.
        src = ("import os\n"
               "class C:\n"
               "    def f(self, fh):\n"
               "        with self.clock:\n"
               "            os.fsync(fh.fileno())\n"
               "        self.clock.acquire()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI006"] == []

    # -- type-based lock identity (shared lockscope collector) ---------

    def test_fires_on_bare_acquire_of_innocently_named_rlock(self):
        # An RLock assigned to a non-lockish attribute name is still a
        # lock: identity comes from the declared TYPE via the shared
        # lock-scope collector, not just the name token.
        src = ("import threading\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._state = threading.RLock()\n"
               "    def f(self):\n"
               "        self._state.acquire()\n"
               "        self.n += 1\n"
               "        self._state.release()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI006" and "acquire" in f.message
                   for f in findings)

    def test_fires_on_blocking_call_under_typed_semaphore(self):
        src = ("import os, threading\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._slots = threading.Semaphore(4)\n"
               "    def f(self, fh):\n"
               "        with self._slots:\n"
               "            os.fsync(fh.fileno())\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI006" and "fsync" in f.message
                   for f in findings)

    def test_event_named_like_a_lock_is_not_a_lock(self):
        # The collector knows the primitive kind: an Event named
        # `_sem_ready` must not be treated as a lock by the name token.
        src = ("import threading\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._sem_ready = threading.Event()\n"
               "    def f(self):\n"
               "        self._sem_ready.wait()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI006"] == []

    # -- Condition notify/wait outside its lock ------------------------

    def test_fires_on_notify_outside_condition_lock(self):
        src = ("import threading\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._cv = threading.Condition()\n"
               "    def f(self):\n"
               "        self._cv.notify()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI006" and "notify" in f.message
                   for f in findings)

    def test_notify_inside_with_condition_is_clean(self):
        src = ("import threading\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._cv = threading.Condition()\n"
               "    def f(self):\n"
               "        with self._cv:\n"
               "            self._cv.notify_all()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI006"] == []

    def test_condition_lock_aliasing_is_honored(self):
        # Condition(self._lock) ALIASES the lock: holding self._lock IS
        # holding the condition, so notify under it is clean — while a
        # notify under a DIFFERENT lock still fires.
        clean = ("import threading\n"
                 "class C:\n"
                 "    def __init__(self):\n"
                 "        self._lock = threading.Lock()\n"
                 "        self._cv = threading.Condition(self._lock)\n"
                 "    def f(self):\n"
                 "        with self._lock:\n"
                 "            self._cv.notify()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", clean))
        assert [f for f in findings if f.rule == "KAI006"] == []
        wrong = ("import threading\n"
                 "class C:\n"
                 "    def __init__(self):\n"
                 "        self._lock = threading.Lock()\n"
                 "        self._other = threading.Lock()\n"
                 "        self._cv = threading.Condition(self._lock)\n"
                 "    def f(self):\n"
                 "        with self._other:\n"
                 "            self._cv.notify()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", wrong))
        assert any(f.rule == "KAI006" and "notify" in f.message
                   for f in findings)


# ---------------------------------------------------------------------------
# KAI007 exception-swallowing
# ---------------------------------------------------------------------------

class TestKAI007ExceptionSwallowing:
    def test_fires_on_silent_broad_except(self):
        src = ("def reconcile(api):\n"
               "    try:\n"
               "        api.create({})\n"
               "    except Exception:\n"
               "        pass\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert any(f.rule == "KAI007" for f in findings)

    def test_fires_on_bare_except_continue(self):
        src = ("def loop(items):\n"
               "    for i in items:\n"
               "        try:\n"
               "            i.sync()\n"
               "        except:\n"
               "            continue\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert any(f.rule == "KAI007" and "bare except" in f.message
                   for f in findings)

    def test_logged_and_counted_handler_is_clean(self):
        src = ("def reconcile(api, log, METRICS):\n"
               "    try:\n"
               "        api.create({})\n"
               "    except Exception as exc:\n"
               "        METRICS.inc('reconcile_errors')\n"
               "        log.warning('failed: %s', exc)\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert [f for f in findings if f.rule == "KAI007"] == []

    def test_narrow_except_pass_is_clean(self):
        src = ("def parse(raw):\n"
               "    try:\n"
               "        return int(raw)\n"
               "    except ValueError:\n"
               "        pass\n"
               "    return 0\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert [f for f in findings if f.rule == "KAI007"] == []

    def test_out_of_scope_module_ignored(self):
        src = ("def f(x):\n"
               "    try:\n"
               "        return x()\n"
               "    except Exception:\n"
               "        pass\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI007"] == []


# ---------------------------------------------------------------------------
# KAI008 metrics-hygiene
# ---------------------------------------------------------------------------

class TestKAI008MetricsHygiene:
    def test_fires_on_non_snake_case_name(self):
        src = ("from ..utils.metrics import METRICS\n"
               "def f():\n"
               "    METRICS.inc('BadName')\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert any(f.rule == "KAI008" and "snake_case" in f.message
                   for f in findings)

    def test_fires_on_cross_type_duplicate_registration(self):
        a = ("from ..utils.metrics import METRICS\n"
             "def f():\n"
             "    METRICS.inc('cycle_latency')\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g():\n"
             "    METRICS.observe('cycle_latency', 12.0)\n")
        findings = lint(("kai_scheduler_tpu/controllers/a.py", a),
                        ("kai_scheduler_tpu/controllers/b.py", b))
        assert any(f.rule == "KAI008" and "one instrument" in f.message
                   for f in findings)

    def test_fires_on_inconsistent_label_keys(self):
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.set_gauge('queue_share', v, queue='a')\n"
               "    METRICS.set_gauge('queue_share', v)\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert any(f.rule == "KAI008" and "label keys" in f.message
                   for f in findings)

    def test_consistent_usage_is_clean(self):
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.inc('fenced_writes_total')\n"
               "    METRICS.set_gauge('queue_share', v, queue='a')\n"
               "    METRICS.set_gauge('queue_share', v, queue='b')\n"
               "    METRICS.observe('cycle_ms', v)\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_cycle_span_family_consistent_usage_is_clean(self):
        # The flight recorder's per-span-kind latency families
        # (utils/tracing.py end_cycle): each name is one histogram.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.observe('cycle_span_cycle_latency_ms', v)\n"
               "    METRICS.observe('cycle_span_kernel_latency_ms', v)\n"
               "    METRICS.observe('cycle_span_action_latency_ms', v)\n"
               "    METRICS.observe('cycle_span_commit_latency_ms', v)\n"
               "    METRICS.observe('cycle_span_kubeapi_latency_ms', v)\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_columnar_families_consistent_usage_is_clean(self):
        # PR 12's columnar host-state families (cache_builder /
        # podgrouper): one instrument per name, label-free.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.inc('columnar_fallback_total')\n"
               "    METRICS.set_gauge('snapshot_columnar_rows', v)\n"
               "    METRICS.inc('grouper_vectorized_batches_total')\n"
               "    METRICS.observe('snapshot_build_latency_ms', v)\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_columnar_cross_instrument_collision_fires(self):
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v):\n"
             "    METRICS.set_gauge('snapshot_columnar_rows', v)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g():\n"
             "    METRICS.inc('snapshot_columnar_rows')\n")
        findings = lint(("kai_scheduler_tpu/controllers/a.py", a),
                        ("kai_scheduler_tpu/framework/b.py", b))
        assert any(f.rule == "KAI008" and "one instrument" in f.message
                   and "snapshot_columnar_rows" in f.message
                   for f in findings)

    def test_wire_families_consistent_usage_is_clean(self):
        # PR 13's daemon-scale apiserver families (apiserver /
        # httpclient / binder / status_updater / cache_builder): the
        # labeled counters keep ONE label-key set per family.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.inc('watch_frame_cache_hits_total')\n"
               "    METRICS.inc('watch_frame_cache_misses_total')\n"
               "    METRICS.inc('apiserver_pool_saturated_total')\n"
               "    METRICS.inc('apiserver_pool_dispatch_total')\n"
               "    METRICS.inc('apiserver_list_requests_total',"
               " kind='Pod')\n"
               "    METRICS.inc('apiserver_whole_kind_lists_total',"
               " kind='Pod')\n"
               "    METRICS.inc('apiserver_bulk_requests_total',"
               " op='create')\n"
               "    METRICS.inc('apiserver_bulk_items_total', v,"
               " op='create')\n"
               "    METRICS.inc('bulk_write_batches_total',"
               " path='bind_wave')\n"
               "    METRICS.inc('bulk_write_items_total', v,"
               " path='status')\n"
               "    METRICS.inc('bulk_write_errors_total',"
               " path='binder')\n"
               "    METRICS.inc('http_list_pages_total')\n"
               "    METRICS.inc('http_list_continue_gone_total')\n"
               "    METRICS.inc('http_throttled_retries_total')\n"
               "    METRICS.inc('watch_barrier_timeouts_total')\n")
        findings = lint(("kai_scheduler_tpu/controllers/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_wire_family_label_drift_fires(self):
        # A bulk_write_* call dropping its `path` label would fork the
        # family's label-key set across the tree.
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v):\n"
             "    METRICS.inc('bulk_write_batches_total',"
             " path='status')\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g():\n"
             "    METRICS.inc('bulk_write_batches_total')\n")
        findings = lint(("kai_scheduler_tpu/controllers/a.py", a),
                        ("kai_scheduler_tpu/controllers/b.py", b))
        assert any(f.rule == "KAI008" and "label keys" in f.message
                   and "bulk_write_batches_total" in f.message
                   for f in findings)

    def test_wireobs_families_consistent_usage_is_clean(self):
        # PR 19's wire-observatory families (utils/wireobs.py single
        # call sites): byte/syscall counters per request class on both
        # dialect ends, frame-cache byte split, fanout counters, the
        # depth gauge, and the graft outcome counters.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v, p, s):\n"
               "    METRICS.inc('wire_bytes_total', v, dir='in',"
               " end='client', path=p)\n"
               "    METRICS.inc('wire_bytes_total', v, dir='out',"
               " end='server', path=p)\n"
               "    METRICS.inc('wire_syscalls_total', v, end='client',"
               " op='send', path=p)\n"
               "    METRICS.inc('frame_cache_bytes_total', v,"
               " src='cache')\n"
               "    METRICS.inc('frame_cache_serve_encodes_total')\n"
               "    METRICS.inc('watch_fanout_frames_total', v,"
               " stream=s)\n"
               "    METRICS.inc('watch_fanout_bytes_total', v,"
               " stream=s)\n"
               "    METRICS.set_gauge('watch_fanout_lag_frames', v,"
               " stream=s)\n"
               "    METRICS.set_gauge('watch_stream_queue_depth', v,"
               " stream=s)\n"
               "    METRICS.inc('watch_stream_depth_gone_total')\n"
               "    METRICS.inc('wire_spans_grafted_total', v)\n"
               "    METRICS.inc('wire_spans_orphaned_total', v)\n"
               "    METRICS.inc('wire_spans_duplicate_total', v)\n"
               "    METRICS.inc('wire_spans_unattributed_total', v)\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_wireobs_family_label_drift_fires(self):
        # A wire_bytes_total call dropping its `end` label (or a fanout
        # counter dropping `stream`) would fork the family's label-key
        # set and break wire_totals()'s reconciliation fold.
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v, p):\n"
             "    METRICS.inc('wire_bytes_total', v, dir='in',"
             " end='client', path=p)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g(v, p):\n"
             "    METRICS.inc('wire_bytes_total', v, dir='in', path=p)\n")
        findings = lint(("kai_scheduler_tpu/utils/a.py", a),
                        ("kai_scheduler_tpu/controllers/b.py", b))
        assert any(f.rule == "KAI008" and "label keys" in f.message
                   and "wire_bytes_total" in f.message
                   for f in findings)
        c = ("from ..utils.metrics import METRICS\n"
             "def h(v, s):\n"
             "    METRICS.set_gauge('watch_fanout_lag_frames', v,"
             " stream=s)\n"
             "    METRICS.set_gauge('watch_fanout_lag_frames', v)\n")
        findings = lint(("kai_scheduler_tpu/controllers/c.py", c))
        assert any(f.rule == "KAI008" and "label keys" in f.message
                   and "watch_fanout_lag_frames" in f.message
                   for f in findings)

    def test_wireobs_cross_instrument_collision_fires(self):
        # The depth gauge reused as a counter would double-register the
        # family in the exposition.
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v, s):\n"
             "    METRICS.set_gauge('watch_stream_queue_depth', v,"
             " stream=s)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g(s):\n"
             "    METRICS.inc('watch_stream_queue_depth', stream=s)\n")
        findings = lint(("kai_scheduler_tpu/utils/a.py", a),
                        ("kai_scheduler_tpu/controllers/b.py", b))
        assert any(f.rule == "KAI008" and "one instrument" in f.message
                   and "watch_stream_queue_depth" in f.message
                   for f in findings)

    def test_cycle_span_cross_instrument_collision_fires(self):
        # A counter reusing a cycle_span_* histogram name would double-
        # register the family in the exposition: the whole-tree pass
        # must catch it across modules.
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v):\n"
             "    METRICS.observe('cycle_span_kernel_latency_ms', v)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g():\n"
             "    METRICS.inc('cycle_span_kernel_latency_ms')\n")
        findings = lint(("kai_scheduler_tpu/utils/a.py", a),
                        ("kai_scheduler_tpu/controllers/b.py", b))
        assert any(f.rule == "KAI008" and "one instrument" in f.message
                   and "cycle_span_kernel_latency_ms" in f.message
                   for f in findings)

    def test_cycle_span_inconsistent_labels_fire(self):
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.observe('cycle_span_action_latency_ms', v)\n"
               "    METRICS.observe('cycle_span_action_latency_ms', v,\n"
               "                    action='allocate')\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI008" and "label keys" in f.message
                   for f in findings)

    def test_pod_latency_family_consistent_usage_is_clean(self):
        # The lifecycle observatory's families (utils/lifecycle.py):
        # labeled histograms/counters behind the cardinality guard, used
        # with ONE label-key set per family.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v, q, p):\n"
               "    METRICS.observe('pod_latency_ms', v, queue=q)\n"
               "    METRICS.observe('pod_phase_latency_ms', v, phase=p)\n"
               "    METRICS.inc('slo_pod_latency_burn_total', queue=q)\n"
               "    METRICS.inc('slo_cycle_budget_burn_total')\n"
               "    METRICS.inc('lifecycle_open_overflow_total')\n"
               "    METRICS.inc('metrics_label_overflow_total')\n"
               "    METRICS.set_gauge('lifecycle_open_timelines', v)\n"
               "    METRICS.set_gauge('pods_in_phase', v, phase=p)\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_pod_latency_inconsistent_labels_fire(self):
        # A bare pod_latency_ms observation next to the per-queue one is
        # an unmergeable-series bug the rule must catch.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v, q):\n"
               "    METRICS.observe('pod_latency_ms', v, queue=q)\n"
               "    METRICS.observe('pod_latency_ms', v)\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI008" and "label keys" in f.message
                   and "pod_latency_ms" in f.message for f in findings)

    def test_fairshare_family_consistent_usage_is_clean(self):
        # The queue-forest fair-share families (ops/fairshare.py): prep
        # cache reuse + single-dispatch counters, unlabeled.
        src = ("from ..utils.metrics import METRICS\n"
               "def f():\n"
               "    METRICS.inc('fairshare_prep_reuse_total')\n"
               "    METRICS.inc('fairshare_dispatch_total')\n"
               "    METRICS.observe('cycle_span_fairshare_latency_ms', 1)\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_fairshare_cross_instrument_collision_fires(self):
        # A gauge reusing the dispatch counter's name would corrupt the
        # structural one-dispatch-per-cycle gate (tools/fleet_budget.py).
        a = ("from ..utils.metrics import METRICS\n"
             "def f():\n"
             "    METRICS.inc('fairshare_dispatch_total')\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g(v):\n"
             "    METRICS.set_gauge('fairshare_dispatch_total', v)\n")
        findings = lint(("kai_scheduler_tpu/utils/a.py", a),
                        ("kai_scheduler_tpu/controllers/b.py", b))
        assert any(f.rule == "KAI008" and "one instrument" in f.message
                   and "fairshare_dispatch_total" in f.message
                   for f in findings)

    def test_pipeline_family_consistent_usage_is_clean(self):
        # The overlapped-cycle families (framework/pipeline.py +
        # operator/cache_builder): overlap gauge, commit-executor
        # counters/gauge, speculation + coalescing + dedupe counters.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.set_gauge('cycle_overlap_ratio', v)\n"
               "    METRICS.inc('commit_executor_batches_total')\n"
               "    METRICS.inc('commit_executor_errors_total')\n"
               "    METRICS.inc('commit_executor_poisoned_total')\n"
               "    METRICS.set_gauge('commit_executor_queue_depth', v)\n"
               "    METRICS.set_gauge('pipeline_speculative_entries', v)\n"
               "    METRICS.inc('pipeline_speculation_rollback_total', v)\n"
               "    METRICS.inc('pipeline_fenced_commits_total')\n"
               "    METRICS.inc('pipeline_drained_to_serial_total')\n"
               "    METRICS.inc('pipeline_drain_timeouts_total')\n"
               "    METRICS.inc('event_writes_deduped_total')\n"
               "    METRICS.inc('watch_events_coalesced_total', v)\n"
               "    METRICS.inc('status_writes_deduped_total')\n"
               "    METRICS.inc('evict_writes_batched_total', v)\n"
               "    METRICS.observe('evict_write_latency_ms', v)\n"
               "    METRICS.observe('cycle_span_commit_async_latency_ms',"
               " v)\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_pipeline_cross_instrument_collision_fires(self):
        # A counter reusing the overlap gauge's name would corrupt the
        # structural min_overlap_ratio gate (tools/fleet_budget.py).
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v):\n"
             "    METRICS.set_gauge('cycle_overlap_ratio', v)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g():\n"
             "    METRICS.inc('cycle_overlap_ratio')\n")
        findings = lint(("kai_scheduler_tpu/framework/a.py", a),
                        ("kai_scheduler_tpu/controllers/b.py", b))
        assert any(f.rule == "KAI008" and "one instrument" in f.message
                   and "cycle_overlap_ratio" in f.message
                   for f in findings)

    def test_solver_family_consistent_usage_is_clean(self):
        # The scenario solver's families (actions/solvers.py, PR 35): the
        # prescreen's three label-free counters and the evictions a solve
        # committed, labelled by the action as
        # scenarios_simulation_by_action is.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v, name):\n"
               "    METRICS.inc('scenarios_simulation_by_action',"
               " action=name)\n"
               "    METRICS.inc('scenario_prescreen_prefixes_total', v)\n"
               "    METRICS.inc('scenario_prescreen_feasible_total', v)\n"
               "    METRICS.inc('scenarios_skipped_by_prescreen_total',"
               " v)\n"
               "    METRICS.inc('solver_evictions_total', v,"
               " action=name)\n")
        findings = lint(("kai_scheduler_tpu/actions/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_solver_label_drift_fires(self):
        # solver_evictions_total is labelled by action everywhere: one
        # label-free call site would split the family on /metrics.
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v, name):\n"
             "    METRICS.inc('solver_evictions_total', v, action=name)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g(v):\n"
             "    METRICS.inc('solver_evictions_total', v)\n")
        findings = lint(("kai_scheduler_tpu/actions/a.py", a),
                        ("kai_scheduler_tpu/actions/b.py", b))
        assert any(f.rule == "KAI008" and "label keys" in f.message
                   and "solver_evictions_total" in f.message
                   for f in findings)

    def test_consolidation_family_consistent_usage_is_clean(self):
        # What PR 37 added: the victims a solved job placed again, labelled
        # by the action as solver_evictions_total is, and the
        # counted-prescreen family moved by 0 or 1 from its one call site.
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v, name, counted):\n"
               "    METRICS.inc('solver_evictions_total', v, action=name)\n"
               "    METRICS.inc('solver_victims_replaced_total', v,"
               " action=name)\n"
               "    METRICS.inc('scenario_prescreen_counted_total',"
               " int(counted))\n")
        findings = lint(("kai_scheduler_tpu/actions/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_replaced_label_drift_fires(self):
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v, name):\n"
             "    METRICS.inc('solver_victims_replaced_total', v,"
             " action=name)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g(v):\n"
             "    METRICS.inc('solver_victims_replaced_total', v)\n")
        findings = lint(("kai_scheduler_tpu/actions/a.py", a),
                        ("kai_scheduler_tpu/actions/b.py", b))
        assert any(f.rule == "KAI008" and "label keys" in f.message
                   and "solver_victims_replaced_total" in f.message
                   for f in findings)

    def test_stackprof_family_consistent_usage_is_clean(self):
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.inc('stackprof_samples_total', v)\n"
               "    METRICS.inc('stackprof_dump_errors_total')\n"
               "    METRICS.set_gauge('stackprof_dropped_stacks', v)\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_stackprof_cross_instrument_collision_fires(self):
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v):\n"
             "    METRICS.inc('stackprof_samples_total', v)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g(v):\n"
             "    METRICS.observe('stackprof_samples_total', v)\n")
        findings = lint(("kai_scheduler_tpu/utils/a.py", a),
                        ("kai_scheduler_tpu/server.py", b))
        assert any(f.rule == "KAI008" and "one instrument" in f.message
                   and "stackprof_samples_total" in f.message
                   for f in findings)

    def test_locktrace_family_consistent_usage_is_clean(self):
        # The KAI_LOCKTRACE validator counters (utils/locktrace.py,
        # published from /healthz + the Prometheus render path).
        src = ("from ..utils.metrics import METRICS\n"
               "def f(v):\n"
               "    METRICS.inc('locktrace_orders_recorded_total', v)\n"
               "    METRICS.inc('locktrace_contradictions_total', v)\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f for f in findings if f.rule == "KAI008"] == []

    def test_locktrace_cross_instrument_collision_fires(self):
        a = ("from ..utils.metrics import METRICS\n"
             "def f(v):\n"
             "    METRICS.inc('locktrace_orders_recorded_total', v)\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g(v):\n"
             "    METRICS.set_gauge('locktrace_orders_recorded_total',"
             " v)\n")
        findings = lint(("kai_scheduler_tpu/utils/a.py", a),
                        ("kai_scheduler_tpu/server.py", b))
        assert any(f.rule == "KAI008" and "one instrument" in f.message
                   and "locktrace_orders_recorded_total" in f.message
                   for f in findings)

    def test_engine_reuse_does_not_leak_rule_state(self):
        # A reused Engine is a supported caller (watch mode, hooks):
        # stateful rules must start fresh each run.
        engine = Engine(default_rules())
        a = ("from ..utils.metrics import METRICS\n"
             "def f():\n"
             "    METRICS.inc('good_name')\n")
        b = ("from ..utils.metrics import METRICS\n"
             "def g(v):\n"
             "    METRICS.observe('good_name', v)\n")
        path = "kai_scheduler_tpu/controllers/fix.py"
        assert engine.run_modules([(path, a)]).findings == []
        assert engine.run_modules([(path, b)]).findings == []


# ---------------------------------------------------------------------------
# engine mechanics: suppressions, baseline, CLI
# ---------------------------------------------------------------------------

class TestSuppressions:
    SRC = ("import time\n"
           "def a():\n"
           "    return time.time()\n")

    def test_standalone_comment_suppresses_next_line(self):
        src = ("import time\n"
               "def a():\n"
               "    # kailint: disable=KAI003 — wall-clock intentional\n"
               "    return time.time()\n")
        assert lint(("kai_scheduler_tpu/utils/fix.py", src)) == []

    def test_file_level_suppression(self):
        src = ("# kailint: disable-file=KAI003\n" + self.SRC)
        assert lint(("kai_scheduler_tpu/utils/fix.py", src)) == []

    def test_wrong_rule_id_does_not_suppress(self):
        src = ("import time\n"
               "def a():\n"
               "    return time.time()  # kailint: disable=KAI006\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI003" for f in findings)

    def test_suppressed_counted_in_report(self):
        src = ("import time\n"
               "def a():\n"
               "    return time.time()  # kailint: disable=all\n")
        report = Engine(default_rules()).run_modules(
            [("kai_scheduler_tpu/utils/fix.py", src)])
        assert report.findings == [] and report.suppressed >= 1

    def test_string_literal_mentioning_marker_does_not_suppress(self):
        # Only real comments suppress — a string that QUOTES the
        # suppression syntax (docs, log messages) must not disable
        # enforcement on its line.
        src = ("import time\n"
               "def a():\n"
               "    msg = '# kailint: disable=KAI003'\n"
               "    return time.time(), msg\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert any(f.rule == "KAI003" and f.line == 4 for f in findings)
        src2 = ("import time\n"
               "def a():\n"
               "    return time.time(), '# kailint: disable=all'\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src2))
        assert any(f.rule == "KAI003" for f in findings)

    def test_pending_consumed_by_inline_suppressed_line(self):
        # A standalone marker above a line that carries its own inline
        # suppression must attach to THAT line, not leak onto a later
        # unrelated line and hide a real finding there.
        src = ("import time\n"
               "def a():\n"
               "    # kailint: disable=KAI003\n"
               "    t = time.time()  # kailint: disable=all\n"
               "    return time.time()\n")
        findings = lint(("kai_scheduler_tpu/utils/fix.py", src))
        assert [f.line for f in findings if f.rule == "KAI003"] == [5]


class TestBaselineDrift:
    VIOLATION = ("import time\n"
                 "def backoff():\n"
                 "    return time.time() + 5\n")

    def _tree(self, tmp_path, extra: str = ""):
        pkg = tmp_path / "pkg" / "utils"
        pkg.mkdir(parents=True, exist_ok=True)
        (tmp_path / "pkg" / "__init__.py").write_text("")
        (pkg / "__init__.py").write_text("")
        (pkg / "mod.py").write_text(self.VIOLATION + extra)
        return str(tmp_path / "pkg")

    def test_baselined_violation_passes_new_violation_fails(self, tmp_path):
        root = self._tree(tmp_path)
        baseline_path = str(tmp_path / "baseline.json")
        engine = Engine(default_rules())
        report = engine.run([root])
        assert len(report.findings) == 1  # the seeded KAI003
        write_baseline(baseline_path, report.findings)

        # Same tree + baseline: clean.
        report = Engine(default_rules()).run(
            [root], baseline=load_baseline(baseline_path))
        assert report.findings == [] and len(report.baselined) == 1
        assert report.exit_code == 0

        # Introduce a NEW violation: only IT is reported.
        root = self._tree(tmp_path, extra=(
            "def retry_deadline():\n"
            "    return time.time() + 30\n"))
        report = Engine(default_rules()).run(
            [root], baseline=load_baseline(baseline_path))
        assert len(report.findings) == 1
        assert report.findings[0].line == 5
        assert report.exit_code == 1

    def test_filtered_run_does_not_misreport_stale(self, tmp_path):
        # An entry unmatched because its rule never ran is NOT stale.
        root = self._tree(tmp_path)
        baseline_path = str(tmp_path / "baseline.json")
        report = Engine(default_rules()).run([root])
        write_baseline(baseline_path, report.findings)  # KAI003 entry
        report = Engine(default_rules(), select={"KAI006"}).run(
            [root], baseline=load_baseline(baseline_path))
        assert report.stale_baseline == []

    def test_added_duplicate_of_baselined_line_still_fails(self, tmp_path):
        # Identical lines share a fingerprint; the baseline's count
        # caps how many it covers, so a NEW copy of an old sin fails.
        root = self._tree(tmp_path)
        baseline_path = str(tmp_path / "baseline.json")
        report = Engine(default_rules()).run([root])
        write_baseline(baseline_path, report.findings)
        # Add a second function whose flagged line is TEXTUALLY
        # identical to the baselined one (same fingerprint).
        (tmp_path / "pkg" / "utils" / "mod.py").write_text(
            self.VIOLATION +
            "def another():\n"
            "    return time.time() + 5\n")
        report = Engine(default_rules()).run(
            [root], baseline=load_baseline(baseline_path))
        # One occurrence covered, anything beyond it is new.
        assert len(report.baselined) == 1
        assert len(report.findings) == 1

    def test_non_utf8_file_is_an_error_not_a_crash(self, tmp_path):
        root = self._tree(tmp_path)
        (tmp_path / "pkg" / "utils" / "bin.py").write_bytes(
            b"# caf\xe9 latin-1 comment\nx = 1\n")
        report = Engine(default_rules()).run([root])
        assert any("bin.py" in e for e in report.errors)
        assert report.exit_code == 2

    def test_fixed_finding_reported_stale(self, tmp_path):
        root = self._tree(tmp_path)
        baseline_path = str(tmp_path / "baseline.json")
        report = Engine(default_rules()).run([root])
        write_baseline(baseline_path, report.findings)
        # "Fix" the violation; its baseline entry goes stale.
        (tmp_path / "pkg" / "utils" / "mod.py").write_text(
            "import time\ndef backoff(now=time.monotonic):\n"
            "    return now() + 5\n")
        report = Engine(default_rules()).run(
            [root], baseline=load_baseline(baseline_path))
        assert report.findings == []
        assert len(report.stale_baseline) == 1


class TestCLI:
    def _tree(self, tmp_path, src):
        pkg = tmp_path / "pkg" / "utils"
        pkg.mkdir(parents=True)
        (tmp_path / "pkg" / "__init__.py").write_text("")
        (pkg / "__init__.py").write_text("")
        (pkg / "mod.py").write_text(src)
        return str(tmp_path / "pkg")

    def test_exit_codes_and_json(self, tmp_path, capsys):
        root = self._tree(tmp_path,
                          "import time\ndef f():\n    return time.time()\n")
        baseline = str(tmp_path / "b.json")
        assert kailint_main([root, "--baseline", baseline,
                             "--format", "json"]) == 1
        out = json.loads(capsys.readouterr().out)
        assert out["exit_code"] == 1
        assert out["findings"][0]["rule"] == "KAI003"

        assert kailint_main([root, "--baseline", baseline,
                             "--write-baseline"]) == 0
        capsys.readouterr()
        assert kailint_main([root, "--baseline", baseline]) == 0

    def test_select_and_ignore(self, tmp_path, capsys):
        root = self._tree(tmp_path,
                          "import time\ndef f():\n    return time.time()\n")
        baseline = str(tmp_path / "b.json")
        assert kailint_main([root, "--baseline", baseline,
                             "--select", "KAI006"]) == 0
        assert kailint_main([root, "--baseline", baseline,
                             "--ignore", "KAI003"]) == 0
        # Whitespace after a comma must not silently drop a rule.
        assert kailint_main([root, "--baseline", baseline,
                             "--select", "KAI006, KAI003"]) == 1
        capsys.readouterr()

    def test_unknown_rule_id_is_an_error_not_a_green_run(self, tmp_path,
                                                         capsys):
        root = self._tree(tmp_path,
                          "import time\ndef f():\n    return time.time()\n")
        assert kailint_main([root, "--select", "KAI03"]) == 2
        assert kailint_main([root, "--ignore", "KAI999"]) == 2
        err = capsys.readouterr().err
        assert "unknown rule id" in err

    def test_corrupt_baseline_is_exit_2(self, tmp_path, capsys):
        root = self._tree(tmp_path,
                          "import time\ndef f():\n    return time.time()\n")
        bad = tmp_path / "b.json"
        bad.write_text("{not json")
        assert kailint_main([root, "--baseline", str(bad)]) == 2
        bad.write_text('{"entries": [{"rule": "KAI003"}]}')  # no fingerprint
        assert kailint_main([root, "--baseline", str(bad)]) == 2
        bad.write_text("[]")                     # valid JSON, wrong shape
        assert kailint_main([root, "--baseline", str(bad)]) == 2
        bad.write_text('{"entries": ["oops"]}')  # non-dict entry
        assert kailint_main([root, "--baseline", str(bad)]) == 2
        assert "kailint: error:" in capsys.readouterr().err

    def test_usage_errors(self, capsys):
        assert kailint_main([]) == 2
        assert kailint_main(["/nonexistent/path/xyz"]) == 2
        capsys.readouterr()

    def test_parse_error_is_exit_2_not_green(self, tmp_path, capsys):
        # A file the analyzer cannot parse is a file whose invariants
        # went unchecked — the gate must go red, not silently green.
        root = self._tree(tmp_path, "def broken(:\n")
        assert kailint_main([root, "--baseline",
                             str(tmp_path / "b.json")]) == 2
        capsys.readouterr()
        report = Engine(default_rules()).run([root])
        assert report.errors and report.exit_code == 2

    def test_write_baseline_refuses_partial_scan(self, tmp_path, capsys):
        # A parse error means a whole file went unchecked; regenerating
        # the ledger from that partial scan must be refused, not green.
        root = self._tree(tmp_path, "def broken(:\n")
        baseline = str(tmp_path / "b.json")
        assert kailint_main([root, "--baseline", baseline,
                             "--write-baseline"]) == 2
        assert not os.path.exists(baseline)
        assert "partial scan" in capsys.readouterr().err

    def test_write_baseline_refuses_rule_filters(self, tmp_path, capsys):
        # A --select'ed run sees a subset of findings; writing it out
        # would erase every other rule's entries from the ledger.
        root = self._tree(tmp_path,
                          "import time\ndef f():\n    return time.time()\n")
        baseline = str(tmp_path / "b.json")
        assert kailint_main([root, "--baseline", baseline,
                             "--select", "KAI003",
                             "--write-baseline"]) == 2
        assert not os.path.exists(baseline)
        err = capsys.readouterr().err
        assert "--select" in err

    def test_list_rules_names_all_eight(self, capsys):
        assert kailint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for i in range(1, 9):
            assert f"KAI00{i}" in out


# ---------------------------------------------------------------------------
# the package gate (the point of the exercise)
# ---------------------------------------------------------------------------

class TestPackageGate:
    def test_tree_is_clean_against_committed_baseline(self):
        """Zero non-baselined findings over the real package.  A failure
        here means a new commit violated one of the PR1/PR2 contracts —
        fix the code, suppress with a reason, or (last resort) baseline
        it via --write-baseline."""
        engine = Engine(default_rules())
        report = engine.run([PACKAGE], baseline=load_baseline(BASELINE))
        assert report.errors == []
        rendered = "\n".join(f.render() for f in report.findings)
        assert report.findings == [], (
            f"new kailint findings (see docs/STATIC_ANALYSIS.md):\n"
            f"{rendered}")

    def test_committed_baseline_is_small(self):
        entries = load_baseline(BASELINE)
        assert len(entries) <= 10, (
            "the baseline is a debt ledger, not a dumping ground — fix "
            "findings instead of baselining them")

    def test_cli_entrypoint_runs_clean(self):
        proc = subprocess.run(
            [sys.executable, "-m", "kai_scheduler_tpu.tools.kailint",
             "kai_scheduler_tpu/"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 new finding(s)" in proc.stdout


# ---------------------------------------------------------------------------
# the seam: actions ask framework/propose.py, they do not reach around it
# ---------------------------------------------------------------------------

ACTIONS = os.path.join(PACKAGE, "actions")
# Allocation kernels and their span helper: what only framework/propose.py
# may import.  actions/solvers.py keeps its own kernel,
# ops.scenario_batch.batch_prefix_feasibility.
KERNEL_MODULES = ("ops.allocate_grouped", "ops.allocate")
KERNEL_NAMES = {"allocate_grouped", "allocate_groups_kernel",
                "allocate_jobs_kernel", "fused_dispatch_span"}
SESSION_PRIVATES = {"_task_row", "_device_arrays"}


def _reach_arounds(source: str) -> list[str]:
    import ast
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.ImportFrom):
            module = node.module or ""
            names = {alias.name for alias in node.names}
            if module.split(".")[0] == "parallel" or "parallel" in names \
                    and not module:
                found.append(f"line {node.lineno}: imports from parallel")
            if module in KERNEL_MODULES and names & KERNEL_NAMES:
                found.append(f"line {node.lineno}: imports "
                             f"{sorted(names & KERNEL_NAMES)} from {module}")
            if module == "ops" and names & {"allocate_grouped", "allocate"}:
                found.append(f"line {node.lineno}: imports a kernel module")
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if ".parallel" in alias.name or any(
                        alias.name.endswith(m) for m in KERNEL_MODULES):
                    found.append(f"line {node.lineno}: import {alias.name}")
        elif isinstance(node, ast.Attribute) \
                and node.attr in SESSION_PRIVATES:
            found.append(f"line {node.lineno}: reads .{node.attr}")
    return found


class TestActionsAskPropose:
    @pytest.mark.parametrize("module", sorted(
        f for f in os.listdir(ACTIONS) if f.endswith(".py")))
    def test_no_action_reaches_around_the_seam(self, module):
        """No module under actions/ imports from ..parallel, imports an
        allocation kernel or ``fused_dispatch_span``, or reads
        ``_task_row`` / ``_device_arrays`` off a session."""
        with open(os.path.join(ACTIONS, module)) as f:
            assert _reach_arounds(f.read()) == []

    @pytest.mark.parametrize("source", (
        "from ..parallel.sharded_grouped import sharded_allocate_grouped",
        "from ..parallel import cluster_mesh",
        "from .. import parallel",
        "from ..ops.allocate_grouped import allocate_grouped",
        "from ..ops.allocate_grouped import fused_dispatch_span",
        "from ..ops.allocate import allocate_jobs_kernel",
        "from ..ops import allocate_grouped as ag",
        "def f(ssn, t):\n    return ssn._task_row(t)",
        "def f(ssn):\n    return ssn._device_arrays()",
    ))
    def test_the_check_sees_each_reach_around(self, source):
        assert _reach_arounds(source)

    def test_the_check_lets_the_prescreen_keep_its_kernel(self):
        assert _reach_arounds(
            "from ..ops.scenario_batch import batch_prefix_feasibility\n"
            "from ..ops.allocate_grouped import _next_pow2\n"
            "from ..framework import propose\n") == []


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
