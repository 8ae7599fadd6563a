"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Tier-1 runs on the CPU in x64; shardings are validated on a virtual
8-device CPU mesh (``__graft_entry__.dryrun_multichip`` uses whatever
devices JAX finds, so under this harness it finds these).
"""

import os

# Force CPU even if the outer environment points at an accelerator: tests
# need x64 determinism and the virtual 8-device mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# KAI_LOCKTRACE=1 (chaos_matrix --races): install the runtime lock-order
# validator BEFORE any suite module constructs scheduler objects — locks
# created before install are invisible to the journal.  The shim dumps
# observed acquisition orders to KAI_LOCKTRACE_OUT at process exit; the
# matrix harness joins them against the static kairace lock graph.
if os.environ.get("KAI_LOCKTRACE"):
    from kai_scheduler_tpu.utils.locktrace import install_from_env

    install_from_env()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

# Persistent compilation cache (the one helper every entry point shares):
# repeated suite runs and the scale ring skip recompiles, so first-cycle
# numbers measure the scheduler, not XLA.
from kai_scheduler_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache)

enable_compile_cache()
