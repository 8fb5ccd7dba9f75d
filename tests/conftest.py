"""Test fixture: an 8-device virtual CPU mesh — the demo-cluster analog.

The reference tests multi-node behavior on a single host via
``make create-demo-cluster`` (gpAux/gpdemo/demo_cluster.sh); we do the same
with XLA's host-platform device-count override so every sharding/collective
path runs under pytest without TPU hardware.
"""

import os

# set before jax is imported: the suite runs on the virtual CPU mesh even
# on a machine that has a chip
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# The suite checks answers, not XLA's CPU optimizer, and a fresh checkout
# starts with a cold compile cache (<checkout>/.jax_cache): skipping the
# expensive LLVM passes took ~40% off the cold-compile share of a 154-test
# sample, which is what keeps a cold run inside tier-1's timeout.
if "xla_backend_optimization_level" not in flags:
    flags += (" --xla_backend_optimization_level=0"
              " --xla_llvm_disable_expensive_passes=true")
os.environ["XLA_FLAGS"] = flags.strip()

import faulthandler  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

# Hang forensics: tier-1 runs under `timeout -k 10 870`, which kills a hung
# suite SILENTLY. Dump every thread's stack shortly before that deadline so
# a future channel/collective hang leaves a traceback in the log instead of
# nothing (docs/ROBUSTNESS.md). repeat=False: one dump, no log spam.
# Close to the deadline on purpose: the dump reads every thread's frames
# while dozens of threads run, and a healthy-but-slow run died rc=139
# with no output just as it crossed the old 840 s mark (every test so far
# green), so a run that would still finish in time is not dumped.
_WATCHDOG_S = float(os.environ.get("GGTPU_TEST_WATCHDOG_S", "862"))
if _WATCHDOG_S > 0:
    faulthandler.dump_traceback_later(_WATCHDOG_S, repeat=False, exit=False)


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; the slow tier holds the long fuzz loops
    config.addinivalue_line(
        "markers", "slow: long fuzz/stress variants excluded from tier-1")
    # debug-mode lock-order assertions (docs/ANALYSIS.md): every
    # lockdebug.named() site created after this point records real
    # acquisition orders and fails the suite on an inversion — the
    # dynamic half of the `gg check` lock-order analyzer
    from greengage_tpu.runtime import lockdebug

    lockdebug.enable(True)
    # cross-role access witness (docs/ANALYSIS.md "Race analysis"): every
    # lockdebug.shared() structure created after this point records
    # (thread role, held-lock set) per access and fails the suite on the
    # first unprotected cross-role pair — the dynamic half of the
    # `gg check races` analyzer
    lockdebug.enable_races(True)


def pytest_sessionfinish(session, exitstatus):
    # a finished run must not leave the timer armed (it would fire inside
    # whatever process reuses this interpreter, e.g. pytest plugins' atexit)
    faulthandler.cancel_dump_traceback_later()
    # failure forensics (docs/OBSERVABILITY.md): counters live in THIS
    # process, so a post-mortem shell can't read them — dump the snapshot
    # and the newest statement trace here, where CI uploads them as
    # workflow artifacts alongside the cluster CSV logs
    if exitstatus not in (0, 5):   # 5 = no tests collected
        import json

        try:
            from greengage_tpu.runtime.logger import counters, histograms

            with open("/tmp/gg_tier1_counters.json", "w") as f:
                json.dump({"counters": counters.snapshot(),
                           "gauges": sorted(counters.gauges()),
                           "histograms": histograms.snapshot()},
                          f, indent=1, sort_keys=True)
        except Exception:
            pass
        try:
            from greengage_tpu.runtime.trace import TRACES, to_chrome

            tr = TRACES.last()
            if tr is not None:
                with open("/tmp/gg_tier1_trace.json", "w") as f:
                    json.dump(to_chrome(tr), f, indent=1)
        except Exception:
            pass


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]
