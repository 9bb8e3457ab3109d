"""Test harness config (SURVEY.md §4 conclusions):

- force the CPU backend with 8 virtual devices
  (`xla_force_host_platform_device_count`) so every DP/TP/PP/SP/EP test
  runs on a faked mesh with no TPU — the translation of the reference's
  `tools/launch.py --launcher local` multi-process-on-one-host testing.
- must run BEFORE any computation: the platform and device count are
  read once, when the backend starts.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
# same placement rule as runtime.use_compile_cache, spelled out here
# because the package must not be imported before the lock-witness block
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"))

# Lock witness (MXTPU_LOCK_WITNESS=1): must be installed BEFORE the
# package is imported so module-level locks (telemetry registries,
# flight recorder) are created through the patched factories.  The
# module is loaded by file path and pre-registered in sys.modules —
# a normal `from incubator_mxnet_tpu import lock_witness` would run
# the package __init__ first, creating those locks un-witnessed.
_LOCK_WITNESS = None
if os.environ.get("MXTPU_LOCK_WITNESS") == "1":
    import importlib.util
    import sys

    _spec = importlib.util.spec_from_file_location(
        "incubator_mxnet_tpu.lock_witness",
        os.path.join(os.path.dirname(__file__), "..",
                     "incubator_mxnet_tpu", "lock_witness.py"))
    _LOCK_WITNESS = importlib.util.module_from_spec(_spec)
    sys.modules["incubator_mxnet_tpu.lock_witness"] = _LOCK_WITNESS
    _spec.loader.exec_module(_LOCK_WITNESS)
    _LOCK_WITNESS.install()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

if os.environ.get("MXTPU_CHECK_TRACER_LEAKS") == "1":
    # surfaces tracers that escape their trace (stashed on self, returned
    # through closures); ~2x tracing overhead, so opt-in
    jax.config.update("jax_check_tracer_leaks", True)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _retrace_guard(request):
    """Fail any test whose watched programs recompile beyond the budget.

    Counting is keyed by callable name (the only identity JAX's compile
    log carries), so the guard watches only the package's jitted program
    names and the budget is per-test.  MXTPU_RETRACE_GUARD=0 disables;
    MXTPU_RETRACE_BUDGET overrides the default of 64.
    """
    if os.environ.get("MXTPU_RETRACE_GUARD", "1") == "0":
        yield
        return
    from incubator_mxnet_tpu.retrace_guard import PROGRAM_NAMES, RetraceGuard

    with RetraceGuard(watch=PROGRAM_NAMES) as guard:
        yield guard


def pytest_sessionfinish(session, exitstatus):
    """Witness contract at end of a MXTPU_LOCK_WITNESS=1 run: the
    observed held-while-acquiring graph must be acyclic and a subset
    of tpulint's static lock graph."""
    if _LOCK_WITNESS is None or not _LOCK_WITNESS.installed():
        return
    stats = _LOCK_WITNESS.assert_clean()
    print(f"\nlock witness: {stats['edges']} edge(s) over "
          f"{stats['tracked_locks']} tracked lock(s), acyclic, "
          f"all in the static graph "
          f"(contention {stats['contention_seconds']:.3f}s)")


@pytest.fixture
def mesh8():
    import incubator_mxnet_tpu.parallel as par

    return par.create_mesh(data=8)


@pytest.fixture
def mesh42():
    import incubator_mxnet_tpu.parallel as par

    return par.create_mesh(data=4, model=2)
