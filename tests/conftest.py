import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Tests run on the single real CPU device (the dry-run subprocesses set
# their own XLA_FLAGS); keep math deterministic.
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The kernels compile with Mosaic unless asked otherwise (repro.kernels
# .interpret): the CPU suite asks for the Pallas interpreter here, and the
# subprocess drivers inherit the setting through the environment.
os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")

import pytest  # noqa: E402  (path shim must run first)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "kernel: Pallas kernel oracle-parity tests — execute (not skip) on "
        "CPU in the Pallas interpreter; ci.yml runs them as a dedicated "
        "step (`make test-kernels`)")
    config.addinivalue_line(
        "markers",
        "mesh: multi-device sharded-serving parity tests — execute (not "
        "skip) on CPU-only boxes: the CI `mesh` job and `make test-mesh` "
        "force XLA_FLAGS=--xla_force_host_platform_device_count=8, and "
        "the suites' subprocess drivers force it themselves so plain "
        "`make test` covers them too")
    config.addinivalue_line(
        "markers",
        "audit: static hot-path auditor suite — compiles (never executes) "
        "every serve-step cell and checks donation/gather/dtype/roofline "
        "invariants on the optimized HLO, plus the jaxlint AST pass and "
        "injected-violation regressions; the CI `audit` job and `make "
        "test-audit` run it as its own lane (mesh cells go through a "
        "subprocess that forces 8 host devices itself)")


@pytest.fixture
def interpret_mode(monkeypatch):
    """Run the Pallas kernels in the interpreter for this test, whatever
    the process setting (repro.kernels.interpret)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
