"""Test config: run JAX on a virtual 8-device CPU mesh.

Tests never require the real TPU; multi-chip sharding logic is exercised
on 8 virtual CPU devices, Pallas kernels in interpret mode.  The env
vars are set BEFORE jax initializes: JAX_PLATFORMS=cpu is also what
tells the device codec that JAX-on-CPU was asked for
(ec_context.device_info refuses a JAX that merely fell back to it).
The compiled kernels and the chip-owning worker are checked on the
chip by chip_smoke.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import importlib.util  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

assert jax.default_backend() == "cpu"

# Shared skip marker for the optional `cryptography` wheel (iam kms,
# sftp transport, tls cert minting, s3 sse-c/sse-kms).  A decorator —
# not an in-body importorskip — so guarded tests skip BEFORE their
# cluster fixtures boot (the tier-1 budget is tight; a skipped test
# must cost ~0s).
needs_crypto = pytest.mark.skipif(
    importlib.util.find_spec("cryptography") is None,
    reason="needs the optional `cryptography` wheel")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running scenario (excluded from tier-1's "
        "`-m 'not slow'` fast pass)")


@pytest.fixture(scope="session")
def package_analysis():
    """ONE full-package analyzer scan per tier-1 run, shared by
    test_analyze_clean's CI gate and every lint's *_repo_is_clean
    test.  A full scan costs ~7 s on this box and SIX of them ran
    per round before ISSUE 13's budget pass — this fixture is where
    ~25 s of tier-1 wall went."""
    import os

    from seaweedfs_tpu.devtools.analyze import repo_root, run_paths
    findings, errors = run_paths(
        [os.path.join(repo_root(), "seaweedfs_tpu")])
    assert errors == [], f"unparsable sources: {errors}"
    return findings
