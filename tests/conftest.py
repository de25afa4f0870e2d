"""Test harness: single-process multi-device simulation.

The reference runs its suite SPMD under ``mpiexec -n {2,4,8}``
(ref ``Makefile:53-62``). Here the same coverage runs in ONE process on a
virtual 8-device CPU mesh via ``--xla_force_host_platform_device_count``
— something the reference cannot do (SURVEY §4 implication (a)). f64 is
enabled so oracle comparisons against NumPy are bit-meaningful.

The CPU is REQUESTED here (``jax.config.update('jax_platforms', 'cpu')``
before the first backend use), so the suite runs the same way with or
without an accelerator attached; nothing in the package falls back to
it on its own.
"""

import os

# Mesh size is env-driven so CI can run the suite at {2, 4, 8} devices
# plus a ragged-heavy non-power count (5), mirroring the reference's
# rank matrix (ref .github/workflows/build.yml:15-27). Default stays 8.
NDEV = int(os.environ.get("PYLOPS_MPI_TPU_TEST_DEVICES", "8"))

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={NDEV}").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _reset_fft_mode():
    """The local-FFT engine mode is cached at first use for determinism
    (ops/dft.py); tests that monkeypatch PYLOPS_MPI_TPU_FFT_MODE need a
    fresh resolution each test.

    Unlike ``set_fft_mode``, this does NOT clear JAX's jit caches.
    That is safe *for this suite* because no compiled executable can
    survive a mode flip into the wrong test: the fused-solver cache is
    keyed on ``id(Op)`` with the operator instance pinned in the entry
    (solvers/basic.py ``_get_fused``) and every test builds fresh
    instances; operator matvec jits and shard_map kernels are
    per-instance / per-call closures (new function identity → retrace,
    which re-resolves the mode); and eager ``dft.fft``-family calls
    branch on the mode in Python before any dispatch. Code outside the
    suite that flips modes on live operators must use ``set_fft_mode``.
    """
    from pylops_mpi_tpu.ops import dft
    dft._mode_cache = None
    dft._base_cache = None
    yield
    dft._mode_cache = None
    dft._base_cache = None


@pytest.fixture(scope="session")
def ndev():
    """Actual device count (== NDEV unless XLA_FLAGS was pre-set)."""
    return len(jax.devices())
