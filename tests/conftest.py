"""Test harness.

The reference's answer to "multi-node without a cluster" is forking N
processes over NCCL/Gloo on one host (tests/unit/common.py:16
@distributed_test). The TPU-native answer is simpler and faster: a single
process with a virtual 8-device CPU mesh
(--xla_force_host_platform_device_count), over which real NamedSharding /
collective lowering runs exactly as on a pod. Real-TPU tests can opt in via
DSTPU_TEST_TPU=1.
"""

import os

# Must happen before any backend initialisation: the virtual device
# count is read when the CPU backend starts.
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

if os.environ.get("DSTPU_TEST_TPU", "0") != "1":
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from deepspeed_tpu.utils.parity import (BF16_ATOL, BF16_RTOL,  # noqa: E402
                                        bf16_mismatch)

# Real-chip parity runs (DSTPU_TEST_TPU=1): kernel-vs-oracle comparisons
# assert at the bf16 floor of deepspeed_tpu/utils/parity.py (the rule and
# its measurements are documented there). Scoped to the KERNEL-parity
# modules only (an autouse fixture below) so engine/optimizer/checkpoint
# assertions keep their exact tolerances on TPU runs too.
_TPU_PARITY_NAMES = ("test_flash_attention", "test_sparse_attention",
                     "test_xent", "test_serving_fastpath",
                     "test_chunked_prefill", "test_fused_update")
_TPU_PARITY_MODULES = _TPU_PARITY_NAMES + tuple(
    f"tests.{name}" for name in _TPU_PARITY_NAMES)
_ORIG_ALLCLOSE = np.testing.assert_allclose


def _tpu_allclose(actual, desired, rtol=1e-7, atol=0, **kw):
    rt, at = max(rtol, BF16_RTOL), max(atol, BF16_ATOL)
    try:
        return _ORIG_ALLCLOSE(actual, desired, rtol=rt, atol=at, **kw)
    except AssertionError:
        msg = bf16_mismatch(actual, desired, rtol=rt, atol=at)
        if msg is not None:
            raise AssertionError(msg) from None


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _tpu_kernel_parity_tolerance(request, monkeypatch):
    """See the bf16-floor note above: active only on DSTPU_TEST_TPU=1 runs
    and only inside the kernel-parity modules."""
    if (os.environ.get("DSTPU_TEST_TPU", "0") == "1"
            and request.module.__name__ in _TPU_PARITY_MODULES):
        monkeypatch.setattr(np.testing, "assert_allclose", _tpu_allclose)
    yield
