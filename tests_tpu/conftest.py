"""On-chip numerics tests — run on a TPU host (through the chip tool):

    python -m pytest tests_tpu -q

Unlike ``tests/`` (which forces an 8-virtual-device CPU mesh), this
directory runs on the accelerator JAX finds, and without a TPU it is a
usage ERROR, not a skip: a suite that "passes" anywhere proves nothing
about the chip. One process per chip — do not run it under xdist.
"""

import jax
import pytest

from tpu_distalg.utils import compile_cache


def pytest_configure(config):
    backend = jax.default_backend()
    if backend != "tpu":
        raise pytest.UsageError(
            f"tests_tpu needs a TPU: the default jax backend is "
            f"{backend!r} (the CPU suite is tests/)")
    compile_cache.configure()


@pytest.fixture(scope="session")
def tpu_mesh():
    from tpu_distalg.parallel import get_mesh

    return get_mesh()


@pytest.fixture(scope="session")
def cancer_data():
    from tpu_distalg.utils import datasets

    return datasets.breast_cancer_split()
