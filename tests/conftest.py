"""Test harness: 8 virtual CPU devices — the JAX analogue of Spark
``local[*]`` (SURVEY.md §4): every collective path is exercised on CPU with
no TPU attached. Must configure XLA before anything imports jax.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

from tpu_distalg.parallel import get_mesh  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    return get_mesh(data=8)


@pytest.fixture(scope="session")
def mesh4():
    """4-replica mesh matching the reference's n_slices=4."""
    return get_mesh(data=4, devices=jax.devices()[:4])


@pytest.fixture(scope="session")
def mesh1():
    return get_mesh(data=1, devices=jax.devices()[:1])


@pytest.fixture(scope="session")
def mesh_2x4():
    return get_mesh(data=2, model=4)


@pytest.fixture(scope="session")
def cancer_data():
    from tpu_distalg.utils import datasets

    return datasets.breast_cancer_split()
