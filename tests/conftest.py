"""Shared test fixtures."""

import jax
import pytest


def make_abstract_mesh(shape=(16, 16), axes=("data", "model")):
    """AbstractMesh from parallel axis-size and axis-name tuples."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


@pytest.fixture
def fake_mesh():
    """Factory fixture (works under any pytest import mode, unlike a
    ``from conftest import ...`` in a test module): lets tests build
    specs for the production mesh without 512 devices — tests run
    single-device per the dry-run contract."""
    return make_abstract_mesh
