"""Shared paths for the benchmark's tests."""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "benchmark", "data", "tiny")
ARCH = os.path.join(ROOT, "tests", "benchmark", "data", "arch")


@pytest.fixture(scope="session")
def root():
    return ROOT


@pytest.fixture(scope="session")
def tiny():
    return TINY


@pytest.fixture(scope="session")
def arch():
    """The architecture that lives in files of its own (``data/arch``:
    configuration, cell, reference module, costs module). Its two
    modules become importable under the names the harness looks them up
    by, ``benchmark.reference.*`` and ``benchmark.costs.*``, as if the
    files had been added there; nothing in ``benchmark/`` is written."""
    import benchmark.costs
    import benchmark.reference

    added = [
        (benchmark.reference, os.path.join(ARCH, "reference")),
        (benchmark.costs, os.path.join(ARCH, "costs")),
    ]
    for package, directory in added:
        package.__path__.append(directory)
    yield ARCH
    for package, directory in added:
        package.__path__.remove(directory)
