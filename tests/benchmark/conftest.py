"""Shared paths for the benchmark's tests."""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "benchmark", "data", "tiny")


@pytest.fixture(scope="session")
def root():
    return ROOT


@pytest.fixture(scope="session")
def tiny():
    return TINY
