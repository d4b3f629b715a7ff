"""CPU test set-up for the benchmark's own tests: 8 virtual devices (as
tests/conftest.py asks), and tiny copies of the cells."""
import dataclasses
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + flags)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import pytest  # noqa: E402

from bench import spec  # noqa: E402

TINY_BATCH = 16


def tiny(cell_name: str, chips: int = None):
    """A cell at its program's tiny size (``tiny_config``) and batch 16,
    with the cell's own limits."""
    cell = spec.load_cell(cell_name)
    return dataclasses.replace(
        cell, config=cell.program.tiny_config(cell.config),
        chips=chips or cell.chips,
        traffic=dict(cell.traffic, global_batch=TINY_BATCH))


@pytest.fixture
def tiny_cell():
    return tiny
