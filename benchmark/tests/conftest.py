"""The benchmark's own tests run on the CPU, outside ``tests/``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="session")
def tiny_bench() -> dict:
    with open(os.path.join(DATA, "bench_tiny.json")) as f:
        return json.load(f)


@pytest.fixture
def tiny_cell(tiny_bench):
    from benchmark import harness

    return lambda name: harness.Cell(name, tiny_bench, base=DATA)
