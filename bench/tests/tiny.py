"""The benchmark's cells at a size a CPU test run holds: the same harness
and metric readers, with the configurations and traffic of ``fixtures/``
(20,000 rows, 64 clusters, nprobe 8, a 256-query pool) and the cells and
metrics of ``fixtures/benchmark.json``."""
import json
import os

from bench import spec

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
CELLS = ("deep1b-ivf.online", "deep1b-flat.offline")


def benchmark() -> dict:
    with open(os.path.join(FIXTURES, "benchmark.json")) as f:
        return json.load(f)


def cell(name: str) -> spec.Cell:
    return spec.load_cell(name, benchmark(), traffic_dir=FIXTURES)
