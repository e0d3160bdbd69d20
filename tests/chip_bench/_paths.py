"""Where the benchmark lives, for the tests of this directory."""

import os

from benchmarks.chip import manifest as manifest_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
RUN = os.path.join(BENCH, "run.py")
# for a child process: this checkout first, then whatever path the tests
# themselves were started with (a copy of the benchmark in a temporary
# checkout finds ``deepspeed_tpu`` there)
PYTHONPATH = os.pathsep.join(
    p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)


def manifest():
    return manifest_mod.load(ROOT)
