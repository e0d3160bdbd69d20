"""Where the benchmark lives, for the tests of this directory."""

import os

from benchmarks.chip import manifest as manifest_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
RUN = os.path.join(BENCH, "run.py")


def manifest():
    return manifest_mod.load(ROOT)
