"""Tests of the benchmark harness import its modules by name."""

import sys

import harness

harness.add_paths()
if str(harness.SRC) not in sys.path:
    sys.path.insert(0, str(harness.SRC))
