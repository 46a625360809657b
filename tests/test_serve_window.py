"""``benchmark/tests/test_serve_window_cpu.py``'s nine cases under tier-1: the
serving window that every serving cell's ``correct`` rests on (a server under
its knee runs the whole window, one seed offers the same requests traced and
untraced, a broken decode program reads ``correct: false``, both traffic
files sit at 1.25 x their knees). ``benchmark/tests`` is not collected by
tier-1, and PR 34, a benchmark PR, could not add this file."""
import os
import sys

# That module's ``from conftest import CHECKOUT`` means its own directory's.
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))

from benchmark.tests.test_serve_window_cpu import *  # noqa: E402,F401,F403
