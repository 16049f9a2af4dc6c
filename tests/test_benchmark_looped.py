"""The benchmark's CPU tests of the Ouro cell
(``benchmark/tests/test_looped.py``), collected here so that they run under
tier-1's command, which collects ``tests/`` only."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests.test_looped import *  # noqa: E402,F401,F403
