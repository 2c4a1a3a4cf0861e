"""``b1_1m_share.pub40``'s cases, counted with the tier-1 tests.

Every case of ``benchmark/tests/test_correct_b1s.py`` starts no broker: the
``exact_zipf_share`` generator (the group rows, then ``exact_zipf``'s table;
the members subscribers 0-63; the plain reference's answer; ``exact_zipf``'s
stream over the exact rows), the two ``shared.*`` readers on made runs, and
the cell's place on the metrics' lists.
"""

import importlib.util
import sys
from pathlib import Path

BENCH_TESTS = Path(__file__).resolve().parent.parent / "benchmark" / "tests"

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_test_correct_b1s", BENCH_TESTS / "test_correct_b1s.py")
_b1s = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _b1s
_spec.loader.exec_module(_b1s)

# every case of the file, under its own name
globals().update({k: v for k, v in vars(_b1s).items() if k.startswith("test_")})
