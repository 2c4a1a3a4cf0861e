"""``b1_1m_zipf.pub40``'s fast cases, counted with the tier-1 tests.

From ``benchmark/tests/test_correct_b1z.py`` come the cases that start no
broker: the ``exact_zipf`` generator (``exact_one_each``'s table, one stream in
every process, only subscribed topics, Zipf's share at the top rank, hot ranks
spread over owners) and the three ``routing.cache_*`` readers on made runs.
Its two ``--cpu`` rehearsals (``correct`` on a sound broker and under the
``drop`` control) stay where they are: ``python -m pytest benchmark/tests``
runs them.
"""

import importlib.util
import sys
from pathlib import Path

BENCH_TESTS = Path(__file__).resolve().parent.parent / "benchmark" / "tests"

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_test_correct_b1z", BENCH_TESTS / "test_correct_b1z.py")
_b1z = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _b1z
_spec.loader.exec_module(_b1z)

_REHEARSALS = {"test_sound_broker_is_correct",
               "test_control_dropped_delivery_is_not_correct"}

# every case of the file but the rehearsals, under its own name
globals().update({k: v for k, v in vars(_b1z).items()
                  if k.startswith("test_") and k not in _REHEARSALS})
