"""``uns_1m_isa95.pub40``'s fast cases, counted with the tier-1 tests.

From ``benchmark/tests/test_correct_uns.py`` come the cases that start no
broker: the ``isa95_uns`` generator (the seed's table in every process with
its four kinds of row, at full size and at the rehearsal's; a stream of the
namespace's tags only; the plain reference's 3.11 pairs a publish), the two
``matcher.*`` readers on made runs, and the cell's place on the metrics'
lists. Its two ``--cpu`` rehearsals (``correct`` on a sound broker and under
the ``drop`` control) stay where they are: ``python -m pytest
benchmark/tests`` runs them.
"""

import importlib.util
import sys
from pathlib import Path

BENCH_TESTS = Path(__file__).resolve().parent.parent / "benchmark" / "tests"

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_test_correct_uns", BENCH_TESTS / "test_correct_uns.py")
_uns = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _uns
_spec.loader.exec_module(_uns)

_REHEARSALS = {"test_sound_broker_is_correct",
               "test_control_dropped_delivery_is_not_correct"}

# every case of the file but the rehearsals, under its own name
globals().update({k: v for k, v in vars(_uns).items()
                  if k.startswith("test_") and k not in _REHEARSALS})
