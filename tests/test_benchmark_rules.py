"""The benchmark's own rules, counted with the tier-1 tests.

``benchmark/tests/test_validity.py`` (the validity rule on a scripted broker
and a clock of its own, ``acks_out_of_order`` in the publisher child, the two
readers that print what they know) runs here as it stands: its cases are fast
and touch no process. From ``benchmark/tests/test_correct.py`` comes the one
case that plays the rule through a whole ``--cpu`` rehearsal: a broker whose
``hybrid_max`` no batch can pass ends the run in words, nothing left running.
The file's other cases (rehearsals of ``correct`` under faults) stay where
they are: ``python -m pytest benchmark/tests`` runs them.
"""

import importlib.util
import sys
from multiprocessing import resource_tracker
from pathlib import Path

import pytest

BENCH_TESTS = Path(__file__).resolve().parent.parent / "benchmark" / "tests"


def _load(name: str):
    spec = importlib.util.spec_from_file_location(f"benchmark_tests_{name}",
                                                  BENCH_TESTS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


_validity = _load("test_validity")
_correct = _load("test_correct")

# every case and fixture of test_validity.py, under its own name
globals().update({k: v for k, v in vars(_validity).items() if not k.startswith("__")})



@pytest.fixture
def short_rehearsal(monkeypatch):
    """``test_correct.py``'s autouse ``short_run``, for the one case that wants
    it here (the scripted cases above hold ``warm_up`` to its real constants)."""
    for name, seconds in (("WARMUP_MIN_S", 2.0), ("WARMUP_CAP_S", 6.0),
                          ("SETTLE_LIMIT_S", 5.0)):
        monkeypatch.setattr(_correct.cell, name, seconds)


def test_a_run_that_never_offers_the_device_a_batch_ends_in_words(
        short_rehearsal, monkeypatch, capfd):
    # the case counts this process's children before and after: the spawn
    # context's resource tracker, a child that stays, has to be up already
    # (in its own file an earlier case has started it)
    resource_tracker.ensure_running()
    _correct.test_a_run_that_never_offers_the_device_a_batch_ends_in_words(
        monkeypatch, capfd)
