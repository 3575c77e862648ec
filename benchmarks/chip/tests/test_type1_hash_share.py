"""The reader of ``tm.type1_hash_share``: the mean over the span rounds
of the round's counter, in %, and nothing from a program without it.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

from repro.fl.obs.tracer import Spans  # noqa: E402

NAME = "tm.type1_hash_share"


def _reader():
    return harness.load_module("metrics", NAME)


def _spans(counts):
    return Spans({"round": 1.0}, {}, {}, counts)


def test_reads_the_mean_of_the_rounds_counters_in_percent():
    rec = {"spans": [_spans({NAME: 0.25}), _spans({NAME: 0.5})]}
    assert _reader().read(rec) == pytest.approx(37.5)


def test_reads_nothing_without_the_counter():
    read = _reader().read
    assert read({}) is None
    assert read({"spans": []}) is None
    # an MLP round charges no counter; one round without it voids all
    assert read({"spans": [_spans({}), _spans({NAME: 0.5})]}) is None


def test_reads_nothing_from_spans_that_carry_no_counters():
    """A program that predates the counter hands spans without
    ``counts``: the metric is left out, not an error."""
    class OldSpans(dict):
        compiles: dict = {}

    assert _reader().read({"spans": [OldSpans(round=1.0)]}) is None
