"""Regression tests for the RUN_SLOW env-var truthiness rules (conftest).

A CI fork once enabled every slow test by exporting ``RUN_SLOW=0`` — any
non-empty string was truthy.  The parsing now lives in one pure helper with
an explicit falsy set; these tests pin it down.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest


def _this_conftest():
    """This directory's ``conftest.py``, loaded by path: ``bench/tests`` has
    a ``conftest.py`` of its own, and the bare module name ``conftest`` is
    whichever of the two pytest loaded last."""
    spec = importlib.util.spec_from_file_location("tests_conftest",
                                                  Path(__file__).with_name("conftest.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


run_slow_enabled = _this_conftest().run_slow_enabled


@pytest.mark.parametrize(
    "value",
    [None, "", "  ", "0", "false", "False", "FALSE", " 0 ", "no", "No", "off", "OFF"],
)
def test_falsy_values_keep_fast_lane(value):
    assert run_slow_enabled(value) is False


@pytest.mark.parametrize("value", ["1", "true", "True", "yes", "on", " 1 ", "anything"])
def test_truthy_values_enable_slow_tests(value):
    assert run_slow_enabled(value) is True
