"""``test_chipbench_readers.py`` holds one table (``EXPECTED``) of every
file under ``chipbench/metrics/`` and checks that none is missing from
it. Only a ``benchmark`` PR may edit a test that is there; a PR of
another kind that adds per-layer metrics brings their known answers in
a test file of its own, named below, and its table is joined to the
first one here, so that the completeness check still means "every
metric has a reader test with a known answer". A ``benchmark`` PR folds
the tables into one and drops the name from this list.
"""

import importlib

LATER_TABLES = ("test_chipbench_timeline_readers",)


def pytest_collection_modifyitems(items):
    first = next((item.module for item in items
                  if getattr(item, "module", None) is not None
                  and item.module.__name__ == "test_chipbench_readers"),
                 None)
    if first is not None:
        for name in LATER_TABLES:
            first.EXPECTED.update(importlib.import_module(name).EXPECTED)
