"""A pin of position that a later cell makes false.

``test_trickle.py::test_the_cell_follows_what_was_there`` (PR 42) says
that the trickle cell is the LAST configuration, cell and entry of
``group_rounds_per_s.workloads`` and that there are seven cells. PR 47
appends ``engine1m-r3-zipf.ycsb-a`` after it, as the contract asks (new
entries go to the end of their lists), and may not edit a file the
benchmark already has. The test is therefore expected to fail, strictly:
the `benchmark` PR that turns the pin into a rule (``test_load.py::
test_the_cell_follows_what_was_there`` shows one: "after what was there",
not "last") makes it pass again and this file fail, and takes this file
away.
"""

import pytest

OUTDATED = ("tests/benchmark/test_trickle.py::"
            "test_the_cell_follows_what_was_there")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid == OUTDATED:
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins the trickle cell as the last of seven; PR 47 "
                       "appended an eighth and may not edit this file"))
