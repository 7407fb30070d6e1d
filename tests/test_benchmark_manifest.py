"""Tier-1's guard of what the benchmark needs from the program.

The benchmark's own tests live in ``benchmark/tests/`` (``python -m pytest
benchmark/tests -q``), which the tier-1 command does not run. This thin file
runs, from there, the checks of the shipped manifest. Each configuration
that came after GPT-2 has a thin file of its own beside this one
(``tests/test_benchmark_<family>.py``: its counts against hand-worked
numbers, its file's cut against ``published``, its readers and the
``--rehearse`` runs of its cell, sound and with a planted fault that has to
read not correct), so that a PR that breaks what a cell reads from the
program (a program's name, a counter, the family seam) fails tier-1, and so
that the test runner, whose unit is a file, can run the cells' rehearsals
beside one another."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_manifest")

from benchmark.tests.test_manifest import (  # noqa: E402,F401
    test_check_names_a_configuration_that_is_not_whole,
    test_every_cell_resolves_and_every_moves_is_reported,
    test_names_units_and_entry_keys,
    test_no_driver_or_reader_names_an_architecture,
    test_shipped_manifest_is_sound,
)
