"""Tier-1's guard of what the cell ``longcat-flash-omni.moe-decode`` needs from
the program.

A file of its own (``tests/test_benchmark_manifest.py`` held it until PR 50),
so that the test runner's workers share the rehearsals, whose bodies live in
``benchmark/tests/test_longcat_cell.py``: the configuration's counts against
hand-worked numbers, its file's cut against ``published``, its readers where
there is nothing to read and by hand, the rehearsal overlay and the
``--rehearse`` runs of the cell (sound, and with a planted fault that has to
read not correct)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_longcat_cell")

from benchmark.tests.test_longcat_cell import (  # noqa: E402,F401
    config,
    test_counter_readers_by_hand,
    test_counts_by_hand,
    test_readers_find_nothing_on_a_program_without_the_counters,
    test_rehearsal_of_the_cell,
    test_the_file_states_the_cut_and_every_published_width,
    test_the_rehearsal_overlay_is_the_tiny_models_sizes,
)
