"""Tier-1's guard of what the cell ``trinity-large-preview.window-decode``
needs from the program.

As ``tests/test_benchmark_manifest.py`` for the four configurations before
it, in a file of its own so that the test runner's workers share the
rehearsals: the configuration's counts against hand-worked numbers, its
file's cut against ``published`` and the catalog, the program's tree, pool
and rings against the counts, its readers where there is nothing to read and
by hand, the lists the cell joins, the ``--rehearse`` runs of the cell
(sound, and with the window ignored, which has to read not correct) and each
planted fault's launcher."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_trinity_cell")

from benchmark.tests.test_trinity_cell import (  # noqa: E402,F401
    test_each_trinity_launcher_plants_the_fault_it_says,
    test_rehearsal_of_the_trinity_cell,
    test_the_new_metrics_are_files_and_two_ride_a_reader_that_was_there,
    test_the_roofline_reader_by_hand,
    test_the_trinity_cell_joins_the_lists_the_issue_names,
    test_the_trinity_file_states_the_cut_the_floors_and_every_published_width,
    test_the_trinity_files_name_no_other_architecture,
    test_the_trinity_program_holds_what_the_counts_say,
    test_the_trinity_rehearsal_overlay_is_the_tiny_models_sizes,
    test_trinity_counter_readers_by_hand,
    test_trinity_counts_by_hand,
    test_trinity_published_agrees_with_the_catalog_where_both_speak,
    test_trinity_readers_find_nothing_where_there_is_nothing_to_read,
    test_with_the_window_ignored_the_cell_is_not_correct,
    trinity_config,
)
