"""Tier-1's guard of what the cell ``nemotron-3-nano-30b-a3b.reason-decode``
needs from the program.

As ``tests/test_benchmark_trinity.py`` for the configuration before it, in a
file of its own so that the test runner's workers share the rehearsals: the
configuration's counts against hand-worked numbers, its file's cut against
``published`` and the catalog (by agreement on the keys both have), the
program's tree, pool and slot state against the counts, its readers where
there is nothing to read and by hand, the lists the cell joins, the
``--rehearse`` runs of the cell (sound, and with one mixer layer skipped,
which has to read not correct) and each planted fault's launcher."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_nemotron_h_cell")

from benchmark.tests.test_nemotron_h_cell import (  # noqa: E402,F401
    nemotron_config,
    test_each_nemotron_launcher_plants_the_fault_it_says,
    test_nemotron_counter_readers_by_hand,
    test_nemotron_counts_by_hand,
    test_nemotron_published_agrees_with_the_catalog_where_both_speak,
    test_nemotron_readers_find_nothing_where_there_is_nothing_to_read,
    test_rehearsal_of_the_nemotron_cell,
    test_the_nemotron_cell_joins_the_lists_the_issue_names,
    test_the_nemotron_file_states_the_cut_the_floors_and_every_published_width,
    test_the_nemotron_files_name_no_other_architecture,
    test_the_nemotron_program_holds_what_the_counts_say,
    test_the_nemotron_rehearsal_overlay_is_the_tiny_models_sizes,
    test_the_two_new_metrics_are_files_on_readers_that_were_there,
    test_with_a_mixer_layer_skipped_the_cell_is_not_correct,
)
