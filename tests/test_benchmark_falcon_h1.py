"""Tier-1's guard of what the cell ``falcon-h1-34b.ssm-decode`` needs from the
program.

A file of its own (``tests/test_benchmark_manifest.py`` held it until PR 50),
so that the test runner's workers share the rehearsals, whose bodies live in
``benchmark/tests/test_falcon_h1_cell.py``: the configuration's counts against
hand-worked numbers, its file's cut and floors against ``published`` and the
catalog, the program's tree and state against the counts, its readers, the
lists the cell joins, the rehearsal overlay, the ``--rehearse`` runs of the
cell (sound, and with a state zeroed every 16th step, which has to read not
correct) and each planted fault's launcher."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_falcon_h1_cell")

from benchmark.tests.test_falcon_h1_cell import (  # noqa: E402,F401
    falcon_config,
    test_a_state_zeroed_every_16th_step_is_not_correct as
    test_a_falcon_state_zeroed_every_16th_step_is_not_correct,
    test_each_falcon_launcher_plants_the_fault_it_says,
    test_falcon_counter_readers_by_hand,
    test_falcon_counts_by_hand,
    test_falcon_readers_find_nothing_where_there_is_nothing_to_read,
    test_published_agrees_with_the_catalog_where_both_speak,
    test_rehearsal_of_the_falcon_cell,
    test_the_falcon_cell_joins_the_lists_the_issue_names,
    test_the_falcon_file_states_the_cut_the_floors_and_every_published_width,
    test_the_falcon_files_name_no_other_architecture,
    test_the_falcon_program_holds_what_the_counts_say,
    test_the_falcon_rehearsal_overlay_is_the_tiny_models_sizes,
    test_the_two_new_metrics_are_files_on_readers_that_were_there,
)
