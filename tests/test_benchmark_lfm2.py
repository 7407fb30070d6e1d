"""Tier-1's guard of what the cell ``lfm2-8b-a1b.conv-decode`` needs from the
program.

As ``tests/test_benchmark_nemotron_h.py`` for a configuration before it, in a
file of its own so that the test runner's workers share the rehearsals: the
configuration's counts against hand-worked numbers (3,197M parameters, 4,096
B a context token, 65,536 B a slot; 4,667M, 6,144 B and 90,112 B at ISSUE
55's first depth; 8.34B and 1.56B for ``published``), its
file's cut against ``published`` and the catalog (by agreement on the keys
both have), the program's tree, pool and tail against the counts, its
readers where there is nothing to read and by hand, the lists the cell joins,
the ``--rehearse`` runs of the cell (sound, and with the tail zeroed every
16th token step, which has to read not correct) and each planted fault's
launcher."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_lfm2_cell")

from benchmark.tests.test_lfm2_cell import (  # noqa: E402,F401
    lfm2_config,
    test_each_lfm2_launcher_plants_the_fault_it_says,
    test_lfm2_counts_by_hand,
    test_lfm2_published_agrees_with_the_catalog_where_both_speak,
    test_lfm2_readers_by_hand,
    test_lfm2_readers_find_nothing_where_there_is_nothing_to_read,
    test_rehearsal_of_the_lfm2_cell,
    test_the_lfm2_cell_joins_the_lists_the_issue_names,
    test_the_lfm2_file_states_the_cut_the_floors_and_every_published_width,
    test_the_lfm2_files_name_no_other_architecture,
    test_the_lfm2_program_holds_what_the_counts_say,
    test_the_lfm2_rehearsal_overlay_is_the_tiny_models_sizes,
    test_the_new_metrics_are_files_with_this_cell_alone,
    test_with_the_tail_zeroed_every_16th_step_the_cell_is_not_correct,
)
