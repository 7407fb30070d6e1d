"""Tier-1's guard of what the cell ``mimo-v2-flash.swa-decode`` needs from
the program.

As ``tests/test_benchmark_trinity.py`` for the configuration before it, in a
file of its own so that the test runner's workers share the rehearsals: the
configuration's counts against hand-worked numbers, its file's cut against
``published`` and the catalog, the program's tree, pool and rings against the
counts, the new reader where there is nothing to read and by hand, the lists
the cell joins, the ``--rehearse`` runs of the cell (sound, and with the sink
left out, which has to read not correct) and each planted fault's launcher."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_mimo_v2_cell")

from benchmark.tests.test_mimo_v2_cell import (  # noqa: E402,F401
    mimo_config,
    test_each_mimo_launcher_plants_the_fault_it_says,
    test_mimo_counts_by_hand,
    test_mimo_published_agrees_with_the_catalog_where_both_speak,
    test_rehearsal_of_the_mimo_cell,
    test_the_mimo_cell_joins_the_lists_the_issue_names,
    test_the_mimo_file_states_the_cut_the_floors_and_every_published_width,
    test_the_mimo_files_name_no_other_architecture,
    test_the_mimo_program_holds_what_the_counts_say,
    test_the_mimo_rehearsal_overlay_is_the_tiny_models_sizes,
    test_the_new_metric_is_a_file_on_a_new_reader,
    test_the_prefill_reader_by_hand,
    test_the_prefill_reader_finds_nothing_where_there_is_nothing_to_read,
    test_with_the_sink_left_out_the_cell_is_not_correct,
)
