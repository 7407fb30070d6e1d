"""Tier-1's guard of what the cell ``kimi-k2.5.agent-decode`` needs from the
program.

A file of its own (``tests/test_benchmark_manifest.py`` held it until PR 50),
so that the test runner's workers share the rehearsals, whose bodies live in
``benchmark/tests/test_kimi_k2_cell.py``: the configuration's counts against
hand-worked numbers, its file's cut and floors against ``published`` (red
until a ``benchmark`` PR mends the list it holds by equality: ROADMAP M9), the
program's tree against the counts, its readers, the lists the cell joins, the
rehearsal overlay, the ``--rehearse`` runs of the cell (sound, and without the
shared expert, which has to read not correct) and each planted fault's
launcher."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_kimi_k2_cell")

from benchmark.tests.test_kimi_k2_cell import (  # noqa: E402,F401
    kimi_config,
    test_each_launcher_plants_the_fault_it_says,
    test_kimi_counter_readers_by_hand,
    test_kimi_counts_by_hand,
    test_kimi_readers_find_nothing_where_there_is_nothing_to_read,
    test_rehearsal_of_the_kimi_cell,
    test_the_cell_joins_the_lists_the_issue_names,
    test_the_kimi_file_states_the_cut_the_floors_and_every_published_width,
    test_the_kimi_program_holds_what_the_counts_say,
    test_the_kimi_rehearsal_overlay_is_the_tiny_models_sizes,
    test_the_new_reader_file_names_no_architecture,
    test_without_the_shared_expert_the_cell_is_not_correct,
)
