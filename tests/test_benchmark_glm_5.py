"""Tier-1's guard of what the cell ``glm-5.sparse-decode`` needs from the
program.

A file of its own, so that the test runner's workers share the rehearsals,
whose bodies live in ``benchmark/tests/test_glm_5_cell.py``: the
configuration's counts against hand-worked numbers (the card's 743.9B and
40.8B from the published keys among them), its file's cut and floors against
``published``, the program's tree and its two pool arrays against the counts,
the new metrics' readers where there is nothing to read, the lists the cell
joins, the rehearsal overlay and the ``--rehearse`` runs of the cell (sound,
and with the selection ignored, which has to read not correct). The planted
faults' launchers are held to the reference in ``tests/test_glm_dsa.py``."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_glm_5_cell")

from benchmark.tests.test_glm_5_cell import (  # noqa: E402,F401
    glm_config,
    test_glm_counter_readers_by_hand,
    test_glm_counts_by_hand,
    test_glm_readers_find_nothing_where_there_is_nothing_to_read,
    test_rehearsal_of_the_glm_cell,
    test_the_cell_joins_the_lists_the_issue_names,
    test_the_glm_file_states_the_cut_the_floors_and_every_published_width,
    test_the_glm_files_name_no_other_architecture_and_import_no_program,
    test_the_glm_program_holds_what_the_counts_say,
    test_the_glm_rehearsal_overlay_is_the_tiny_models_sizes,
    test_with_the_selection_ignored_the_cell_is_not_correct,
)
