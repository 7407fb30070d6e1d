"""Tier-1's guard of what the cell ``olmo-hybrid-7b.hybrid-decode`` needs from
the program.

A file of its own (``tests/test_benchmark_manifest.py`` held it until PR 50),
so that the test runner's workers share the rehearsals, whose bodies live in
``benchmark/tests/test_olmo_hybrid_cell.py``: the configuration's counts
against hand-worked numbers, its file's cut against ``published``, the
program's tree and state against the counts, its readers, the rehearsal
overlay, the ``--rehearse`` runs of the cell (sound, and with a state zeroed
every 16th step, which has to read not correct) and the bfloat16 launcher."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_olmo_hybrid_cell")

from benchmark.tests.test_olmo_hybrid_cell import (  # noqa: E402,F401
    olmo_config,
    test_a_state_zeroed_every_16th_step_is_not_correct,
    test_no_new_reader_names_an_architecture,
    test_olmo_counter_readers_by_hand,
    test_olmo_counts_by_hand,
    test_olmo_readers_find_nothing_on_a_program_without_the_counters,
    test_rehearsal_of_the_olmo_cell,
    test_the_bfloat16_launcher_rounds_the_state_it_says,
    test_the_olmo_file_states_the_cut_and_every_published_width,
    test_the_olmo_rehearsal_overlay_is_the_tiny_models_sizes,
    test_the_program_holds_what_the_counts_say,
)
