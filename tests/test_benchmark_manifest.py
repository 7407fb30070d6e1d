"""Tier-1's guard of what the benchmark needs from the program.

The benchmark's own tests live in ``benchmark/tests/`` (``python -m pytest
benchmark/tests -q``), which the tier-1 command does not run. This thin file
runs, from there, the checks of the shipped manifest and, for each of the
four configurations that came after GPT-2 (LongCat-Flash, Olmo-Hybrid,
Kimi-K2, Falcon-H1), its counts against hand-worked numbers, its file's cut
against ``published``, its readers and the ``--rehearse`` runs of its cell
(sound, and with a planted fault that has to read not correct), so that a PR
that breaks what a cell reads from the program (a program's name, a counter,
the family seam) fails tier-1."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_manifest",
                               "benchmark.tests.test_longcat_cell",
                               "benchmark.tests.test_olmo_hybrid_cell",
                               "benchmark.tests.test_kimi_k2_cell",
                               "benchmark.tests.test_falcon_h1_cell")

from benchmark.tests.test_longcat_cell import (  # noqa: E402,F401
    config,
    test_counter_readers_by_hand,
    test_counts_by_hand,
    test_readers_find_nothing_on_a_program_without_the_counters,
    test_rehearsal_of_the_cell,
    test_the_file_states_the_cut_and_every_published_width,
    test_the_rehearsal_overlay_is_the_tiny_models_sizes,
)
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
from benchmark.tests.test_manifest import (  # noqa: E402,F401
    test_check_names_a_configuration_that_is_not_whole,
    test_every_cell_resolves_and_every_moves_is_reported,
    test_names_units_and_entry_keys,
    test_no_driver_or_reader_names_an_architecture,
    test_shipped_manifest_is_sound,
)
