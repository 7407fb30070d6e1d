"""Tier-1's guard of ISSUE 35's metrics (see ``test_benchmark_manifest.py``
for why a thin file): each resolves, is worked by hand, reads nothing on a
program without the counters, and a traced rehearsal of a closed and of a
session cell prints every one listed for it. A file of its own, so that the
two rehearsals run beside the other cells' and not after them."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_step_accounting")

from benchmark.tests.test_step_accounting import (  # noqa: E402,F401
    man,
    test_a_program_without_the_counters_or_the_ring_reads_nothing,
    test_each_new_metric_resolves_through_the_manifest,
    test_new_metrics_by_hand,
    test_the_three_time_counters_account_for_the_window,
    test_traced_rehearsal_prints_every_new_metric_of_the_cell,
)
