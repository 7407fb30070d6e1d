"""Tier-1's guard of ISSUE 51's metrics (see ``test_benchmark_manifest.py``
for why a thin file): each resolves through the manifest, is worked by hand
on a small ring and counter pair, and reads nothing on a program without the
stream account."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_stream_account")

from benchmark.tests.test_stream_account import (  # noqa: E402,F401
    man,
    test_a_program_without_the_stream_account_reads_nothing,
    test_a_span_that_lacks_an_attr_is_left_out_not_read_as_zero,
    test_each_stream_metric_resolves_through_the_manifest,
    test_stream_metrics_by_hand,
)
