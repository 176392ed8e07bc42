"""The arithmetic of the benchmark's three readers of the host's spans
(``upload.put``, ``host.lock_wait``), from the benchmark's own test file so
that tier-1 holds it too; the traced run of the engine stays with
``benchmark/tests``."""
from benchmark.tests.test_host_readers import (  # noqa: F401
    test_a_registered_span_that_never_fired_reads_zero,
    test_lock_wait_share_stays_within_the_slice,
    test_none_of_the_three_names_an_idle_gap,
    test_put_and_stage_partition_the_upload,
    test_reader_finds_nothing_on_a_program_without_the_span,
    test_reader_on_hand_written_spans,
    test_scan_readers_need_an_upload_in_the_slice)
