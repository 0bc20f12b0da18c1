"""`tiering` (the Tier-1 build from a selection) and `classify_log` (the
query log's classification) spans per refit, ms."""
import span_reduce


def read(run):
    return span_reduce.per_refit_ms(run, "tiering", "classify_log")
