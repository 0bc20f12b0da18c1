"""Tier-1 classify (`classify` span) per query answered, ms."""
import readers


def read(run):
    return readers.span_ms_per_query(run, "classify")
