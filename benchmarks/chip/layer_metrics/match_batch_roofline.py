"""`match_batch` share of its HBM roofline, %.

Least bytes per query: one W-word postings row per query term read and
one W-word result row written (`Run.match_bytes`)."""
import readers


def read(run):
    return readers.program_roofline_pct(run, "match_batch", run.match_bytes)
