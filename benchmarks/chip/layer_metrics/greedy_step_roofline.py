"""`_greedy_step` share of its HBM roofline, %.

Least bytes per step: `clause_doc_bits` and `clause_query_bits` read once."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    seconds, n = trace_reduce.program_s(run.trace, "_greedy_step")
    if not n or seconds <= 0:
        return None
    return 100.0 * n * run.greedy_step_bytes / run.peaks["hbm_bytes_per_s"] \
        / seconds
