"""Device time of the `match_batch` programs per query answered, us."""
import trace_reduce


def read(run):
    if run.trace is None or not run.n_served:
        return None
    seconds, n = trace_reduce.program_s(run.trace, "match_batch")
    return seconds * 1e6 / run.n_served if n else None
