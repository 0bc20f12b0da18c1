"""Time the serving thread stood still inside `serve`: wall less the
thread's CPU time less its children's device waits and copies, per query
served, ms."""
import span_reduce


def read(run):
    return span_reduce.serve_ms_per_query(run, span_reduce.stall_ms)
