"""Device wait (`sync_ms`) of the `t1_match` and `t2_match` spans per
query served, ms."""
import span_reduce


def read(run):
    return span_reduce.serve_ms_per_query(run,
                                          span_reduce.match_ms("sync_ms"))
