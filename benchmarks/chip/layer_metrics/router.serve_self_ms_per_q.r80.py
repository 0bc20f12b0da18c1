"""`serve` span's own time, wall less its children's (`classify`,
`t1_match`, `t2_match`, `merge`, `compile`), per query served, ms."""
import span_reduce


def read(run):
    return span_reduce.serve_ms_per_query(run, span_reduce.self_ms)
