"""`prune` span (`stream.prune_state`) per refit, ms."""
import span_reduce


def read(run):
    return span_reduce.per_refit_ms(run, "prune")
