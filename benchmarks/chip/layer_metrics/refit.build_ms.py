"""`build` spans (`shard_tier_postings` per shard, on the host) per
refit, ms."""
import span_reduce


def read(run):
    return span_reduce.per_refit_ms(run, "build")
