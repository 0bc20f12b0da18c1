"""`upload` spans (each shard's Tier-1 sub-index put on the device and
waited for) per refit, ms."""
import span_reduce


def read(run):
    return span_reduce.per_refit_ms(run, "upload")
