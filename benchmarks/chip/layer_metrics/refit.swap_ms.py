"""Tier-1 build, swap and the first batch at the new generation, per
refit, ms."""
import readers


def read(run):
    return readers.refit_mean_ms(run, "swap_s")
