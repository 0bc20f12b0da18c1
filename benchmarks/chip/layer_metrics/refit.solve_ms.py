"""Prune, warm refit and any cold fallback, per refit, ms."""
import readers


def read(run):
    return readers.refit_mean_ms(run, "solve_s")
