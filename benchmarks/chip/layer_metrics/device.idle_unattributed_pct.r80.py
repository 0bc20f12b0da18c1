"""Share of the traced window the device sat idle while no program span
was open, %."""
import span_reduce


def read(run):
    return span_reduce.idle_pct(run, span_reduce.UNATTRIBUTED)
