"""Share of the traced window the device sat idle while `merge` was the
innermost program span, %."""
import span_reduce


def read(run):
    return span_reduce.idle_pct(run, "merge")
