"""Share of the traced window with no operation on the device, %."""
import readers


def read(run):
    return readers.idle_pct(run)
