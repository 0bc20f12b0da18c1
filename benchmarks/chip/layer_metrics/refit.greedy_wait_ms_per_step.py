"""Device wait of a greedy step (`greedy.step` `sync_ms`, the wait for
its stop flag), per step, ms."""
import span_reduce


def read(run):
    return span_reduce.greedy_step_ms(run, lambda s, kids: s["sync_ms"])
