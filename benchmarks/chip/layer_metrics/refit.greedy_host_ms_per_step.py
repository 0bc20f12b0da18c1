"""Host time of a greedy step: `greedy.step` wall less its device wait
(`sync_ms`) and its children (`compile`), per step, ms."""
import span_reduce


def read(run):
    return span_reduce.greedy_step_ms(
        run, lambda s, kids: span_reduce.self_ms(s, kids) - s["sync_ms"])
