"""Wall time of the dispatch loop's thread in the window inside no
program span, in per cent (apply covers a whole batch step, poll_wait the
wait for the next: what is left is the loop's own bookkeeping and its
idle sleeps). Source: the program's spans."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    thread = w.worker_thread() if w else None
    if thread is None or not w.named("poll_wait"):
        return None
    owned = program_spans.covered_s(
        [(s[1], s[2]) for s in w.spans if s[3] == thread], w.t_a, w.t_b)
    return 100.0 * (1.0 - owned / (w.t_b - w.t_a))
