"""Share of the timed window the training loop spent waiting for its next
batch from the program's loader (the ``data_wait`` spans), in percent."""


def read(outcome, cell, peak):
    t0, t1 = outcome.window
    return 100.0 * outcome.spans.total("data_wait", t0, t1) / (t1 - t0)
