"""Share of the profiled window in which no operation ran on the device,
averaged over the chips, in percent (``trace_reduce``)."""


def read(outcome, cell, peak):
    if not outcome.trace:
        return None
    return 100.0 * outcome.trace["idle_share"]
