"""Share of the profiled window in which a collective ran on a chip and no
other operation did (``trace_reduce``'s ``collective_exposed_s``, averaged
over the chips), in percent."""


def read(outcome, cell, peak):
    if not outcome.trace:
        return None
    return 100.0 * outcome.trace["collective_exposed_s"] / \
        outcome.trace["window_s"]
