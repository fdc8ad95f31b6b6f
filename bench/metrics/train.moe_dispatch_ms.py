"""Device self time per profiled step under the program's ``moe_dispatch``
scope (router, top-k, sort, gathers into expert order and the weighted
scatter-add back), all passes, in ms; nothing where the program names no
such scope."""


def read(outcome, cell, peak):
    t = outcome.trace.get("scopes", {}).get("moe_dispatch")
    if not t:
        return None
    return 1e3 * sum(t.values()) / cell.traffic["profile_steps"]
