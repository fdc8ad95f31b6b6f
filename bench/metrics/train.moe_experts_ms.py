"""Device self time per profiled step under the program's ``moe_experts``
scope (the grouped matmuls over the held experts and their activation),
all passes, in ms; nothing where the program names no such scope."""


def read(outcome, cell, peak):
    t = outcome.trace.get("scopes", {}).get("moe_experts")
    if not t:
        return None
    return 1e3 * sum(t.values()) / cell.traffic["profile_steps"]
