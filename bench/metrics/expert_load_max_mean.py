"""How uneven the routing is over the held experts: the window's mean over
decode passes of the busiest held expert's pairs, over the mean pairs a
held expert gets in a pass (1 is even; every pass waits on its busiest
expert).  From the program's ``moe_*`` counters, which count the live
lanes of decode passes alone."""


def read(rec):
    c = rec.counters
    pairs, passes = c.get("moe_routed_pairs"), c.get("moe_expert_passes")
    held = getattr(rec.cell.dims, "n", None)
    if not pairs or not passes or not held or "moe_load_max_sum" not in c:
        return None
    return (c["moe_load_max_sum"] / passes) / (pairs / (passes * held))
