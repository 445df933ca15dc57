def read(run, params):
    c = run["counters"]
    if not c.get("experts_active_total"):
        return None
    return c["expert_assignments_held_total"] / c["experts_active_total"]
