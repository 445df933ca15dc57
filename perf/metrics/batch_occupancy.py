def read(run, params):
    c = run["counters"]
    if not c.get("decode_steps"):
        return None
    return c["occupancy_sum"] / (c["decode_steps"] * c["slots"])
