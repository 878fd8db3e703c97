def read(run, spec):
    hist = run["counters"].get("hist:gen.slot_occupancy")
    if not hist:
        return None
    n = sum(hist.values())
    return sum(int(k) * v for k, v in hist.items()) / n if n else None
