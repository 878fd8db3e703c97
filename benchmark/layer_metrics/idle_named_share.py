from lib import spanclock


def read(run, spec):
    idle = spanclock.idle_by_span(run)
    if not idle or not sum(idle.values()):
        return None
    named = sum(secs for name, secs in idle.items()
                if name is not None and name not in spec["bare"])
    return 100.0 * named / sum(idle.values())
