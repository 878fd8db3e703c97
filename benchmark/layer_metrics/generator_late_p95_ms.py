from lib import stats


def read(run, spec):
    late = run["facts"].get("lateness_s")
    if not late:
        return None
    return stats.percentile(late, 95) * 1e3
