from lib import scoperuns, stats


def read(run, spec):
    """Median length (ms) of the runs of the executables whose program
    wrote one of the roles ``holding`` into its instructions' scope
    paths; None without a device trace or without such a run."""
    found = scoperuns.runs_holding(run, spec["holding"])
    if not found:
        return None
    lengths = [(end - start) / 1e9 for plane in found
               for start, end, _ in plane]
    return stats.percentile(lengths, 50) * 1e3
