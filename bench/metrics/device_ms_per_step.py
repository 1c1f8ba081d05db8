"""Device busy time per train step in the traced window."""


def read(trace, counts):
    if not counts.get("steps"):
        return None
    return 1e3 * trace["busy_s"] / counts["steps"]
