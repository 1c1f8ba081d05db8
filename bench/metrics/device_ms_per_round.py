"""Device busy time per FL round in the traced window."""


def read(trace, counts):
    if not counts.get("rounds"):
        return None
    return 1e3 * trace["busy_s"] / counts["rounds"]
