"""Device: share of the traced window in which no operation ran on the
chip (1 - busy union / window), from the trace."""


def read(r):
    tr = r["trace"]
    if not tr or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / r["window_s"])
