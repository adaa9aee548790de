"""Share of the traced interval in which no operation ran on device 0
(which holds the KV pool and runs decode), %."""


def read(rec):
    red = rec["trace"]
    if not red or not red["devices"] or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["devices"][0]["busy_s"] / red["window_s"])
