"""``peak_bytes_in_use`` over ``bytes_limit`` of the fullest chip, read
after the window, %."""


def read(rec):
    m = rec["memory"]
    if not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes"] / m["bytes_limit"]
