"""Share of the KV positions that the window's decode steps moved (rows x
KV extent each, ``decode_kv_positions``) that live rows held
(``decode_kv_positions_live``), %: the rest is padding of the pow-2 row
and KV extents."""
from bench.window import delta


def read(rec):
    moved = delta(rec, "decode_kv_positions")
    live = delta(rec, "decode_kv_positions_live")
    if not moved or live is None:
        return None
    return 100.0 * live / moved
