"""Decode tokens committed in the window (every token but a flow's first,
which prefill makes) per decode program the backend launched
(``decode_device_calls``, which counts fused segments' programs and single
steps alike): how many rows and steps each launch carries."""
from bench.window import delta, walls_in


def read(rec):
    calls = delta(rec, "decode_device_calls")
    if not calls:
        return None
    return len(walls_in(rec, rec["ws"], rec["we"], first=False)) / calls
