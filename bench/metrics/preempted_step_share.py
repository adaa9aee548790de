"""Announced decode steps cancelled before launch at a segment boundary
(``aborted_steps``) over all announced steps (``fused_steps`` +
``aborted_steps``), in the window, %."""
from bench.window import delta


def read(rec):
    aborted, fused = delta(rec, "aborted_steps"), delta(rec, "fused_steps")
    if aborted is None or fused is None or aborted + fused == 0:
        return None
    return 100.0 * aborted / (aborted + fused)
