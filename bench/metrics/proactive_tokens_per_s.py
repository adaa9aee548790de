"""Proactive output tokens committed inside the window over its seconds
(host clock): the capacity the reactive load leaves to the agents."""
from bench.window import walls_in


def read(rec):
    n = len(walls_in(rec, rec["ws"], rec["we"], "proactive"))
    return n / (rec["we"] - rec["ws"])
