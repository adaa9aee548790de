"""On-chip benchmark of the Agent.xpu served path (see ``run.py``)."""
