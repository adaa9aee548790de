"""Share of the traced interval in which an operation ran on device 1,
the stage-decoupled backend's prefill device, %: whether prompts really
prefilled there.  No value where the trace holds a single device."""


def read(rec):
    red = rec["trace"]
    if not red or len(red["devices"]) < 2 or red["window_s"] <= 0:
        return None
    return 100.0 * red["devices"][1]["busy_s"] / red["window_s"]
