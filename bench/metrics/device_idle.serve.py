"""Share of the traced window in which no operation ran on the device, in
the served cells.  Moves ``prompt_tokens_per_s``."""


def read(run):
    s = run.summary
    if s["window_s"] <= 0 or s["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
