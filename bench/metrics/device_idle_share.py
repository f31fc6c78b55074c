"""Device: share of the traced window, in percent, in which no operation
ran on the chip (1 - the union of the device operations' intervals over
the window).  Source: the profiler's device trace."""


def read(b):
    if b.get("busy_s") is None or not b.get("window_s"):
        return None
    return (1.0 - b["busy_s"] / b["window_s"]) * 100.0
