"""Share of the traced span in which no operation ran on the device:
1 - union of the device's op intervals over the span from the first op's
start to the last op's end (device trace)."""


def read(run: dict):
    traced = run.get("traced")
    if not traced or not traced.get("window_s"):
        return None
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
