"""95th percentile of the time from one ``next()`` of the loop to the
following one, over all steps of the window: the loop's host-side cadence.
Dispatch runs ahead of the device, so this is the host's step, not the
device's; a stall (a save, a report) shows as a long gap."""

import statistics


def read(run: dict):
    gaps = run["window"]["gaps"]
    if len(gaps) < 20:
        return None
    return 1000.0 * statistics.quantiles(gaps, n=20)[-1]
