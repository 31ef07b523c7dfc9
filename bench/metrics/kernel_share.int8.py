"""The int8 Pallas kernels' device time over the device's busy time."""


def read(ctx):
    """The int8 kernels' share of device busy time, in %."""
    red = ctx["trace"]
    if red["busy_s"] <= 0:
        return None
    return 100.0 * sum(red["kernels"].values()) / red["busy_s"]
