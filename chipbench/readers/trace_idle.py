"""1 - (seconds an operation ran on the device) / (traced window), in
per cent, averaged over the chips."""


def read(spec, run):
    t = run.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
