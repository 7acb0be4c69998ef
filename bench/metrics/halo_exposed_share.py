"""Per layer (collectives): the device time of the halo exchange's
collective permutes inside the window, summed over the devices, over
(devices that ran anything x the traced window), in percent: the share
of the chips' time that waited on an exchange compute did not hide.
Nothing to read without a device trace or collective ops in it."""


def read(run):
    t = run.trace
    if t is None or t.collective_s <= 0 or t.devices <= 0:
        return None
    return 100.0 * t.collective_s / (t.devices * t.window_s)
