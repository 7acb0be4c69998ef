"""Per layer (kernels): the benchmark's least time for the window's work
(bench/roofline.py) over the device time of the engine's Mosaic kernels,
in percent. Kernel time is summed over every device, so on several chips
this is the aggregate share: one chip's least time over the kernels'
time on all of them. Nothing to read without a device trace or kernel
time."""


def read(run):
    t = run.trace
    if t is None or t.kernel_s <= 0:
        return None
    return 100.0 * run.count["roofline_s"] / t.kernel_s
