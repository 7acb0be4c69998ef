"""Per layer (device): the benchmark's least time for the window's work
over the whole traced window, in percent: the share of the chips' peak
the window used, which bounds every kernel's share whatever runs the
work. The least time on a cell's ``chips`` chips is one chip's least
time (bench/roofline.py) over ``chips``. Nothing to read without device
time or completed work."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or run.window.completed == 0:
        return None
    return 100.0 * run.count["roofline_s"] / run.cell.chips / t.window_s
