"""Per layer (service): padding rows over all batch rows the service
dispatched in the window (StencilService.metrics), in percent."""


def read(run):
    c = run.window.counters
    rows = c.get("problems", 0) + c.get("pad_rows", 0)
    return 100.0 * c["pad_rows"] / rows if rows else None
