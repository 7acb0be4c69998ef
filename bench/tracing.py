"""Reduce a profiler trace of the window to the benchmark's numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
plain data: for each device plane the events of its op line, and the
host spans the benchmark itself opened around its calls into each layer
(``SPANS``). ``reduce`` works on that plain data, so the tests run it on
a small recorded trace committed beside them.

* The window is the host span ``window``; without it, the whole trace.
* Busy time of a device is the union of its op intervals inside the
  window; ``busy_s`` is its mean over the devices that ran anything.
* Kernel time is the summed device time of the Mosaic kernels (custom
  calls to ``tpu_custom_call``: the engine's Pallas kernels, which the
  program gives no stable names yet), over all devices.
* Collective time (``collective_s``) is the device time of the ops whose
  opcode is a collective permute (``collective-permute-start``,
  ``-done`` or the synchronous one; not an op that only takes a
  permute's result as an operand), the union of their intervals on each
  device, summed over the devices. On a TPU core they hold the op line,
  so it is the exchange that compute did not hide. ``devices`` counts
  the devices that ran anything in the window.
* ``device_ops``: the ten device ops with the most time, summed over the
  devices, by instruction name less its number, shape and opcode
  (``short_name``): the same kernel or fusion of every sweep of an
  unrolled program, and of every device, counts as one op.
* ``idle_gaps``: the window's idle device time, each gap charged to the
  innermost benchmark span open on the host at the gap's midpoint, the
  ten largest totals by span name (``none`` where no span was open).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SPANS = ("window", "stencil_run", "block_until_ready", "submit", "flush",
         "wait_arrival")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Summary:
    kernel_s: float
    busy_s: float
    window_s: float
    device_ops: list
    idle_gaps: list
    collective_s: float = 0.0
    devices: int = 0


def load(trace_dir: str) -> dict:
    """Plain data from the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_xplane(max(paths, key=os.path.getmtime))


def load_xplane(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns]]},
    "spans": [[name, start_ns, dur_ns]]}`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "spans": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend([e.name, e.start_ns, e.duration_ns]
                               for e in line.events)
            out["devices"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"].extend([e.name, e.start_ns, e.duration_ns]
                                    for e in line.events
                                    if e.name in SPANS)
    return out


def is_kernel(name: str) -> bool:
    """A Mosaic kernel: the engine's Pallas kernels."""
    return "tpu_custom_call" in name


def _parts(name: str):
    """``(instruction name less its number, result shape, opcode)`` of an
    op's HLO text ``%name.N = shape opcode(operands), ...``; None for an
    event name that is not HLO text."""
    lhs, sep, rhs = name.partition(" = ")
    m = re.search(r"\s([a-z][\w.\-]*)\(", rhs) if sep else None
    if m is None:
        return None
    shape = re.sub(r"\{[^}]*\}", "", rhs[:m.start()]).replace(" ", "")
    base = re.sub(r"\.\d+$", "", lhs.strip().lstrip("%"))
    return base, shape, m.group(1)


def is_collective(name: str) -> bool:
    """A halo exchange: an op whose opcode is an asynchronous or
    synchronous collective permute. A fusion that reads a permute's
    result (``fusion(%collective-permute-done.3, ...)``) is compute."""
    parts = _parts(name)
    op = parts[2] if parts else re.sub(r"\.\d+$", "", name.lstrip("%"))
    return op.startswith("collective-permute")


def short_name(name: str) -> str:
    """An op's HLO text cut to its instruction name less its number,
    result shape and opcode: ``stencil3d_stream f32[512,512,512]
    custom-call`` for ``%stencil3d_stream.193 = f32[512,512,512]{...}
    custom-call(...)``."""
    parts = _parts(name)
    return " ".join(parts) if parts else name


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _charge(spans, gaps) -> dict:
    """Seconds of idle per span name: each gap ``(a, b)`` goes to the
    shortest benchmark span (other than the window) open at its
    midpoint, or to ``none``. One sweep over both, sorted by time."""
    order = sorted((s, s + d, d, n) for n, s, d in spans if n != "window")
    out: dict = {}
    active = []
    j = 0
    for a, b in sorted(gaps):
        t = (a + b) / 2
        while j < len(order) and order[j][0] <= t:
            active.append(order[j])
            j += 1
        active = [sp for sp in active if sp[1] >= t]
        who = min(active, key=lambda sp: sp[2])[3] if active else "none"
        out[who] = out.get(who, 0.0) + (b - a)
    return out


def _top(totals: dict, k: int = 10) -> list:
    return [[n, s] for n, s in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:k]]


def reduce(trace: dict) -> Summary:
    spans = trace["spans"]
    windows = [(s, s + d) for n, s, d in spans if n == "window"]
    all_ev = [e for evs in trace["devices"].values() for e in evs]
    if windows:
        w0, w1 = min(w[0] for w in windows), max(w[1] for w in windows)
    elif all_ev:
        w0 = min(e[1] for e in all_ev)
        w1 = max(e[1] + e[2] for e in all_ev)
    else:
        return Summary(0.0, 0.0, 0.0, [], [])
    kernel_ns = 0.0
    collective_ns = 0.0
    op_ns: dict = {}
    gaps = []
    busy = []
    for evs in trace["devices"].values():
        inside = []
        collective = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            inside.append((a, b))
            short = short_name(name)
            op_ns[short] = op_ns.get(short, 0.0) + (b - a)
            if is_kernel(name):
                kernel_ns += b - a
            if is_collective(name):
                collective.append((a, b))
        collective_ns += sum(b - a for a, b in _union(collective))
        merged = _union(inside)
        if not merged:
            continue
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n = max(len(busy), 1)
    return Summary(
        kernel_s=kernel_ns * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        window_s=(w1 - w0) * 1e-9,
        device_ops=_top({k: v * 1e-9 for k, v in op_ns.items()}),
        idle_gaps=_top({k: v / n * 1e-9
                        for k, v in _charge(spans, gaps).items()}),
        collective_s=collective_ns * 1e-9,
        devices=len(busy))
