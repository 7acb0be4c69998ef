"""The benchmark's one traffic generator.

A traffic mix is a data file, ``bench/traffic/<mix>.json``, whose
``loop`` key picks one of two drivers and whose other keys are that
driver's parameters. Both make every input on the device from the seed,
warm up every shape the window will use (that is set-up), and then
drive the program for the window:

* ``closed``: one client runs back-to-back ``ops.stencil_run`` solves of
  the configuration's grid, each ending in ``block_until_ready`` as a
  user waiting for a solution does. ``inputs_in_rotation`` distinct
  inputs take turns, so no solve repeats the one before it. A cell's
  ``chips`` reaches it through ``drive``: on more than one chip every
  input is made split along its leading axis over a 1D mesh of the
  first ``chips`` devices, so that no device makes or holds a whole
  grid, and each solve is ``ops.stencil_run(..., n_devices=chips,
  devices=...)``, the deep-halo sharded runner. On one chip the calls
  and the inputs are those of a single device.
* ``open``: requests arrive on a schedule fixed in advance from the
  seed, at ``rate_per_s``, and go through ``StencilService.submit``;
  the loop submits every request that is due, then flushes, and sleeps
  until the next arrival when nothing is due. A request's latency runs
  from its scheduled arrival to the return of the flush that served it.
  Every seed gets the same arrival gaps (the quantiles of an
  exponential distribution) and the same count of each size, in an
  order drawn from the seed, so the seed changes the order of the work
  and not its amount.

Each driver hands back a ``Window``: what the end-to-end and per-layer
readers need, plus what the comparison with the reference needs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
import numpy as np


@dataclasses.dataclass
class Window:
    """What one measured window did."""

    seconds: float                 # window start to the last completion
    attempted: int
    completed: int
    failed: int
    # Completed work as {"grid", "n_steps", "count"}: what the rate and
    # roofline readers count.
    work: list = dataclasses.field(default_factory=list)
    latencies_s: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    plan: dict = dataclasses.field(default_factory=dict)
    diagnostics: dict = dataclasses.field(default_factory=dict)
    # For the comparison with the reference, after the window.
    outputs: list = dataclasses.field(default_factory=list)

    @property
    def cell_updates(self) -> int:
        """Grid cells x steps over the completed work."""
        return sum(w["count"] * math.prod(w["grid"]) * w["n_steps"]
                   for w in self.work)


class NoHooks:
    """Hooks for a window that is neither timed nor traced."""

    def begin(self):
        pass

    def end(self):
        pass


def prng_key(seed: int):
    """A key from any non-negative seed, also one past 32 bits."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def program_spec(config: dict):
    """The program's ``StencilSpec`` for a configuration's stencil."""
    from repro.core.stencil import AuxOperand, StencilSpec
    st = config["stencil"]
    src = st.get("source")
    return StencilSpec(
        dims=st["dims"], radius=st["radius"], center=st["center"],
        axis_weights=tuple(tuple(float(w) for w in row)
                           for row in st["axis_weights"]),
        boundary=st["boundary"],
        aux=(AuxOperand(src["operand"], role="source"),) if src else (),
        name=config["name"])


def make_problems(config: dict, grid, count: int, key, sharding=None):
    """``count`` problems of ``grid`` in one jitted call on the device:
    a list of dicts holding the grid ``x`` and every operand the
    configuration's inputs name, each uniform in its stated range.
    ``sharding`` places every array as it is made, each device making
    its own part."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(config["dtype"])
    names = sorted(config["inputs"])
    grid = tuple(grid)

    def make(k):
        keys = jax.random.split(k, count * len(names))
        out = []
        for i in range(count):
            p = {}
            for j, nm in enumerate(names):
                lo, hi = config["inputs"][nm]
                u = jax.random.uniform(keys[i * len(names) + j], grid,
                                       jnp.float32)
                p[nm] = (lo + (hi - lo) * u).astype(dtype)
            out.append(p)
        return out

    if sharding is None:
        return jax.jit(make)(key)
    return jax.jit(make, out_shardings=sharding)(key)


def _span(annotate: bool, name: str):
    if not annotate:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _plan_fields(tuned) -> dict:
    return {"bx": tuned.bx, "bt": tuned.bt, "variant": tuned.variant,
            "source": tuned.source}


def closed(config: dict, traffic: dict, seed: int, seconds: float,
           annotate: bool, hooks, chips: int = 1) -> Window:
    """Back-to-back solves by one client (module docstring)."""
    import jax
    from bench import reference
    from repro.kernels import autotune, ops
    spec = program_spec(config)
    grid = tuple(config["grid"])
    n_steps = int(config["n_steps"])
    k = int(traffic.get("inputs_in_rotation", 1))
    tune_kw, run_kw, sharding = {}, {}, None
    if chips > 1:
        devices = jax.devices()[:chips]
        sharding = jax.sharding.NamedSharding(
            jax.sharding.Mesh(np.array(devices), ("rows",)),
            jax.sharding.PartitionSpec("rows"))
        tune_kw = {"n_devices": chips}
        run_kw = {"n_devices": chips, "devices": devices}
    probs = make_problems(config, grid, k, prng_key(seed), sharding)
    src_name = (config["stencil"].get("source") or {}).get("operand")
    auxes = [({src_name: reference.source_grid(config["stencil"], p)}
              if src_name else None) for p in probs]

    def solve(i):
        return ops.stencil_run(probs[i % k]["x"], spec, n_steps,
                               backend="auto", aux=auxes[i % k], **run_kw)

    for i in range(k):                       # warm-up: compiles once
        jax.block_until_ready(solve(i))
    tuned = autotune.plan(grid, spec, dtype=config["dtype"],
                          backend="auto", n_steps=n_steps, **tune_kw)
    d0 = ops.dispatch_count()
    n = 0
    y = None
    hooks.begin()
    t0 = time.perf_counter()
    with _span(annotate, "window"):
        while True:
            if chips > 1:
                # The sharded runner holds a padded copy of its input and
                # three times its output in temporaries a chip: on a grid
                # sized to fill the chips the last solution cannot live
                # through the next solve.
                y = None
            with _span(annotate, "stencil_run"):
                y = solve(n)
            with _span(annotate, "block_until_ready"):
                jax.block_until_ready(y)
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
    t1 = time.perf_counter()
    hooks.end()
    last = (n - 1) % k
    return Window(
        seconds=t1 - t0, attempted=n, completed=n, failed=0,
        work=[{"grid": grid, "n_steps": n_steps, "count": n}],
        counters={"dispatches": ops.dispatch_count() - d0, "solves": n},
        plan=dict(_plan_fields(tuned), **tune_kw),
        outputs=[{"got": y, "problem": probs[last], "grid": grid,
                  "n_steps": n_steps}])


def schedule(traffic: dict, seed: int, seconds: float):
    """(arrival seconds, size index, pool index) for every request of
    the window: fixed gaps and size counts, in an order from the seed."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    arrivals = np.cumsum(gaps)
    mix = np.asarray(traffic.get("mix", [1] * len(traffic["sizes"])),
                     float)
    counts = np.floor(n * mix / mix.sum()).astype(int)
    counts[: n - counts.sum()] += 1
    sizes = rng.permutation(np.repeat(np.arange(len(mix)), counts))
    pool = int(traffic["pool"])
    slots = rng.integers(0, pool, n)
    return arrivals, sizes, slots


def open_(config: dict, traffic: dict, seed: int, seconds: float,
          annotate: bool, hooks, chips: int = 1) -> Window:
    """Open-loop arrivals through ``StencilService`` (module docstring),
    on one chip."""
    if chips != 1:
        raise ValueError(f"the open loop runs on one chip, not {chips}")
    import jax
    from bench import reference
    from repro.kernels import autotune
    from repro.serving import StencilRequest, StencilService
    spec = program_spec(config)
    n_steps = int(traffic["n_steps"])
    grids = [tuple(g) for g in traffic["sizes"]]
    pool = int(traffic["pool"])
    max_batch = int(traffic["max_batch"])
    src_name = (config["stencil"].get("source") or {}).get("operand")
    key = prng_key(seed)
    # Clients send host arrays; the pool is made on the device and
    # fetched once.
    problems, payloads = [], []
    for gi, grid in enumerate(grids):
        probs = make_problems(config, grid, pool, jax.random.fold_in(key, gi))
        problems.append(probs)
        payloads.append([
            (np.asarray(p["x"]),
             {src_name: np.asarray(reference.source_grid(
                 config["stencil"], p))} if src_name else None)
            for p in probs])

    def request(uid, gi, slot):
        x, aux = payloads[gi][slot]
        return StencilRequest(uid=uid, x=x, spec=spec, n_steps=n_steps,
                              aux=aux)

    svc = StencilService(max_batch=max_batch)
    buckets = [1 << i for i in range(int(math.log2(max_batch)) + 1)]
    for gi in range(len(grids)):             # warm-up: every bucket
        for b in buckets:
            svc.run([request(-1, gi, j % pool) for j in range(b)])
    plan = {f"{b}x{'x'.join(map(str, g))}": _plan_fields(autotune.plan(
        (b,) + g, spec, dtype=config["dtype"], backend="auto",
        n_steps=n_steps)) for g in grids for b in buckets}

    arrivals, sizes, slots = schedule(traffic, seed, seconds)
    n = len(arrivals)
    rng = np.random.default_rng([seed, 1])
    n_sample = min(int(traffic["sample"]), n)
    sample = set(rng.choice(n, n_sample, replace=False).tolist())
    largest = np.flatnonzero(sizes == max(
        range(len(grids)), key=lambda g: math.prod(grids[g])))
    if len(largest) and not any(u in sample for u in largest):
        sample.add(int(largest[0]))
    m0 = dict(svc.metrics)
    latencies = np.full(n, np.nan)
    late = np.zeros(n)
    flush_s = []
    kept = {}
    errors = 0
    i = 0
    hooks.begin()
    t0 = time.perf_counter()
    with _span(annotate, "window"):
        while i < n:
            now = time.perf_counter() - t0
            if arrivals[i] > now:
                with _span(annotate, "wait_arrival"):
                    time.sleep(arrivals[i] - now)
                continue
            with _span(annotate, "submit"):
                while i < n and arrivals[i] <= now:
                    late[i] = now - arrivals[i]
                    svc.submit(request(i, int(sizes[i]), int(slots[i])))
                    i += 1
            with _span(annotate, "flush"):
                done = svc.flush()
            t_done = time.perf_counter() - t0
            flush_s.append(t_done - now)
            for c in done:
                if c.error is not None:
                    errors += 1
                    continue
                latencies[c.uid] = t_done - arrivals[c.uid]
                if c.uid in sample:
                    kept[c.uid] = c.result
    t1 = time.perf_counter()
    hooks.end()
    ok = ~np.isnan(latencies)
    completed = int(ok.sum())
    work = [{"grid": grid, "n_steps": n_steps,
             "count": int((ok & (sizes == gi)).sum())}
            for gi, grid in enumerate(grids)]
    counters = {k: svc.metrics[k] - m0[k]
                for k in ("dispatches", "problems", "pad_rows",
                          "bucket_failures", "failed")}
    outputs = [{"got": kept.get(u), "problem": problems[sizes[u]][slots[u]],
                "grid": grids[sizes[u]], "n_steps": n_steps, "uid": u}
               for u in sorted(sample)]
    return Window(
        seconds=t1 - t0, attempted=n, completed=completed,
        failed=n - completed, work=work,
        # In arrival order; a request that failed counts with its wait
        # to the end of the window.
        latencies_s=np.where(ok, latencies,
                             (t1 - t0) - arrivals).tolist(),
        counters=counters, plan=plan,
        diagnostics={"late_p95_ms": 1e3 * float(np.percentile(late, 95)),
                     "late_max_ms": 1e3 * float(late.max()),
                     "flush_max_ms": 1e3 * max(flush_s, default=0.0),
                     "flush_p99_ms": 1e3 * float(np.percentile(flush_s, 99))
                     if flush_s else 0.0,
                     "errors": errors,
                     "offered_per_s": float(traffic["rate_per_s"])},
        outputs=outputs)


DRIVERS = {"closed": closed, "open": open_}


def drive(config: dict, traffic: dict, seed: int, seconds: float,
          annotate: bool, hooks, chips: int = 1) -> Window:
    """Run the driver the mix's ``loop`` names on the first ``chips``
    devices. ``hooks.begin()`` is called when set-up ends and the window
    starts, ``hooks.end()`` when the window closes."""
    loop = traffic["loop"]
    if loop not in DRIVERS:
        raise ValueError(f"traffic loop {loop!r} is not one of "
                         f"{sorted(DRIVERS)}")
    return DRIVERS[loop](config, traffic, seed, seconds, annotate, hooks,
                         chips)
