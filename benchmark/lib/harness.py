"""One run of one cell: load its files, drive it, assemble the result."""

from __future__ import annotations

from pathlib import Path

from benchmark.lib import device, serve_driver, train_driver
from benchmark.lib.manifest import Cell, load_cell
from benchmark.lib.run import Run, log

DRIVERS = {"train_steps": train_driver.run, "open_loop": serve_driver.run,
           "closed_loop": serve_driver.run}


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             traced: bool, t_start: float) -> dict:
    """The result object of one run (``--trace 0``: the cell's end-to-end
    metrics; ``--trace 1``: its per-layer metrics, the device's busy time
    and a breakdown).  Checks no platform: ``run.py`` does, before it
    calls this."""
    cell = load_cell(root, workload)
    cache = device.setup_compile_cache()
    compiles = device.CompileCounter()
    log(f"[cell] {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name} ({cell.traffic['driver']}), seed {seed}, "
        f"{seconds:g} s, trace {int(traced)}; compile cache {cache}")
    run = DRIVERS[cell.traffic["driver"]](cell, seed, seconds, traced,
                                          t_start, compiles)
    log(f"[cell] set-up {run.setup_s:.1f} s; compile requests "
        f"{compiles.requests}, served from the cache {compiles.hits}")
    return assemble(cell, run, traced)


def assemble(cell: Cell, run: Run, traced: bool) -> dict:
    facts = device.facts()
    metrics = {}
    if traced:
        for m in cell.per_layer:
            value = cell.readers[m["name"]](run, cell)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(run.end_to_end, setup_s=run.setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"the run of {cell.name} gave no "
                                   f"{m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": facts}
    if traced and run.trace is not None:
        facts["busy_s"] = run.trace.busy_s
        facts["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops,
                            "idle_gaps": run.trace.idle_gaps}
    return out
