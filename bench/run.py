#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names its configuration
(``bench/configs/``) and traffic mix (``bench/traffic/``), and the mix
names the driver that runs it (``bench/drivers/``).  Per-layer metrics are
the readers ``bench/metrics/<metric>.py`` that ``BENCHMARK.json`` lists for
the cell.  The last line of stdout is the result; the numbers compared for
``correct`` are the last lines of stderr.  With no TPU, or fewer chips than
the cell asks for, the run exits non-zero and prints no result.

``--sweep R1,R2,...`` runs a serving cell's traffic for one window per
rate after one set-up and prints no result (how a cell's rate is found).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The ``section`` metrics of ``BENCHMARK.json`` this cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None)
    args = ap.parse_args(argv)
    common.use_checkout_cache()

    bench = common.benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if entry is None:
        common.log(f"run: no workload {args.workload!r} in BENCHMARK.json")
        return 2
    cell = common.load_json("workloads", args.workload)
    conf = common.load_json("configs", cell["config"])
    mix = common.load_json("traffic", cell["traffic"])
    driver = common.load_module("drivers", mix["driver"])

    from repro.launch import chip

    cache = chip.setup_compile_cache()
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)  # jax may be imported already
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devs = chip.require_tpu(entry["chips"])
    except RuntimeError as e:
        common.log(f"run: {e}")
        return 2
    dev = devs[0]
    peaks = common.peaks_for(dev.device_kind)
    common.log(f"run: {args.workload} seed {args.seed} on {len(devs)} x {dev.device_kind}; "
               f"compile cache {cache}")

    from repro.configs import get_arch

    ctx = {"args": args, "cell": cell, "config": conf, "traffic": mix, "device": dev,
           "devices": devs, "peaks": peaks, "arch": get_arch(conf["arch"]),
           "t_start": T_START}
    if args.sweep:
        driver.sweep(ctx, [float(r) for r in args.sweep.split(",")])
        return 0
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    ctx["trace_dir"] = trace_dir
    try:
        result, e2e, compared = driver.run(ctx)
        device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
                  "memory_peak_bytes": ctx["memory_peak_bytes"]}
        metrics = {}
        if args.trace:
            from bench import trace as T

            tr = T.load(trace_dir)
            ctx["trace"] = tr
            device["busy_s"] = T.busy_s(tr)
            device["window_s"] = T.window_s(tr)
            for m in cell_metrics(bench, args.workload, "per_layer"):
                value = common.load_module("metrics", m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            result["breakdown"] = T.breakdown(tr)
        else:
            for m in cell_metrics(bench, args.workload, "end_to_end"):
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = device
    common.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
