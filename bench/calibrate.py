#!/usr/bin/env python3
"""Readings for a cell's limits: sound runs and the lower-precision control.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

Runs the cell once per seed in this one process, as ``bench/run.py``
would, and prints each number compared; then the control on the same
seed, at the cell's own size:

- serving cells: the float32 reference computed with every matmul input
  rounded to float8 (e4m3), read over the same prompts and served tokens:
  the gap of the token the control puts first at each position
  (``serve.control``);
- planner cells: the reference put in the program's place on weights
  rounded to bfloat16, for as many layers as the sound run planned, held
  against the float32 reference like the program (``plan.control``).

The control is judged by the cell's own rule and limits, as a run is, and
has to come out not correct.  A limit lies above the largest sound
reading and below the smallest control reading (PERF.md gives both and
the limit).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    common.use_checkout_cache()

    cell = common.load_json("workloads", args.workload)
    conf = common.load_json("configs", cell["config"])
    mix = common.load_json("traffic", cell["traffic"])
    driver = common.load_module("drivers", mix["driver"])

    from repro.launch import chip

    cache = chip.setup_compile_cache()
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_compilation_cache_dir", cache)  # jax may be imported already
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        dev = chip.require_tpu(cell["chips"])[0]
    except RuntimeError as e:
        common.log(f"calibrate: {e}")
        return 2
    from repro.configs import get_arch

    for seed in [int(s) for s in args.seeds.split(",")]:
        run_args = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        ctx = {"args": run_args, "cell": cell, "config": conf, "traffic": mix, "device": dev,
               "arch": get_arch(conf["arch"]), "t_start": time.perf_counter(),
               "peaks": common.peaks_for(dev.device_kind)}
        result, e2e, compared = driver.run(ctx)
        t = time.perf_counter()
        ok, ctl = driver.control(ctx, getattr(jnp, driver.CONTROL))
        print(f"calibrate {args.workload} seed {seed}: correct {result['correct']} "
              f"compared {({k: v['value'] for k, v in compared.items()})} e2e {e2e}",
              flush=True)
        print(f"calibrate {args.workload} seed {seed} control: correct {ok} compared "
              f"{({k: (v['value'], v['limit']) for k, v in ctl.items()})} "
              f"({time.perf_counter() - t:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
