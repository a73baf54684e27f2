"""What every driver shares: finding files by name, statistics, the result line."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def use_checkout_cache() -> str:
    """Keep JAX's persistent compile cache inside the checkout, at a fixed
    path, whatever directory the environment names: the program's
    ``launch/chip.setup_compile_cache`` takes ``JAX_COMPILATION_CACHE_DIR``,
    which JAX also reads when it is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    return str(CACHE_DIR)


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def model_dims(conf: dict) -> dict:
    """The sizes the references and operation counts use, from a config file."""
    heads = conf["num_attention_heads"]
    return {
        "n_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "n_heads": heads,
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["assumed"].get("head_dim", conf["hidden_size"] // heads),
        "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "rope_theta": conf["rope_theta"],
        "rms_norm_eps": conf["rms_norm_eps"],
    }


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p`` percent
    of the values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(0, math.ceil(p / 100.0 * len(xs)) - 1)
    return xs[k]


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: dict, compared: dict) -> None:
    """Print each compared number beside its limit (last lines of stderr),
    then the result line (last line of stdout, ``compared`` its last key)."""
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    out = dict(result)
    out["compared"] = compared
    print(json.dumps(out), flush=True)
