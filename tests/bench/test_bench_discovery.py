"""The harness finds every cell, configuration, traffic mix and metric by
name, so a later cell is added as files only."""
import json
import os
import shutil
import subprocess
import sys

from bench import common

ROOT = common.ROOT



def test_every_benchmark_entry_has_its_files():
    bench = common.benchmark()
    assert bench["command"] == ["python3", "bench/run.py"]
    for w in bench["workloads"]:
        cell = common.load_json("workloads", w["name"])
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"] == 1
        conf = common.load_json("configs", w["config"])
        mix = common.load_json("traffic", w["traffic"])
        common.load_module("drivers", mix["driver"])
        common.load_module("traffic", mix["generator"])
        assert conf["source"] and isinstance(conf["reduced"], list)
    for m in bench["per_layer"]:
        assert callable(common.load_module("metrics", m["name"]).read)
    names = [c["name"] for c in bench["configs"]]
    assert sorted(names) == sorted({w["config"] for w in bench["workloads"]})
    for c in bench["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"


def test_a_cell_added_as_files_is_found(tmp_path, monkeypatch):
    shutil.copytree(common.BENCH, tmp_path / "bench")
    b = tmp_path / "bench"
    (b / "traffic" / "burst.json").write_text(json.dumps({
        "generator": "open_loop", "driver": "serve",
        "prompt": {"dist": "uniform", "min": 10, "max": 20},
        "output": {"dist": "uniform", "min": 4, "max": 8}}))
    (b / "workloads" / "burst-internlm2.json").write_text(json.dumps({
        "config": "internlm2-1.8b", "traffic": "burst", "chips": 1, "rate_per_s": 5.0,
        "why": "added as files"}))
    (b / "metrics" / "prompt_tokens.serve.py").write_text(
        "def read(ctx):\n    return float(sum(r['prompt'].size for r in ctx['window']['records']))\n")
    monkeypatch.setattr(common, "BENCH", b)
    cell = common.load_json("workloads", "burst-internlm2")
    mix = common.load_json("traffic", cell["traffic"])
    reqs = common.load_module("traffic", mix["generator"]).generate(
        mix, rate=cell["rate_per_s"], seconds=4, seed=9, vocab=100)
    assert len(reqs) == 20 and all(10 <= r["prompt"].size <= 20 for r in reqs)
    reader = common.load_module("metrics", "prompt_tokens.serve")
    assert reader.read({"window": {"records": reqs}}) == sum(r["prompt"].size for r in reqs)

    sys.path.insert(0, str(ROOT / "bench"))
    try:
        import run as run_mod
    finally:
        sys.path.pop(0)
    bench = {"end_to_end": [{"name": "ttft_p90_ms", "workloads": ["burst-internlm2"]},
                            {"name": "setup_s"}],
             "per_layer": [{"name": "prompt_tokens.serve", "moves": "ttft_p90_ms"},
                           {"name": "other", "moves": "plan_weights_per_s"}]}
    assert [m["name"] for m in run_mod.cell_metrics(bench, "burst-internlm2", "per_layer")] == [
        "prompt_tokens.serve"]
    assert [m["name"] for m in run_mod.cell_metrics(bench, "burst-internlm2", "end_to_end")] == [
        "ttft_p90_ms", "setup_s"]


_ROOFLINE = """
def prefill_ops(dims, prompt):
    return 1e9 * dims["n_layers"] * prompt


def decode_ops(dims, context):
    return 1e9 * dims["n_layers"]
"""


def test_a_configuration_of_another_architecture_is_added_as_files(tmp_path, monkeypatch):
    """An MLA + MoE configuration whose reference and operation count are
    new files: the serving driver reaches its tree, and ``mfu.serve`` its
    count, through the config file's ``"reference"`` alone."""
    import functools

    import jax
    import numpy as np

    from bench import weights as W
    from repro.configs import get_arch
    from repro.models import api

    shutil.copytree(common.BENCH, tmp_path / "bench")
    b = tmp_path / "bench"
    shutil.copy(ROOT / "tests" / "bench" / "mla_moe_reference.py", b / "reference" / "mla_moe.py")
    (b / "roofline" / "mla_moe.py").write_text(_ROOFLINE)
    arch = get_arch("deepseek-v2-236b", reduced=True)
    m, e = arch.mla, arch.moe
    (b / "configs" / "deepseek-v2-reduced.json").write_text(json.dumps({
        "arch": arch.name, "source": "arXiv:2405.04434", "reduced": ["num_hidden_layers"],
        "num_hidden_layers": arch.n_layers, "hidden_size": arch.d_model,
        "num_attention_heads": arch.n_heads, "vocab_size": arch.vocab_size,
        "kv_lora_rank": m.kv_lora_rank, "q_lora_rank": m.q_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim, "qk_rope_head_dim": m.qk_rope_head_dim,
        "v_head_dim": m.v_head_dim, "n_routed_experts": e.n_routed,
        "n_shared_experts": e.n_shared, "num_experts_per_tok": e.top_k,
        "moe_intermediate_size": e.d_expert, "reference": "mla_moe"}))
    (b / "workloads" / "chat-deepseek-v2-reduced.json").write_text(json.dumps({
        "config": "deepseek-v2-reduced", "traffic": "chat", "chips": 1, "rate_per_s": 1.0,
        "why": "added as files"}))
    monkeypatch.setattr(common, "BENCH", b)

    cell = common.load_json("workloads", "chat-deepseek-v2-reduced")
    conf = common.load_json("configs", cell["config"])
    serve = common.load_module("drivers", common.load_json("traffic", cell["traffic"])["driver"])
    ref = serve.reference(conf)
    assert ref.__file__ == str(b / "reference" / "mla_moe.py")
    model = ref.dims(conf)
    shapes = jax.eval_shape(functools.partial(api.init, cfg=arch), jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    tree = {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): tuple(x.shape)
            for path, x in flat}
    assert tree == {l["name"]: l["shape"] for l in W.layout(ref.leaves(model))}

    recs = [{"tokens": [1, 2, 3], "prompt": np.zeros(5, np.int32)},
            {"tokens": [], "prompt": np.zeros(9, np.int32)}]
    ctx = {"config": conf, "model": model, "peaks": {"bf16_flops_per_s": 1e12},
           "window": {"records": recs, "steps": [(0.0, 0.5), (1.0, 1.5)]}}
    work = 1e9 * arch.n_layers * (5 + 2)  # one prefill of 5 tokens, two decoded tokens
    assert common.load_module("metrics", "mfu.serve").read(ctx) == 100.0 * work / 1.0 / 1e12


def test_no_driver_or_reader_names_a_reference():
    """The serving reference and its operation count are reached only
    through the configuration's ``"reference"``."""
    for sub in ("drivers", "metrics"):
        for path in sorted((common.BENCH / sub).glob("*.py")):
            assert "dense_gqa" not in path.read_text(), path


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_prints_no_result():
    p = _run(["--workload", "chat-internlm2", "--seed", "5", "--seconds", "1", "--trace", "0"],
             ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copytree(common.BENCH, tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(["--workload", "chat-internlm2", "--seed", "5", "--seconds", "1", "--trace", "0"],
             tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
