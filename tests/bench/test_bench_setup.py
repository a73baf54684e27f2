"""A serving cell's set-up serves exactly what planning and deploying the
same weights would serve."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, weights as W

serve = common.load_module("drivers", "serve")


def _model(arch):
    return {"n_layers": arch.n_layers, "d_model": arch.d_model, "n_heads": arch.n_heads,
            "n_kv_heads": arch.n_kv_heads, "head_dim": arch.resolved_head_dim,
            "d_ff": arch.d_ff, "vocab_size": arch.vocab_size, "rope_theta": arch.rope_theta}


@pytest.mark.parametrize("name", ["internlm2-1.8b", "yi-6b"])
def test_served_operands_equal_planned_deployment(name):
    from repro.configs import get_arch
    from repro.core.planner import CrossbarSpec, PlannerConfig, build_deployment, deploy_params

    arch = get_arch(name, reduced=True)
    model = _model(arch)
    seed = 2**32 + 17
    served = serve.served_params(arch, model, seed)

    key = W.base_key(seed)
    flat = {}
    for leaf in W.layout(model):
        if leaf["stacked"]:
            flat[leaf["name"]] = jnp.stack([W.leaf_slice(key, leaf, i)
                                            for i in range(leaf["shape"][0])])
        else:
            flat[leaf["name"]] = W.leaf_slice(key, leaf, 0)
    dense = serve._nest(flat)
    plan = build_deployment(dense, CrossbarSpec(), PlannerConfig())
    assert plan.reports and all(r.quant_mse == 0.0 for r in plan.reports.values())
    want = deploy_params(dense, plan, materialize="packed")

    got_l, got_t = jax.tree_util.tree_flatten_with_path(served)
    want_l, want_t = jax.tree_util.tree_flatten_with_path(want)
    assert got_t == want_t
    for (path, a), (_, b) in zip(got_l, want_l):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert a.tobytes() == b.tobytes(), jax.tree_util.keystr(path)


def test_grid_weights_sit_on_the_grid():
    model = {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2, "head_dim": 64,
             "d_ff": 512, "vocab_size": 300}
    key = W.base_key(3)
    for leaf in W.layout(model):
        w = np.asarray(W.leaf_slice(key, leaf, 0))
        if leaf["grid"]:
            q = np.abs(w) / leaf["step"]
            assert np.array_equal(q, np.round(q)) and q.max() == W.LEVELS
        assert np.isfinite(w).all()
