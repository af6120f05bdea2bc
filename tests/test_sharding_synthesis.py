"""The paper's synthesizer as the framework's sharding engine: regime
decisions, divisibility fallbacks, spec construction."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.problem import ConvProblem
from repro.core.sharding_synthesis import synthesize_layer
from repro.models.api import model_fns
from repro.parallel import sharding as shd


# fake_mesh is the AbstractMesh factory fixture from
# conftest.py (tests run single-device per the dry-run contract).


def test_synthesize_layer_prefers_dp_for_activation_heavy():
    """Tall-skinny matmul (huge tokens, small weight): bhw split wins."""
    prob = ConvProblem.from_matmul(1 << 20, 256, 256)
    ls = synthesize_layer(prob, {"data": 16, "model": 16}, 8 * 1024 ** 3,
                          forced={"data": "bhw"})
    assert ls.assignment["model"] == "bhw"


def test_synthesize_layer_prefers_contraction_split_for_wide_weights():
    """Few tokens, giant weight: the 2.5D/3D c-split or k-split wins."""
    prob = ConvProblem.from_matmul(128, 1 << 15, 1 << 15)
    ls = synthesize_layer(prob, {"data": 16, "model": 16}, 8 * 1024 ** 3,
                          forced={"data": "bhw"})
    assert ls.assignment["model"] in ("k", "c")


def test_decide_trains_away_from_pure_dp_when_memory_bound():
    """With a tight Eq. 11 budget the decision must leave 'bhw'."""
    w = shd._decide(1 << 20, 4096, 16384, 16, 16, 1, True, 10**6)
    assert w in ("k", "c")


def test_decide_serve_prefers_tp():
    """Decode (tokens=batch=128): weights dominate -> TP chosen."""
    w = shd._decide(128, 8192, 29568, 16, 16, 1, False, 1 << 62)
    assert w in ("k", "c")


def test_param_specs_cover_all_leaves_and_divide(fake_mesh):
    mesh = fake_mesh()
    for arch in ["llama3.2-1b", "qwen3-moe-235b-a22b", "zamba2-7b",
                 "whisper-tiny", "xlstm-350m"]:
        cfg = get_config(arch)
        fns = model_fns(cfg)
        params_shape = jax.eval_shape(
            lambda fns=fns, cfg=cfg: fns.init(jax.random.PRNGKey(0), cfg))
        specs = shd.param_specs(cfg, params_shape, mesh,
                                tokens_per_step=1 << 20)
        flat_p = jax.tree.leaves(params_shape)
        flat_s = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = int(np.prod([mesh.shape[a] for a in axes]))
                assert leaf.shape[dim] % size == 0, (leaf.shape, spec)


def test_param_specs_shard_moe_experts(fake_mesh):
    mesh = fake_mesh()
    cfg = get_config("qwen3-moe-235b-a22b")
    fns = model_fns(cfg)
    params_shape = jax.eval_shape(
        lambda: fns.init(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(cfg, params_shape, mesh,
                            tokens_per_step=1 << 20)
    assert specs["blocks"]["moe"]["w_up"][1] == "model"   # EP on expert dim


def test_vocab_fallback_for_non_divisible(fake_mesh):
    mesh = fake_mesh()
    cfg = get_config("whisper-tiny")   # vocab 51865, not divisible by 16
    fns = model_fns(cfg)
    params_shape = jax.eval_shape(
        lambda: fns.init(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(cfg, params_shape, mesh, tokens_per_step=4096)
    assert specs["emb"]["lm_head"] == P("model", None)  # d-dim fallback


def test_batch_and_cache_specs(fake_mesh):
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    cfg = get_config("llama3.2-1b")
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    bs = shd.batch_specs(cfg, mesh, batch, global_batch=256)
    assert bs["tokens"][0] == ("pod", "data")
    from repro.models import lm
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 128, 32768))
    cs = shd.cache_specs(cfg, mesh, cache, batch=128)
    assert cs["k"][2] == "model"        # sequence-parallel cache
    assert cs["k"][1] == ("pod", "data")


def test_batch_not_shardable_stays_replicated(fake_mesh):
    mesh = fake_mesh()
    cfg = get_config("llama3.2-1b")
    batch = {"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)}
    bs = shd.batch_specs(cfg, mesh, batch, global_batch=1)
    assert bs["tokens"] == P(None, None)
