"""Per-architecture smoke tests (reduced configs, task spec f): one forward /
train step on CPU asserting shapes + finite values, and decode-vs-forward
consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced_config, SHAPES
from repro.models.registry import (
    build_model, cache_specs, input_specs, model_flops, param_counts,
    supports_shape,
)
from repro.train.optimizer import make_optimizer
from repro.train.train_loop import make_train_step

KEY = jax.random.PRNGKey(0)
ALL_ARCHS = list(ARCHS)


def _batch(cfg, B=2, S=32, train=True):
    b = {"tokens": jax.random.randint(KEY, (B, S + (1 if train else 0)), 1, cfg.vocab)}
    if cfg.family == "vlm":
        b["vision_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_vision_tokens, cfg.d_vision)).astype(jnp.bfloat16)
    if cfg.family == "audio":
        b["frames"] = jax.random.normal(KEY, (B, S, cfg.d_audio)).astype(jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch):
    cfg = reduced_config(arch)
    model = build_model(cfg)
    params, specs = model.init(KEY)
    # spec tree matches param tree structure
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda s: 0, specs,
                                        is_leaf=lambda s: not isinstance(s, dict)))
    opt = make_optimizer(cfg.optimizer, lr=1e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt))
    batch = _batch(cfg)
    p2, o2, metrics = step(params, opt_state, batch, jnp.int32(0))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
        params, p2))
    assert max(delta) > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_loss_decreases(arch):
    cfg = reduced_config(arch)
    model = build_model(cfg)
    params, _ = model.init(KEY)
    opt = make_optimizer(cfg.optimizer, lr=3e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt))
    batch = _batch(cfg)  # overfit one batch
    losses = []
    for i in range(8):
        params, opt_state, m = step(params, opt_state, batch, jnp.int32(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_consistency(arch):
    cfg = reduced_config(arch)
    model = build_model(cfg)
    params, _ = model.init(KEY)
    B, S = 2, 32
    batch = _batch(cfg, B, S, train=False)
    last, cache = jax.jit(model.prefill)(params, batch)
    assert last.shape == (B, cfg.vocab)

    def pad_seq(x, axis, to):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, to - x.shape[axis])
        return jnp.pad(x, pad)

    fam = cfg.family
    if fam in ("dense", "moe"):
        cache = {"layers": {k: pad_seq(v, 2, S + 8) for k, v in cache["layers"].items()},
                 "length": cache["length"]}
    elif fam == "hybrid":
        cache = {"mamba": cache["mamba"],
                 "shared": {k: pad_seq(v, 2, S + 8) for k, v in cache["shared"].items()},
                 "length": cache["length"]}
    elif fam == "vlm":
        cache = {"self": {k: pad_seq(v, 3, S + 8) for k, v in cache["self"].items()},
                 "cross": cache["cross"], "length": cache["length"]}
    elif fam == "audio":
        cache = {"self": {k: pad_seq(v, 2, S + 8) for k, v in cache["self"].items()},
                 "cross": cache["cross"], "length": cache["length"]}
    nxt = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
    logits1, _ = jax.jit(model.decode_step)(params, cache, nxt)

    toks2 = jnp.concatenate([batch["tokens"], nxt], axis=1)
    logits_ref, _, _ = model._full_forward(params, {**batch, "tokens": toks2}, "prefill")
    ref = logits_ref[:, -1].astype(np.float32)
    got = logits1.astype(np.float32)
    err = float(jnp.abs(ref - got).max() / (jnp.abs(ref).max() + 1e-6))
    assert err < 0.06, (arch, err)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_exactness(arch):
    """The FULL configs carry the assigned numbers (exercised abstractly)."""
    cfg = get_config(arch)
    total, active = param_counts(cfg)
    expected = {
        "smollm-360m": 0.36e9, "qwen1.5-4b": 4e9, "qwen2-72b": 72.7e9,
        "qwen1.5-32b": 32e9, "mamba2-780m": 0.78e9, "grok-1-314b": 314e9,
        "deepseek-v2-lite-16b": 15.7e9, "zamba2-7b": 7e9,
        "llama-3.2-vision-90b": 90e9, "seamless-m4t-medium": 1.2e9,
    }[arch]
    assert 0.65 * expected <= total <= 1.35 * expected, (arch, total)


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_and_cache_specs_constructible(arch, shape):
    cfg = get_config(arch)
    sh = SHAPES[shape]
    ok, _ = supports_shape(cfg, sh)
    if not ok:
        pytest.skip("shape unsupported by design")
    ins = input_specs(cfg, sh)
    assert "tokens" in ins
    if sh.kind == "decode":
        shapes, specs = cache_specs(cfg, sh, dp_total=16)
        assert jax.tree.structure(shapes) == jax.tree.structure(
            jax.tree.map(lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, dict)))
    assert model_flops(cfg, sh) > 0


def test_moe_sharded_matches_reference_subprocess():
    """EP a2a dispatch vs dense reference — run on 8 fake devices."""
    import subprocess, sys, os

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import reduced_config
import dataclasses
from repro.configs.base import MoECfg
from repro.models.moe import init_moe, moe_apply_reference, moe_apply_sharded

cfg = reduced_config("grok-1-314b")
cfg = dataclasses.replace(cfg, moe=MoECfg(n_experts=4, top_k=2, d_ff_expert=64,
                                          capacity_factor=8.0))
mesh = jax.make_mesh((4, 2), ("data", "model"))
params, _ = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32, data_size=4)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)
ref, _ = moe_apply_reference(params, cfg, x)
pspec = {"router": {"w": P(None, None)}, "wi": P("data", None, "model"),
         "wg": P("data", None, "model"), "wo": P("data", "model", None)}
with jax.set_mesh(mesh):
    out, aux = jax.jit(jax.shard_map(
        lambda pp, xx: moe_apply_sharded(pp, cfg, xx),
        mesh=mesh, in_specs=(pspec, P(("data",), None, None)),
        out_specs=(P(("data",), None, None), {"aux": P(), "dropped": P()}),
        check_vma=False))(params, x)
err = float(jnp.abs(ref - out).max() / (jnp.abs(ref).max() + 1e-9))
print("rel err", err, "dropped", float(aux["dropped"]))
assert err < 2e-2, err
print("MOE_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "MOE_OK" in r.stdout, r.stdout + r.stderr
