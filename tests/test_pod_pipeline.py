"""Tier-B pod-boundary split inference (core/partition/pod_pipeline):
correctness vs the monolithic forward. Needs >1 fake device for the "pod"
axis, and XLA fixes the device count at first init — so the multi-pod case
runs in a subprocess; the trivial 1-pod case runs in-process."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from conftest import moe_no_drop
from repro.configs.registry import get_smoke_config
from repro.core.partition import pod_pipeline as pp
from repro.models import transformer as tr


def test_pipeline_supported_table():
    ok = {"qwen2-7b", "gemma-7b", "qwen1.5-4b", "nemotron-4-340b",
          "mamba2-2.7b", "mixtral-8x7b", "hubert-xlarge", "qwen2-vl-7b"}
    no = {"zamba2-1.2b", "deepseek-v3-671b"}
    for a in ok:
        assert pp.pipeline_supported(get_smoke_config(a)), a
    for a in no:
        assert not pp.pipeline_supported(get_smoke_config(a)), a


def test_stack_stage_params_shapes():
    cfg = get_smoke_config("qwen2-7b").replace(dtype="float32")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    stacked = pp.stack_stage_params(params, cfg, 2)
    for leaf in jax.tree_util.tree_leaves(stacked):
        assert leaf.shape[0] == 2
        assert leaf.shape[1] == cfg.num_layers // 2


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs.registry import get_smoke_config
    from repro.models import transformer as tr
    from repro.core.partition import pod_pipeline as pp

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    for arch in ["qwen2-7b", "mamba2-2.7b", "mixtral-8x7b"]:
        cfg = get_smoke_config(arch).replace(dtype="float32", remat=False)
        if cfg.moe:
            cfg = cfg.replace(moe=dataclasses.replace(
                cfg.moe,
                capacity_factor=float(cfg.moe.num_experts) / cfg.moe.top_k))
        params = tr.init_params(cfg, jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                 cfg.vocab_size)
        ref, _ = tr.forward(params, cfg, {"tokens": tok})
        sp = dict(params)
        sp["runs"] = [pp.stack_stage_params(params, cfg, 2)]
        with mesh:
            step = pp.make_split_serve_step(cfg, 2, 2, mesh)
            logits = jax.jit(step)(sp, {"tokens": tok})
        err = float(jnp.max(jnp.abs(logits - ref[:, -1])))
        assert err < 2e-3, (arch, err)
        print(arch, "err", err)
    print("PIPELINE_OK")
""")


@pytest.mark.slow
def test_two_pod_pipeline_matches_forward_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], cwd=os.path.join(
        os.path.dirname(__file__), ".."), env=env,
        capture_output=True, text=True, timeout=540)
    assert "PIPELINE_OK" in r.stdout, (r.stdout[-1000:], r.stderr[-2000:])


def test_single_pod_passthrough():
    """n_pods=1: the pipeline degenerates to the plain layer stack."""
    cfg = moe_no_drop(get_smoke_config("qwen2-7b").replace(
        dtype="float32", remat=False))
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                             cfg.vocab_size)
    ref, _ = tr.forward(params, cfg, {"tokens": tok})
    sp = dict(params)
    sp["runs"] = [pp.stack_stage_params(params, cfg, 1)]
    n = len(jax.devices())
    mesh = jax.sharding.Mesh(
        __import__("numpy").array(jax.devices()).reshape(1, 1, n),
        ("pod", "data", "model"))
    with mesh:
        step = pp.make_split_serve_step(cfg, 1, 2, mesh)
        logits = jax.jit(step)(sp, {"tokens": tok})
    err = float(jnp.max(jnp.abs(logits - ref[:, -1])))
    assert err < 2e-3, err
