"""Standalone smoke coverage for serve/step.py and launch/dryrun.py on a
1-device mesh — the pieces previously only imported by integration tests:
serve_rules/cache_specs rule output, both serve_step variants end-to-end,
and the dry-run --smoke CI gate (lower+compile real cells at smoke scale).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist.sharding import cache_specs, serve_rules
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.models.spec import partition_specs


class FakeMesh:
    def __init__(self, **axes):
        self.shape = dict(axes)


# ---------------------------------------------------------------- rules


def test_serve_rules_are_tp_only():
    mesh = FakeMesh(data=16, model=16)
    rules = serve_rules(mesh)
    assert all(v == "model" for v in rules.values())
    assert "embed" not in rules            # batch axes stay free for requests

    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    specs = partition_specs(api.init_specs(), rules, mesh)
    # wq (d_model, heads*hd): heads dim takes "model", embed replicated
    assert specs["group"]["b0_attn"]["attn"]["wq"] == P(None, None, "model")


def test_cache_specs_page_ownership():
    """Page dim shards over the batch axes (each shard owns a contiguous
    page block); GQA kv-head dims that don't divide TP stay replicated."""
    mesh = FakeMesh(data=16, model=16)
    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    caches = jax.eval_shape(lambda: api.init_caches(32, 64, page_tokens=16))
    specs = cache_specs(mesh, caches)
    assert specs["page_table"] == P("data")
    assert specs["lengths"] == P("data")
    # stacked pool (layers, pages, page_tokens, kv, hd): pages over "data",
    # kv (2 heads) % 16 != 0 -> replicated
    pool_spec = specs["group"]["b0_attn"][0]
    assert pool_spec == P(None, "data")


def test_cache_specs_state_caches():
    """Recurrent/SSM state (no pages) shards its batch dim only."""
    mesh = FakeMesh(data=4, model=2)
    cfg = get_config("mamba2-1.3b", smoke=True)
    api = build_model(cfg)
    caches = jax.eval_shape(lambda: api.init_caches(8, 64, page_tokens=16))
    specs = cache_specs(mesh, caches)
    for leaf in jax.tree.leaves(specs["group"],
                                is_leaf=lambda x: isinstance(x, P)):
        assert leaf in (P(), P(None, "data"))  # (layers, B, ...) or scalarish


# ---------------------------------------------------------------- serve_step


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-1.3b"])
def test_serve_step_smoke_decodes(arch):
    from repro.models.spec import init_params
    from repro.serve.step import make_serve_step

    cfg = get_config(arch, smoke=True)
    api = build_model(cfg)
    params = init_params(api.init_specs(), jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        caches = api.init_caches(2, 32, page_tokens=8)
        step, param_sh, cache_sh = make_serve_step(api, mesh, caches,
                                                   donate=False)
        tok = jnp.asarray([[3], [9]], jnp.int32)
        n_new = jnp.asarray([1, 1], jnp.int32)
        for i in range(3):
            logits, caches = step(params, tok, caches, n_new)
        assert logits.shape == (2, 1, cfg.vocab)
        assert np.isfinite(np.asarray(logits)).all()
        np.testing.assert_array_equal(np.asarray(caches["lengths"]), [3, 3])


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-1.3b"])
def test_serve_step_smoke_chunked(arch):
    """The SAME builder serves a multi-token chunk: mixed n_new (one slot
    prefilling a full chunk, one decoding a single token) in one call."""
    from repro.models.spec import init_params
    from repro.serve.step import make_serve_step

    cfg = get_config(arch, smoke=True)
    api = build_model(cfg)
    params = init_params(api.init_specs(), jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        caches = api.init_caches(2, 32, page_tokens=8)
        step, _, _ = make_serve_step(api, mesh, caches, donate=False)
        tok = jnp.asarray([[3, 4, 5, 6, 7, 8, 9, 10],
                           [9, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)
        n_new = jnp.asarray([8, 1], jnp.int32)
        logits, caches = step(params, tok, caches, n_new)
        assert logits.shape == (2, 8, cfg.vocab)
        assert np.isfinite(np.asarray(logits)[0]).all()
        assert np.isfinite(np.asarray(logits)[1, 0]).all()
        np.testing.assert_array_equal(np.asarray(caches["lengths"]), [8, 1])


# ---------------------------------------------------------------- dryrun


def test_dryrun_smoke_cell_decode():
    from repro.launch.dryrun import lower_cell

    record, compiled = lower_cell("qwen2-1.5b", "decode_32k", smoke=True)
    assert record["kind"] == "decode"
    assert record["mesh"].startswith("host")
    assert record["compile_s"] >= 0
    assert record["memory"]["argument_bytes"] > 0
    assert compiled is not None


def test_dryrun_smoke_cell_train():
    from repro.launch.dryrun import lower_cell

    record, _ = lower_cell("qwen2-1.5b", "train_4k", smoke=True,
                           microbatches=2)
    assert record["kind"] == "train"
    assert record["memory"]["peak_bytes_est"] > 0


def test_dryrun_smoke_respects_skip_table():
    from repro.launch.dryrun import lower_cell

    cfg = get_config("qwen2-1.5b")
    if cfg.supports_long_context:
        pytest.skip("arch runs long_500k; skip rule not applicable")
    with pytest.raises(ValueError):
        lower_cell("qwen2-1.5b", "long_500k", smoke=True)


# ---------------------------------------------------------------- jax set-up


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch):
    from repro.launch.jax_setup import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert enable_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    from pathlib import Path

    from repro.launch.jax_setup import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_device_line_names_platform_kind_and_count():
    from repro.launch.jax_setup import device_line

    dev = jax.devices()[0]
    assert device_line() == (f"platform={dev.platform} "
                             f"device_kind={dev.device_kind} "
                             f"count={len(jax.devices())}")
