"""``repro.launch.train.main`` as a library call: ``--full``, the returned
run summary, the non-finite-loss stop, and the compile-cache rule."""

from __future__ import annotations

import math

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.train import main


def _stream_args(tmp_path, *extra):
    return ["--arch", "dcn-v2", "--spec", "dlrm",
            "--data-dir", str(tmp_path / "shards"), "--gen-shards", "2",
            "--batch", "64", "--steps", "2", *extra]


def test_full_builds_published_config_and_returns_summary(tmp_path):
    """--full trains the published dcn-v2 widths (vocabularies scaled down
    so the table fits a test) and main returns the run's summary."""
    from repro.configs import get_arch

    out = main(_stream_args(tmp_path, "--full", "--vocab-scale", "1e-5"))
    published = get_arch("dcn-v2").config
    cfg = out["config"]
    assert (cfg.name, cfg.embed_dim, cfg.top_mlp, cfg.n_sparse) == (
        published.name, published.embed_dim, published.top_mlp,
        published.n_sparse)
    assert cfg.vocab_sizes == tuple(max(1, int(v * 1e-5))
                                    for v in published.vocab_sizes)
    assert out["steps"] == 2 and len(out["losses"]) == 2
    assert all(math.isfinite(v) for v in out["losses"])
    assert len(out["step_seconds"]) == 2
    assert out["stats"].batches == 2
    (platform, _, rows), = out["table_shards"]
    assert platform == jax.devices()[0].platform
    assert rows == cfg.padded_rows


def test_smoke_config_is_the_default(tmp_path):
    out = main(_stream_args(tmp_path))
    assert out["config"].name == "dcnv2-smoke"


def test_non_finite_loss_stops_the_run(tmp_path):
    # An lr near float32's max overflows the dense params on the first update.
    with pytest.raises(FloatingPointError, match="non-finite loss"):
        main(_stream_args(tmp_path, "--lr", "1e38"))


def test_compile_cache_defers_to_env_var(monkeypatch, tmp_path):
    set_dirs = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: set_dirs.append((name, val)))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV_VAR)
    # The CPU backend caches nothing; an accelerator uses the fixed path.
    assert compile_cache.enable_compile_cache() is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.enable_compile_cache() == str(
        compile_cache.DEFAULT_DIR)
    assert set_dirs == [("jax_compilation_cache_dir",
                         str(compile_cache.DEFAULT_DIR))]
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "pyproject.toml").exists()
