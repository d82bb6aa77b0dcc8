"""Entry-point helpers that keep the chip usable: the persistent compile
cache location, and the net front-end's one-worker-per-device rule."""
import argparse
import os

import jax
import pytest

from repro.launch import compile_cache, serve


def test_cache_dir_from_environment_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert path == os.path.join(compile_cache.checkout_root(),
                                    compile_cache.CHECKOUT_DIR)
        assert os.path.isdir(os.path.join(compile_cache.checkout_root(),
                                          "src", "repro"))
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_net_mode_refuses_more_workers_than_devices(monkeypatch):
    monkeypatch.setattr(serve, "_device_census", lambda: ("tpu", 1))
    with pytest.raises(SystemExit, match="each worker process needs one"):
        serve.run_net(argparse.Namespace(workers=2))
