"""Persistent XLA compilation cache (core/compile_cache.py).

Reference analog: engine/result caching in backends (TensorRT serialized
engine cache); here compiled XLA executables persist across processes.
The directory is placed from outside: ``JAX_COMPILATION_CACHE_DIR`` when
set (jax reads it; the program writes no directory), else the fixed
``<checkout>/.jax_cache``.
"""

import os
import subprocess
import sys

import jax
import pytest

from nnstreamer_tpu.core import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_jax_cache_config():
    """Later tests must not write cache entries where these tests point."""
    prior_dir = jax.config.jax_compilation_cache_dir
    prior_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prior_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prior_min)


def test_variable_set_leaves_jax_config_dir_untouched(
        tmp_path, monkeypatch, restore_jax_cache_config):
    target = str(tmp_path / "from_outside")
    monkeypatch.setenv(compile_cache.ENV_VAR, target)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == target
    # jax read the variable at import (or did not, in this process): either
    # way the directory in jax.config is not ours to write
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(compile_cache.CHECKOUT_CACHE) or (
        target != compile_cache.CHECKOUT_CACHE)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_variable_unset_uses_fixed_checkout_path(
        monkeypatch, restore_jax_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.CHECKOUT_CACHE == os.path.join(ROOT, ".jax_cache")
    # an accelerator process caches in the checkout ...
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache.os, "makedirs", lambda *a, **k: None)
    assert compile_cache.enable() == compile_cache.CHECKOUT_CACHE
    assert (jax.config.jax_compilation_cache_dir
            == compile_cache.CHECKOUT_CACHE)
    assert compile_cache.enable() == compile_cache.CHECKOUT_CACHE  # idempotent


def test_cpu_process_without_variable_caches_nothing(
        monkeypatch, restore_jax_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_no_competing_knobs():
    """One way to place the cache: no ini/env knob of our own, no per-host
    subtree, nothing under $HOME."""
    src = open(compile_cache.__file__).read()
    for gone in ("NNS_TPU_XLA", "host_fingerprint", "expanduser", "~/"):
        assert gone not in src, gone


def test_cache_populates_across_processes(tmp_path):
    """A fresh process compiling through the jax-xla backend writes cache
    entries where the variable points — and nowhere in the checkout; a
    second fresh process starts with a warm cache dir."""
    cache = str(tmp_path / "xc")
    src = (
        "import os, sys, numpy as np;"
        f"sys.path.insert(0, {ROOT!r});"
        "from nnstreamer_tpu.elements.filter import SingleShot;"
        "s = SingleShot(framework='jax-xla', model='zoo',"
        " custom='arch:mnist_cnn,dtype:float32');"
        "out = s.invoke_batch([np.zeros((4, 28, 28, 1), np.float32)]);"
        "s.close(); print('OK', out[0].shape)"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PLATFORMS="cpu")
    had_checkout_cache = os.path.exists(compile_cache.CHECKOUT_CACHE)
    r1 = subprocess.run(
        [sys.executable, "-c", src], env=env, capture_output=True,
        text=True, timeout=240,
    )
    assert r1.returncode == 0, r1.stderr[-2000:]
    entries = os.listdir(cache)
    assert entries, "first run wrote no cache entries"
    assert os.path.exists(compile_cache.CHECKOUT_CACHE) == had_checkout_cache
    r2 = subprocess.run(
        [sys.executable, "-c", src], env=env, capture_output=True,
        text=True, timeout=240,
    )
    assert r2.returncode == 0, r2.stderr[-2000:]
