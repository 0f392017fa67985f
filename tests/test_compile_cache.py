"""The entry points' compile-cache helper (launch/compile_cache.py)."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache as cc

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("value", ["/somewhere/else", "relative/dir"])
def test_environment_dir_means_none_set_in_code(value):
    assert cc.compile_cache_dir({cc.ENV: value}) is None


def test_nothing_set_off_the_chip():
    assert cc.compile_cache_dir({}, platform="cpu") is None


def test_default_is_one_fixed_path_inside_the_checkout():
    a, b = cc.compile_cache_dir({}), cc.compile_cache_dir({cc.ENV: ""})
    assert a == b == str(REPO / ".jax_cache")


def test_enable_sets_the_default_only_without_the_variable(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv(cc.ENV, "/from/env")
    assert cc.enable_compile_cache() is None and calls == []
    monkeypatch.delenv(cc.ENV)
    assert cc.enable_compile_cache() == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir",
                      str(REPO / ".jax_cache"))]
