"""The entry points' compile-cache placement (``repro.launch.compile_cache``).

``jax.config.update`` is replaced by a recorder, so no test here turns a
cache on in the test process.
"""

import jax

from repro.launch import compile_cache as CC


def _recorder(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_environment_directory_is_used_and_nothing_is_set(monkeypatch,
                                                          tmp_path):
    calls = _recorder(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_directory_is_fixed_inside_the_checkout(monkeypatch):
    calls = _recorder(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = CC.enable_compile_cache()
    assert first == CC.enable_compile_cache()      # no tmp name, pid or time
    assert first == str(CC.CHECKOUT / ".jax_cache")
    assert (CC.CHECKOUT / "pyproject.toml").is_file()
    assert calls == [("jax_compilation_cache_dir", first)] * 2
