"""Nothing that hides the device, and nothing that moves the compile cache
(ISSUE 22): the cache helper, the store's backend check, the native build."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PRINT_CACHE = (
    "from tikv_tpu.util.compile_cache import place_compile_cache\n"
    "import jax\n"
    "print(place_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _cache_dir_in_child(cwd, **env_over) -> list[str]:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_over)
    r = subprocess.run([sys.executable, "-c", _PRINT_CACHE], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=120, check=True)
    return r.stdout.split()


def test_cache_helper_sets_nothing_when_placed_from_outside(monkeypatch, tmp_path):
    import jax

    from tikv_tpu.util.compile_cache import place_compile_cache

    def refuse(*a, **kw):
        raise AssertionError(f"the helper set {a} in code")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update", refuse)
    assert place_compile_cache() == str(tmp_path)


def test_cache_helper_placed_from_outside_is_what_jax_uses(tmp_path):
    said, used = _cache_dir_in_child(
        tmp_path, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "placed"))
    assert said == used == str(tmp_path / "placed")


def test_cache_helper_default_is_the_checkout_in_every_process(tmp_path):
    """Unset, the cache is ``<checkout>/.jax_cache`` whatever the working
    directory, so two processes of one command share it."""
    other = tmp_path / "elsewhere"
    other.mkdir()
    first = _cache_dir_in_child(tmp_path)
    second = _cache_dir_in_child(other)
    assert first == second == [os.path.join(_ROOT, ".jax_cache")] * 2


def test_device_store_refuses_to_start_without_a_backend(monkeypatch, tmp_path):
    """``StoreServer(enable_device=True)`` initialises the backend first and
    lets what that raises reach the caller: nothing is opened, nothing
    serves on."""
    import jax

    from tikv_tpu.server.standalone import StoreServer

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu': no chip")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="no chip"):
        StoreServer(1, pd=None, data_dir=str(tmp_path / "s1"), enable_device=True)
    assert not (tmp_path / "s1").exists()


def test_device_store_says_what_it_runs_on(capsys):
    import jax

    from tikv_tpu.server.standalone import _default_mesh, init_device_backend

    devices = init_device_backend()
    assert devices == jax.devices()
    d = devices[0]
    assert (f"platform={d.platform} kind={d.device_kind} "
            f"count={len(devices)}") in capsys.readouterr().err
    assert _default_mesh(devices[:1]) is None
    assert _default_mesh(devices[:4]).size == 4


_CC = 'extern "C" int answer() { return %d; }\n'


def test_native_build_replaces_the_target_atomically(tmp_path):
    """Built under a name of its own beside the target and renamed into
    place; fresh targets are left alone, stale ones rebuilt."""
    import ctypes

    from tikv_tpu.native import ensure_built

    src = tmp_path / "lib.cc"
    so = tmp_path / "libanswer.so"
    src.write_text(_CC % 41)
    ensure_built(str(so), str(src))
    assert sorted(p.name for p in tmp_path.iterdir()) == ["lib.cc", "libanswer.so"]
    assert ctypes.CDLL(str(so)).answer() == 41
    built = so.stat().st_mtime_ns
    ensure_built(str(so), str(src))
    assert so.stat().st_mtime_ns == built  # fresh: not rebuilt
    src.write_text(_CC % 42)
    os.utime(src, ns=(built + 10**9, built + 10**9))
    ensure_built(str(so), str(src))
    assert so.stat().st_mtime_ns != built
    assert sorted(p.name for p in tmp_path.iterdir()) == ["lib.cc", "libanswer.so"]


def test_native_build_failure_leaves_no_target(tmp_path):
    from tikv_tpu.native import ensure_built

    src = tmp_path / "bad.cc"
    src.write_text("this is not C++\n")
    with pytest.raises(subprocess.CalledProcessError):
        ensure_built(str(tmp_path / "libbad.so"), str(src))
    assert [p.name for p in tmp_path.iterdir()] == ["bad.cc"]


def test_no_native_binary_is_tracked():
    tracked = subprocess.run(["git", "ls-files", "*.so"], cwd=_ROOT,
                             capture_output=True, text=True)
    if tracked.returncode != 0:
        pytest.skip("not a git checkout")
    assert tracked.stdout.split() == []
