"""Where a checkout keeps what it builds at run time
(_private/cache_dir.py): JAX's persistent compilation cache and the
compiled _native libraries, under one FIXED git-ignored directory. The
path is part of the compilation cache's key, so a directory that moves
(a temporary name, a pid, the time) would never hit."""

import os
import subprocess
import sys

import pytest

from ray_tpu._private import cache_dir, spawn_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = (
    "import jax\n"
    "from ray_tpu._private.cache_dir import enable_compile_cache\n"
    "print('RETURNED', enable_compile_cache())\n"
    "print('CONFIG', jax.config.jax_compilation_cache_dir)\n")


def _ask_a_fresh_process(extra_env):
    env = spawn_env.child_env(repo_path=REPO, extra=extra_env)
    if "JAX_COMPILATION_CACHE_DIR" not in extra_env:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = dict(line.split(" ", 1) for line in out.stdout.splitlines())
    return lines["RETURNED"], lines["CONFIG"]


def test_default_is_one_fixed_path_inside_the_checkout():
    """Two processes (different pids, different times) get the SAME
    directory, and it lies inside the checkout."""
    first = _ask_a_fresh_process({})
    second = _ask_a_fresh_process({})
    want = os.path.join(REPO, cache_dir.CACHE_DIRNAME, "jax")
    assert first == second == (want, want)
    assert os.path.isdir(want)


def test_environment_variable_is_honoured_and_nothing_else_is_set(
        tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax has read it and the
    helper sets no other directory."""
    placed = str(tmp_path / "placed_from_outside")
    returned, configured = _ask_a_fresh_process(
        {"JAX_COMPILATION_CACHE_DIR": placed})
    assert returned == configured == placed


def test_cache_directory_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = [line.strip() for line in f]
    assert cache_dir.CACHE_DIRNAME + "/" in ignored


def test_native_binary_is_named_after_its_source(monkeypatch, tmp_path):
    """A .so is only trusted when it was built from exactly the .cc in
    the tree: its name carries the source's content hash, so a binary of
    another source (or the same source with a newer mtime) never
    loads."""
    import hashlib

    from ray_tpu import _native

    if _native.load_allocator_lib() is None:
        pytest.skip("no C++ toolchain here: " +
                    _native.build_status().get("allocator", ""))
    with open(os.path.join(os.path.dirname(_native.__file__),
                           "allocator.cc"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(cache_dir.checkout_cache_dir("native"),
                      f"_allocator.{digest}.so")
    assert os.path.exists(so)
    assert _native.build_status()["allocator"] in ("built", "found")
    # the build lives in the cache directory, not beside the source
    assert not [f for f in os.listdir(os.path.dirname(_native.__file__))
                if f.endswith(".so")]
