"""Runtime-env dependency plugins: py_modules + pip with URI caching
(reference ``python/ray/_private/runtime_env/{py_modules.py,pip.py,
uri_cache.py}``)."""

import glob
import os
import tempfile
import textwrap

import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def _cluster(ray_cluster):
    yield


def _make_py_module(tmp_path, name: str, body: str) -> str:
    pkg = os.path.join(str(tmp_path), name)
    os.makedirs(pkg, exist_ok=True)
    with open(os.path.join(pkg, "__init__.py"), "w") as f:
        f.write(body)
    return pkg


def test_py_modules_staged_on_worker_path(tmp_path):
    pkg = _make_py_module(tmp_path, "renv_mod_a", "MAGIC = 41\n")

    @ray_tpu.remote
    def use_module():
        import renv_mod_a

        return renv_mod_a.MAGIC + 1

    assert ray_tpu.get(
        use_module.options(runtime_env={"py_modules": [pkg]}).remote(),
        timeout=60) == 42


def test_py_modules_content_hash_invalidates(tmp_path):
    """Editing the module produces a fresh URI: workers see the new code,
    not a stale cache entry."""
    pkg = _make_py_module(tmp_path, "renv_mod_b", "VALUE = 1\n")

    @ray_tpu.remote
    def read_value():
        import renv_mod_b

        return renv_mod_b.VALUE

    assert ray_tpu.get(
        read_value.options(runtime_env={"py_modules": [pkg]}).remote(),
        timeout=60) == 1
    with open(os.path.join(pkg, "__init__.py"), "w") as f:
        f.write("VALUE = 2\n")
    assert ray_tpu.get(
        read_value.options(runtime_env={"py_modules": [pkg]}).remote(),
        timeout=60) == 2


def test_pip_local_package_installed_once(tmp_path):
    """pip requirements install into a cached --target dir exactly once;
    a second task with the same spec reuses the URI (reference
    uri_cache.py create-once semantics)."""
    pip_pkg = str(tmp_path / "pipsrc")
    os.makedirs(os.path.join(pip_pkg, "renv_pipmod"))
    with open(os.path.join(pip_pkg, "renv_pipmod", "__init__.py"), "w") as f:
        f.write("VALUE = 'installed'\n")
    with open(os.path.join(pip_pkg, "pyproject.toml"), "w") as f:
        f.write(textwrap.dedent("""
            [build-system]
            requires = ["setuptools"]
            build-backend = "setuptools.build_meta"
            [project]
            name = "renv-pipmod"
            version = "0.1"
            [tool.setuptools]
            packages = ["renv_pipmod"]
        """))

    @ray_tpu.remote
    def use_pip():
        import renv_pipmod

        return renv_pipmod.VALUE

    renv = {"pip": [pip_pkg]}
    assert ray_tpu.get(use_pip.options(runtime_env=renv).remote(), timeout=60) == "installed"
    before = set(glob.glob("/tmp/ray_tpu/runtime_env/pip/*"))
    assert ray_tpu.get(use_pip.options(runtime_env=renv).remote(), timeout=60) == "installed"
    after = set(glob.glob("/tmp/ray_tpu/runtime_env/pip/*"))
    assert before == after  # cached URI reused, no reinstall


def test_mismatched_envs_never_share_a_worker(tmp_path):
    """Two tasks with identical resources but different py_modules must
    run on different workers (the lease pipeline keys on the FULL runtime
    env; a reused lease would import the wrong world)."""
    pkg_a = _make_py_module(tmp_path, "renv_only_a", "X = 'a'\n")

    @ray_tpu.remote
    def has_module(name):
        import importlib

        try:
            importlib.import_module(name)
            return True
        except ImportError:
            return False

    assert ray_tpu.get(
        has_module.options(runtime_env={"py_modules": [pkg_a]}).remote("renv_only_a"),
        timeout=60) is True
    # plain-env task right after: must NOT land on the py_modules worker
    assert ray_tpu.get(has_module.remote("renv_only_a"), timeout=60) is False


def test_py_executable_plugin(ray_cluster):
    """runtime_env py_executable picks the worker's interpreter
    (reference runtime_env/py_executable.py) — here the same python via
    its real path, proving the plumb reaches the spawn."""
    import sys

    import ray_tpu

    @ray_tpu.remote(runtime_env={"py_executable": sys.executable})
    def which_python():
        import sys as s

        return s.executable

    out = ray_tpu.get(which_python.remote(), timeout=60)
    assert out == sys.executable


def test_conda_and_container_gated_errors(ray_cluster):
    """conda/container plugins fail the LEASE with a clear setup error
    when the node lacks the tooling (this image has neither), instead of
    crash-looping a worker (reference runtime_env setup-error surface)."""
    import pytest as _pytest

    import ray_tpu
    from ray_tpu.core.runtime_env import (
        RuntimeEnvSetupError, resolve_python_executable, wrap_worker_command)

    import shutil

    if shutil.which("conda") or shutil.which("micromamba"):
        _pytest.skip("conda present on this host")
    with _pytest.raises(RuntimeEnvSetupError, match="conda"):
        resolve_python_executable({"conda": "myenv"})
    if shutil.which("docker") or shutil.which("podman"):
        _pytest.skip("container runtime present on this host")
    with _pytest.raises(RuntimeEnvSetupError, match="podman or docker"):
        wrap_worker_command(["python"], {"image_uri": "img:latest"})

    @ray_tpu.remote(runtime_env={"conda": "myenv"})
    def f():
        return 1

    with _pytest.raises(Exception, match="conda"):
        ray_tpu.get(f.remote(), timeout=60)


def test_conda_plugin_resolves_existing_env(tmp_path, monkeypatch):
    """With a (stubbed) conda on PATH, a string spec resolves to the
    named env's interpreter."""
    import stat
    import sys

    from ray_tpu.core.runtime_env import resolve_python_executable

    base = tmp_path / "conda_base"
    envpy = base / "envs" / "myenv" / "bin"
    envpy.mkdir(parents=True)
    (envpy / "python").write_text("#!/bin/sh\n")
    stub = tmp_path / "bin" / "conda"
    stub.parent.mkdir()
    stub.write_text(f"#!/bin/sh\necho {base}\n")
    stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("PATH", f"{stub.parent}:{os.environ['PATH']}")
    monkeypatch.delenv("CONDA_EXE", raising=False)
    py = resolve_python_executable({"conda": "myenv"})
    assert py == str(envpy / "python")
