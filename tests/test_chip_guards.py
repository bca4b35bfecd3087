"""Guards that keep the chip path honest, checked on the CPU.

* ``ops.interpret_default`` picks interpret mode on the CPU only: on any
  backend that is neither TPU nor CPU it raises instead of silently
  running the kernels through the Python interpreter;
* the engine refuses a ``KernelBackend`` behind a ``SocketTransport``,
  whose child processes would each reach for the chip this process holds;
* ``chip_smoke.py`` exits non-zero without a TPU and never prints its
  ``"ok": true`` line; its phases pass at a tiny size on the CPU;
* the compile cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and
  otherwise uses ``<checkout>/.jax_cache``.
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

from repro.cluster import (ClusterConfig, CodedExecutionEngine, NoSlowdown,
                           SocketTransport, kernel_backend)
from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _cpu_env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    env.update(extra)
    return env


class TestInterpretDefault:
    def test_cpu_interprets(self):
        assert ops.interpret_default() is True

    @pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True)])
    def test_known_backends(self, monkeypatch, backend, want):
        monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
        assert ops.interpret_default() is want

    @pytest.mark.parametrize("backend", ["gpu", "cuda", "rocm", "metal"])
    def test_other_backend_raises(self, monkeypatch, backend):
        monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
        with pytest.raises(RuntimeError, match=backend):
            ops.interpret_default()


def test_engine_refuses_kernel_backend_over_sockets():
    with pytest.raises(ValueError, match="KernelBackend"):
        CodedExecutionEngine(ClusterConfig(n_workers=4, k=2), NoSlowdown(),
                             compute=kernel_backend(),
                             transport=SocketTransport())


class TestChipSmoke:
    def test_exits_nonzero_without_tpu(self):
        proc = subprocess.run([sys.executable, str(SMOKE)], cwd=ROOT,
                              env=_cpu_env(), capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout
        assert "no TPU" in proc.stderr

    def test_phases_pass_at_tiny_size(self):
        """The smoke's own phases and checks, rehearsed in interpret mode."""
        spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        check = smoke.Checks()
        smoke.single_chip(2400, 256, seed=0, row_cost=1e-6, check=check,
                          min_shard_bytes=12 * 240 * 256 * 4)
        assert check.failed == []


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(tmp_path, env_dir):
    extra = {}
    want = str(ROOT / ".jax_cache")
    if env_dir is not None:
        want = str(tmp_path / env_dir)
        extra["JAX_COMPILATION_CACHE_DIR"] = want
    env = _cpu_env(**extra)
    if env_dir is None:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = ("import jax\n"
            "from repro.jax_cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [want, want]
