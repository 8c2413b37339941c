"""Platform bootstrap tests (fleetflow_tpu/platform.py).

The decision is made in-process and never falls back: a required chip that
is absent raises, a CPU request is honoured, and the persistent compile
cache goes where JAX_COMPILATION_CACHE_DIR says (else one fixed path in the
checkout). Everything that would disturb this process's (conftest-forced
CPU, cache-off) JAX state runs in a child process with a doctored
environment.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from fleetflow_tpu import platform as fp

REPO = Path(__file__).resolve().parent.parent


def run_py(src: str, env_overrides: dict, timeout: float = 120.0):
    env = dict(os.environ)
    env.update(env_overrides)
    return subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=REPO)


class TestForceCpu:
    def test_appends_device_count_flag(self):
        out = run_py(
            "import os; os.environ.pop('XLA_FLAGS', None);"
            "import fleetflow_tpu.platform as fp; fp.force_cpu(5);"
            "print('FLAGS', os.environ['XLA_FLAGS']);"
            "import jax; print('NDEV', jax.device_count())",
            {"JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        assert "--xla_force_host_platform_device_count=5" in out.stdout
        assert "NDEV 5" in out.stdout

    def test_bumps_too_small_count(self):
        out = run_py(
            "import os;"
            "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=2';"
            "import fleetflow_tpu.platform as fp; fp.force_cpu(6);"
            "print('FLAGS', os.environ['XLA_FLAGS'])",
            {"JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        assert "--xla_force_host_platform_device_count=6" in out.stdout

    def test_keeps_larger_count(self):
        out = run_py(
            "import os;"
            "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=16';"
            "import fleetflow_tpu.platform as fp; fp.force_cpu(4);"
            "print('FLAGS', os.environ['XLA_FLAGS'])",
            {"JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        assert "--xla_force_host_platform_device_count=16" in out.stdout


class TestInitPlatform:
    def test_reports_what_jax_initialised(self):
        import jax
        info = fp.init_platform()
        assert info == {"platform": "cpu",
                        "kind": jax.devices()[0].device_kind,
                        "count": len(jax.devices())}

    def test_required_chip_absent_raises(self, monkeypatch):
        # JAX_PLATFORMS=cpu is ambient in a sandbox, not a request a
        # measurement entry point accepts
        monkeypatch.delenv("FLEET_FORCE_CPU", raising=False)
        with pytest.raises(fp.NoAcceleratorError, match="no accelerator"):
            fp.init_platform(require_accelerator=True)

    def test_force_cpu_env_is_the_explicit_cpu_run(self):
        out = run_py(
            "import fleetflow_tpu.platform as fp;"
            "i = fp.init_platform(min_devices=3, require_accelerator=True);"
            "import jax; print('RES', i['platform'], i['count'],"
            " jax.default_backend(), jax.device_count())",
            {"FLEET_FORCE_CPU": "1", "JAX_PLATFORMS": "", "XLA_FLAGS": ""})
        assert out.returncode == 0, out.stderr
        line = [l for l in out.stdout.splitlines() if l.startswith("RES ")][0]
        assert line.split()[1:] == ["cpu", "3", "cpu", "3"]
        assert "FLEET_FORCE_CPU=1" in out.stderr     # said out loud

    def test_too_few_devices_raises(self):
        import jax
        with pytest.raises(RuntimeError, match="devices required"):
            fp.init_platform(min_devices=len(jax.devices()) + 1)

    def test_broken_platform_raises_instead_of_falling_back(self):
        out = run_py(
            "import fleetflow_tpu.platform as fp; fp.init_platform()",
            {"JAX_PLATFORMS": "nonexistent_backend_xyz"})
        assert out.returncode != 0
        assert "nonexistent_backend_xyz" in out.stderr

    def test_no_probe_machinery_left(self):
        src = (REPO / "fleetflow_tpu" / "platform.py").read_text()
        assert "subprocess" not in src
        assert "FLEET_" + "PROBE" not in src


class TestCompileCachePlacement:
    """Where the persistent cache lands. Children only read the config —
    nothing is compiled, so no directory is populated."""

    SRC = ("import jax, fleetflow_tpu.platform as fp;"
           "print('RES', fp.maybe_enable_compile_cache(), '|',"
           " jax.config.jax_compilation_cache_dir, '|',"
           " jax.config.jax_persistent_cache_min_compile_time_secs)")

    def test_jax_variable_wins_and_is_not_overwritten(self, tmp_path):
        out = run_py(
            "import jax; real = jax.config.update;\n"
            "def spy(k, v):\n"
            "    assert k != 'jax_compilation_cache_dir', (k, v)\n"
            "    real(k, v)\n"
            "jax.config.update = spy\n" + self.SRC.replace(";", "\n"),
            {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
             "JAX_ENABLE_COMPILATION_CACHE": "true"})
        assert out.returncode == 0, out.stderr
        d = str(tmp_path / "cc")
        assert f"RES {d} | {d} | 0.0" in out.stdout

    def test_unset_uses_the_fixed_checkout_path(self):
        out = run_py(self.SRC, {"JAX_COMPILATION_CACHE_DIR": "",
                                "JAX_ENABLE_COMPILATION_CACHE": "true"})
        assert out.returncode == 0, out.stderr
        d = str(REPO / ".jax_cache")
        assert fp.COMPILE_CACHE_DEFAULT == d
        assert f"RES {d} | {d} | 0.0" in out.stdout

    def test_jax_switch_turns_it_off(self):
        # conftest's setting for this very process
        assert fp.maybe_enable_compile_cache() is None
        assert fp.compile_cache_info()["enabled"] is False

    def test_no_moving_cache_path(self):
        """A cache whose path moves never hits: no mkdtemp, pid or clock
        may feed a cache directory in the launchers or the bootstrap."""
        for name in ("chip_smoke.py", "fleetflow_tpu/platform.py"):
            src = (REPO / name).read_text()
            assert "mkdtemp" not in src and "getpid" not in src, name
            for line in src.splitlines():
                if re.search(r"cache", line, re.I) and re.search(
                        r"\btime\.|\bdatetime\b|\buuid\b|\brandom\b", line):
                    raise AssertionError(f"{name}: {line.strip()}")


class TestGraftEntry:
    # The actual driver gates, each in its own clean child process (the
    # driver runs them in separate processes too). XLA_FLAGS is scrubbed so
    # the conftest 8-device flag cannot leak in and mask sizing bugs.

    def test_entry_under_forced_cpu(self):
        out = run_py(
            "import __graft_entry__ as g;"
            "import jax;"
            "fn, args = g.entry();"
            "out = jax.jit(fn)(*args); jax.block_until_ready(out);"
            "print('GATE ok', out.shape)",
            {"FLEET_FORCE_CPU": "1", "XLA_FLAGS": ""}, timeout=420.0)
        assert out.returncode == 0, out.stderr
        assert "GATE ok" in out.stdout

    def test_dryrun_multichip_under_forced_cpu(self):
        # dryrun_multichip(4) must build a real 4-device mesh even though
        # the parent platform only promises 1 device.
        out = run_py(
            "import __graft_entry__ as g;"
            "import jax;"
            "g.dryrun_multichip(4);"
            "print('GATE ok', jax.device_count())",
            {"FLEET_FORCE_CPU": "1", "XLA_FLAGS": ""}, timeout=420.0)
        assert out.returncode == 0, out.stderr
        assert "GATE ok 4" in out.stdout


class TestCompileCacheVerify:
    """Known-answer self-check of the persistent compile cache (PR 16):
    a corrupt cache entry must surface as a REJECT (counter bump, cache
    switched off, fresh compiles) — never as wrong solver numerics."""

    @staticmethod
    def _registry():
        from fleetflow_tpu.obs.metrics import REGISTRY
        return REGISTRY

    def _arm(self, monkeypatch, tmp_path):
        """Pretend the cache was enabled for this process, with the
        module globals restored on teardown."""
        monkeypatch.setattr(fp, "_compile_cache_dir", str(tmp_path))
        monkeypatch.setattr(fp, "_cache_verified", False)

    def test_noop_without_cache(self, monkeypatch):
        monkeypatch.setattr(fp, "_compile_cache_dir", None)
        monkeypatch.setattr(fp, "_cache_verified", False)
        assert fp.verify_compile_cache() is False

    def test_pass_path_is_idempotent(self, monkeypatch, tmp_path):
        self._arm(monkeypatch, tmp_path)
        rejects = self._registry().get(
            "fleet_solver_compile_cache_rejects_total")
        before = rejects.value()
        assert fp.verify_compile_cache() is True     # real probe runs
        assert fp._cache_verified is True
        assert fp.verify_compile_cache() is True     # cached verdict
        assert rejects.value() == before
        assert fp._compile_cache_dir == str(tmp_path)

    def test_wrong_answer_rejects_and_switches_off(self, monkeypatch, tmp_path):
        import jax
        self._arm(monkeypatch, tmp_path)
        rejects = self._registry().get(
            "fleet_solver_compile_cache_rejects_total")
        enabled = self._registry().get("fleet_solver_compile_cache_enabled")
        before = rejects.value()
        # a corrupt deserialize surfacing as wrong numerics: the jitted
        # probe returns a value that is not the known answer
        monkeypatch.setattr(jax, "jit", lambda f: (lambda *a: 0))
        logs = []
        assert fp.verify_compile_cache(log=logs.append) is False
        assert rejects.value() == before + 1
        assert enabled.value() == 0
        assert fp._compile_cache_dir is None         # switched off
        assert fp.compile_cache_info()["enabled"] is False
        assert any("REJECTED" in m for m in logs)

    def test_probe_raise_rejects(self, monkeypatch, tmp_path):
        import jax

        def _boom(f):
            def run(*a):
                raise RuntimeError("corrupt deserialize")
            return run

        self._arm(monkeypatch, tmp_path)
        rejects = self._registry().get(
            "fleet_solver_compile_cache_rejects_total")
        before = rejects.value()
        monkeypatch.setattr(jax, "jit", _boom)
        assert fp.verify_compile_cache(log=lambda m: None) is False
        assert rejects.value() == before + 1
        assert fp._cache_verified is False
        # the next verify (cache already switched off) is a quiet no-op
        assert fp.verify_compile_cache() is False

    def test_solve_path_invokes_verify_once(self, tmp_path):
        """End-to-end in a child process: the cache directory set, the
        first solve() enables AND verifies the cache (probe passes on a
        fresh dir), and the enabled gauge stays up."""
        out = run_py(
            "import os, fleetflow_tpu.platform as fp;"
            "from fleetflow_tpu.obs.metrics import REGISTRY;"
            "from fleetflow_tpu.lower import synthetic_problem;"
            "from fleetflow_tpu.solver.api import solve;"
            "res = solve(synthetic_problem(24, 6, seed=0), steps=8);"
            "print('FEAS', res.feasible);"
            "print('VER', fp._cache_verified);"
            "print('REJ', int(REGISTRY.get("
            "'fleet_solver_compile_cache_rejects_total').value()));"
            "print('EN', int(REGISTRY.get("
            "'fleet_solver_compile_cache_enabled').value()))",
            {"JAX_PLATFORMS": "cpu",
             "JAX_ENABLE_COMPILATION_CACHE": "true",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")},
            timeout=300.0)
        assert out.returncode == 0, out.stderr
        assert "VER True" in out.stdout
        assert "REJ 0" in out.stdout
        assert "EN 1" in out.stdout
