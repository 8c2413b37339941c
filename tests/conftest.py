"""Test harness configuration.

Tier-1 tests run without TPU hardware (the analog of the reference's
"no Docker in fast tests" CI tier, .github/workflows/ci.yml:15-70): JAX is
forced onto a virtual 8-device CPU platform so mesh/sharding paths are
exercised on any machine. The chip run is `python chip_smoke.py`.
"""

import gc
import os

# hermetic tests: no persistent XLA cache (platform.py would otherwise put
# one at <checkout>/.jax_cache and later runs would load what earlier runs
# compiled). JAX reads the variable at import; children inherit it.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

# jax may already be imported by the time this runs, so the env var alone
# is too late: force_cpu pins the platform through jax.config before first
# backend use and adds the 8-device XLA flag.
from fleetflow_tpu.platform import force_cpu  # noqa: E402

force_cpu(8)

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_executables():
    """Every compiled XLA:CPU executable holds memory mappings; the whole
    suite in one process piles up past vm.max_map_count (65,530) and dies
    inside backend_compile_and_load. Drop them between test modules."""
    yield
    import jax

    jax.clear_caches()
    gc.collect()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "docker: tier-2 tests needing a real docker daemon "
        "(self-skip when absent; CI runs them serialized)")
    config.addinivalue_line(
        "markers", "slow: multi-process / long-compile tests")


@pytest.fixture
def project(tmp_path):
    """Write a minimal .fleetflow project into tmp_path (the analog of the
    reference's TestProject fixture, fleetflow/tests/common/mod.rs:10-37)."""
    cfg = tmp_path / ".fleetflow"
    cfg.mkdir()

    def write(name: str, content: str):
        p = cfg / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content)
        return p

    write("fleet.kdl", DEFAULT_FLEET_KDL)
    return tmp_path, write


DEFAULT_FLEET_KDL = '''
project "testproj"

service "postgres" {
    image "postgres"
    version "16"
    ports { port host=11432 container=5432 }
    env { POSTGRES_USER "flowuser" }
    resources { cpu 0.5; memory 256 }
}

service "redis" {
    image "redis"
    version "7"
    ports { port host=11379 container=6379 }
}

service "app" {
    image "myapp"
    version "latest"
    ports { port host=11080 container=8080 }
    depends_on "postgres" "redis"
}

stage "local" {
    service "postgres"
    service "redis"
    service "app"
}
'''
