"""Test fixtures: force an 8-device virtual CPU platform before JAX init.

Mirrors the reference test strategy (SURVEY.md §4): no cluster, no real
accelerator — master logic tested in-memory, multi-device logic on a virtual
CPU mesh via ``xla_force_host_platform_device_count``.
"""

import os
import sys

_FLAG = "--xla_force_host_platform_device_count=8"
_existing = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _existing:
    os.environ["XLA_FLAGS"] = (_existing + " " + _FLAG).strip()
# Force — not setdefault: the shell profile may export an accelerator
# platform; tests (and every subprocess they spawn) must be CPU-deterministic.
os.environ["JAX_PLATFORMS"] = "cpu"

# jax may already be imported by a pytest plugin; XLA_FLAGS is only read at
# backend init, which must not have happened yet.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices("cpu")) >= 8, (
    "XLA backend initialized before conftest could set "
    "xla_force_host_platform_device_count; run pytest from the repo root"
)

import pytest  # noqa: E402

# Every agent, worker and probe a test spawns is handed a persistent
# compile cache (common/compile_cache.py), by default a fixed directory
# inside the checkout. Six xdist workers and their children must not fill
# that with CPU programs: unless the caller named a cache, give each test
# session a temporary one. The environment variable wins by the helper's
# own rule. Set AFTER jax's import, which is when jax reads it: this
# process compiles uncached, as it always has.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil
    import tempfile

    _cache = tempfile.mkdtemp(prefix="dlrover-tpu-test-jax-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    atexit.register(shutil.rmtree, _cache, ignore_errors=True)


@pytest.fixture(scope="session", autouse=True)
def _graftrace_lockcheck():
    """graftrace runtime lock sanitizer, gated on
    ``DLROVER_TPU_LOCKCHECK=1``: traces every package lock created
    during the session, dumps the flight-style report at teardown
    (``DLROVER_TPU_LOCKCHECK_OUT``, default
    /tmp/graftrace_lockcheck.json), and FAILS the session on an
    observed lock-order cycle or a blocking call made under a
    gradient-path lock.  ``tools/graftrace.py --diff`` then compares
    the dump against the static GL702 model."""
    import json

    from dlrover_tpu.analysis import lockcheck

    if os.environ.get(lockcheck.ENV_FLAG) != "1":
        yield
        return
    lockcheck.install()
    try:
        yield
    finally:
        report = lockcheck.report()
        lockcheck.uninstall()
        out = os.environ.get(lockcheck.ENV_OUT, lockcheck.DEFAULT_OUT)
        with open(out, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
        problems = []
        for cycle in report["cycles"]:
            problems.append("observed lock-order cycle: "
                            + " -> ".join(cycle + cycle[:1]))
        for ev in report["hot_blocking"]:
            problems.append(
                f"blocking {ev['func']} under gradient-path lock(s) "
                f"{', '.join(ev['hot_held'])} at {ev['site']}")
        assert not problems, \
            "graftrace lockcheck: " + "; ".join(problems)


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices("cpu")
    assert len(devices) >= 8, f"expected 8 virtual CPU devices, got {len(devices)}"
    return devices[:8]


@pytest.fixture()
def free_port():
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port
