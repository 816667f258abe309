"""Test configuration: JAX on a virtual 8-device CPU mesh.

Platform selection: tests never use a chip. The CPU platform is selected
twice over — JAX_PLATFORMS=cpu in the environment for any child process a
test starts, and ``simple_pbft_tpu.force_cpu()`` in-process, which wins
over whatever the environment carried in — before any backend initializes,
so a test run on a machine that has a chip leaves it to the one process
that should hold it. XLA_FLAGS asks for 8 virtual host devices (it must be
in the environment before the CPU backend initializes) so the shard_map /
multi-chip paths are genuinely executed. The same jitted code runs on the
chip through chip_smoke.py and the benches, which refuse to run a ``tpu``
path on any other platform; ``chip_smoke.py --cpu-dry-run`` is the tiny
CPU cut of the served path that tier-1 drives.
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import simple_pbft_tpu  # noqa: E402

simple_pbft_tpu.force_cpu()
# Persistent compilation cache (JAX_COMPILATION_CACHE_DIR where set, else
# <repo>/.jax_cache): the crypto kernels take 10-60 s each to compile on a
# small CPU host; a rerun loads them from disk. Entries key on the full
# HLO + flags + backend, so sharing the directory with chip runs is safe.
simple_pbft_tpu.enable_jit_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running scenario (large committees, storms)"
    )
    config.addinivalue_line(
        "markers",
        "sanitize_allow(kind, ...): violations of these sanitizer kinds "
        "(loop/locks) are EXPECTED by this test (it deliberately stalls "
        "a loop or crosses a lock) and do not fail it",
    )


# ---------------------------------------------------------------------------
# runtime sanitizers (ISSUE 8): PBFT_SANITIZE=loop,locks arms them; every
# violation recorded during a test FAILS that test with the attributed
# stack. Zero overhead when the env is unset (the fixture yields through).
# ---------------------------------------------------------------------------

import pytest  # noqa: E402

from simple_pbft_tpu import sanitize  # noqa: E402

sanitize.install()  # no-op unless PBFT_SANITIZE asks for the loop watcher


@pytest.fixture(autouse=True)
def _pbft_sanitizer_gate(request):
    if not (sanitize.enabled("loop") or sanitize.enabled("locks")):
        yield
        return
    sanitize.take_violations()  # drop anything from a previous test
    sanitize.reset_owners()  # fresh objects get fresh owner bindings
    yield
    viols = sanitize.take_violations()
    marker = request.node.get_closest_marker("sanitize_allow")
    if marker is not None:
        allowed = set(marker.args)
        viols = [v for v in viols if v["kind"] not in allowed]
    if viols:
        pytest.fail(sanitize.format_violations(viols), pytrace=False)
