"""Test harness (reference analogue: tests/unit/common.py).

The reference forks world_size processes with a file-store rendezvous; the
TPU-native equivalent is a single process with an 8-virtual-device CPU mesh
(``--xla_force_host_platform_device_count=8``), which exercises real XLA
collectives/shardings without TPU hardware.  Must run before jax is imported.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["DS_ACCELERATOR"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


_BUILTIN_MARKERS = frozenset({
    "parametrize", "skip", "skipif", "xfail", "usefixtures",
    "filterwarnings", "tryfirst", "trylast", "anyio",
})


def _registered_marker_names(config):
    """Marker names REGISTERED in tests/pytest.ini (``name:`` /
    ``name(args):``) that ROUTE a suite.  ``config.getini("markers")``
    also reports pytest's builtin markers (parametrize/xfail/skipif/...),
    which must NOT satisfy the coverage lint — a parametrized-but-unrouted
    test file is exactly what it exists to catch — so builtins are
    excluded, as is ``world_size`` (a capability marker: it gates device
    count, it does not select a subsystem)."""
    names = set()
    for entry in config.getini("markers"):
        head = entry.split(":", 1)[0].strip()
        names.add(head.split("(", 1)[0])
    return names - _BUILTIN_MARKERS - {"world_size"}


def pytest_collection_modifyitems(config, items):
    """Marker lints, both failing collection loudly:

    * every test in a chaos-suite file must carry the ``serving_chaos``
      marker — with ``--strict-markers`` (pytest.ini) a misspelled marker
      already fails collection; this closes the remaining hole of a chaos
      file with NO marker silently joining every run;
    * generalized (PR 12): every ``tests/unit/test_*.py`` file must carry
      at least one marker REGISTERED in pytest.ini on every test, so
      ``-m <subsystem>`` selections stay exhaustive and a new suite can't
      land unroutable.
    """
    bad = [item.nodeid for item in items
           if "chaos" in os.path.basename(str(item.fspath))
           and item.get_closest_marker("serving_chaos") is None]
    if bad:
        raise pytest.UsageError(
            "chaos tests must be marked serving_chaos: " + ", ".join(bad))

    registered = _registered_marker_names(config)
    unmarked = {}
    for item in items:
        path = str(item.fspath)
        if os.sep + "unit" + os.sep not in path:
            continue
        if not any(m.name in registered for m in item.iter_markers()):
            unmarked.setdefault(os.path.basename(path), 0)
            unmarked[os.path.basename(path)] += 1
    if unmarked:
        raise pytest.UsageError(
            "test files without a registered pytest marker (add a "
            "subsystem pytestmark; see tests/pytest.ini markers): " +
            ", ".join(sorted(unmarked)))


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Each test gets a fresh global topology."""
    from deepspeed_tpu.runtime import topology

    topology.reset_topology()
    yield
    topology.reset_topology()


@pytest.fixture
def mesh8():
    """Default 8-device pure-DP mesh."""
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    return initialize_mesh(TopologyConfig(), force=True)


def world_size_guard(n: int):
    """Skip when fewer than n devices exist (reference: common.py:262)."""
    return pytest.mark.skipif(
        len(jax.devices()) < n, reason=f"requires {n} devices")
