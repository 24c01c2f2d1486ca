"""Test harness (reference analogue: tests/unit/common.py).

The reference forks world_size processes with a file-store rendezvous; the
TPU-native equivalent is a single process with an 8-virtual-device CPU mesh
(``--xla_force_host_platform_device_count=8``), which exercises real XLA
collectives/shardings without TPU hardware.  Must run before jax is imported.
"""
import json
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# The suite's time is XLA:CPU compiling toy programs, thousands of them an
# op at a time (a reference forward run eagerly is ~300 programs): LLVM at
# its first level and the plain (not the MLIR) fusion emitters compile one in
# a third of the time, and what the tests hold these programs to does not
# hang on how well they were optimised.  The TPU compiler of
# test_chip_compile.py reads neither flag (its text is the same to the byte).
# The tools the smoke gates start as processes inherit both.
if "xla_backend_optimization_level" not in _flags:
    _flags += " --xla_backend_optimization_level=1"
if "xla_cpu_use_fusion_emitters" not in _flags:
    _flags += " --xla_cpu_use_fusion_emitters=false"
os.environ["XLA_FLAGS"] = _flags.strip()
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


#: seconds a test file took in a six-worker run, for the files of 15 s and
#: more (``tests/file_seconds.json``; from a run's ``--junitxml``, the
#: ``time`` of its ``testcase``s summed by file)
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "file_seconds.json")) as _fh:
    FILE_SECONDS = json.load(_fh)


def pytest_configure(config):
    """Under ``--dist loadfile`` xdist hands the files out by their NUMBER of
    tests, most first, so a file of one long test (a smoke gate that starts
    real processes, 100 s) was handed out last and ran alone while five
    workers had nothing left.  Keep the order of the collection, which
    ``pytest_collection_modifyitems`` below sorts by what a file costs."""
    if getattr(config.option, "loadscopereorder", False):
        config.option.loadscopereorder = False


def _longest_files_first(items):
    """Files not in ``FILE_SECONDS`` (the cheap ones, and a new one whatever
    it costs) first, in the order collected, then the listed files from the
    longest to the shortest: what ends a run is a file of seconds.  A file's
    tests stay together and in their order (the sort is stable); every worker
    sorts alike, as xdist requires."""
    items.sort(key=lambda item: -FILE_SECONDS.get(
        os.path.basename(str(item.fspath)), 1 << 30))


def pytest_collection_modifyitems(config, items):
    """The order of the files (``_longest_files_first``), and the marker
    lints, both failing collection loudly:

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
    _longest_files_first(items)


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
