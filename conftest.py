"""Repo-root pytest config: make the in-tree package importable and hold
tests to a virtual 8-device CPU backend (the "fake cluster").

Both settings must be in place before the JAX backend initialises —
conftest import time is early enough (backends are created lazily):
``JAX_PLATFORMS=cpu`` keeps the suite off any accelerator (tests run
several processes at once; a chip belongs to one), and ``XLA_FLAGS``
gives the CPU backend its eight devices.  The chip itself is exercised
by ``chip_smoke.py`` alone.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    # ``testpaths`` does not apply when a path is typed, and tier-1 types
    # ``tests/``: the benchmark's own tests ride along with it, so the
    # yardstick is guarded where the floor is kept
    here = os.path.dirname(os.path.abspath(__file__))
    given = {os.path.abspath(a.split("::")[0]) for a in config.args}
    yardstick = os.path.join(here, "chipbench", "tests")
    if os.path.join(here, "tests") in given and yardstick not in given:
        config.args.append(yardstick)
