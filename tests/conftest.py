"""Process hygiene shared by every test file.

Each program the CPU backend compiles maps its kernels' code and data as
separate memory regions, and jax's caches keep every program a process
has compiled.  A worker that runs many files, or one file that compiles
for many shapes (``test_facade.py`` does for every golden fixture), would
otherwise reach the kernel's cap on mapped regions (``vm.max_map_count``),
and XLA faults when it next maps a program.  So compiled programs are
dropped between files once half the cap is in use, and between tests
once three quarters is.
"""
import gc

import jax
import pytest


def _read_int(path):
    try:
        with open(path) as f:
            return int(f.read())
    except (OSError, ValueError):
        return None


MAP_CAP = _read_int("/proc/sys/vm/max_map_count")


def _mapped_share():
    """Mapped regions of this process as a share of the cap, or 0 where
    the platform does not say."""
    if not MAP_CAP:
        return 0.0
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f) / MAP_CAP
    except OSError:
        return 0.0


def _release_above(share):
    if _mapped_share() > share:
        jax.clear_caches()
        gc.collect()


@pytest.fixture(autouse=True, scope="module")
def _release_programs_between_files():
    yield
    _release_above(0.5)


@pytest.fixture(autouse=True)
def _release_programs_between_tests():
    yield
    _release_above(0.75)
