"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see the host's
real single CPU device (the 512 fake devices exist only in dryrun.py)."""

import pytest

from repro.core import Mode, PMDevice, USplit, Volume, VolumeGeometry

SMALL_GEOMETRY = VolumeGeometry(meta_blocks=64, journal_blocks=128,
                                oplog_slots=2, oplog_blocks=64)


@pytest.fixture
def device():
    return PMDevice(size=64 * 1024 * 1024)


@pytest.fixture
def volume(device):
    return Volume.format(device, SMALL_GEOMETRY)


def make_store(volume, mode=Mode.POSIX, **kw):
    kw.setdefault("staging_file_bytes", 1024 * 1024)
    kw.setdefault("staging_prealloc", 2)
    kw.setdefault("staging_background", False)
    return USplit(volume, mode=mode, **kw)


@pytest.fixture
def store(volume):
    return make_store(volume)


@pytest.fixture
def strict_store(volume):
    return make_store(volume, mode=Mode.STRICT, oplog_slot=0)
