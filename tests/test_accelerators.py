"""TPU accelerator-manager logic (no hardware; pure pod-type math).

Reference semantics: _private/accelerators/tpu.py — v2/v3/v4/v5p pod-type
suffixes count TensorCores (2 per chip); v5e/v6e count chips.
"""

import pytest

from ray_tpu.accelerators.tpu import num_workers_in_slice


def test_core_suffix_generations_halved():
    # v5p-8 = 8 cores = 4 chips = one 4-chip host.
    assert num_workers_in_slice("v5p-8", None) == 1
    # v4-16 = 16 cores = 8 chips = two hosts.
    assert num_workers_in_slice("v4-16", None) == 2
    assert num_workers_in_slice("v2-8", None) == 1
    assert num_workers_in_slice("v3-32", None) == 4


def test_chip_suffix_generations_not_halved():
    assert num_workers_in_slice("v5litepod-16", None) == 4
    assert num_workers_in_slice("v5litepod-4", None) == 1


def test_v5e_v6e_8_chip_is_single_host():
    # ct5lp-hightpu-8t / ct6e-standard-8t: one 8-chip host (topology 2x4).
    assert num_workers_in_slice("v6e-8", None) == 1
    assert num_workers_in_slice("v5litepod-8", None) == 1


def test_malformed_pod_type_defaults_to_one():
    assert num_workers_in_slice("weird", None) == 1
    assert num_workers_in_slice("v5p-x", None) == 1


def _busy_then_free(busy_opens: int):
    """An `open` that answers EBUSY `busy_opens` times, then succeeds."""
    import errno
    calls = []

    def opener(path, flags):
        calls.append(path)
        if len(calls) <= busy_opens:
            raise OSError(errno.EBUSY, "Device or resource busy", path)
        return 99

    return opener, calls


@pytest.mark.parametrize("busy_opens, slept", [(0, 0), (3, 3)])
def test_a_leased_worker_waits_until_its_device_files_are_free(
        busy_opens, slept):
    """`/dev/vfio/0` still held by a process that has exited as a zombie
    leader: the worker polls the file until it opens, then goes on. A free
    device costs one open and no sleep."""
    from ray_tpu.accelerators.tpu import wait_for_free_chips
    opener, calls = _busy_then_free(busy_opens)
    sleeps, closed = [], []
    now = [0.0]

    def sleep(s):
        sleeps.append(s)
        now[0] += s

    waited = wait_for_free_chips(
        glob=lambda pattern: ["/dev/vfio/0"] if "vfio" in pattern else [],
        env={}, opener=opener, closer=closed.append, sleep=sleep,
        clock=lambda: now[0])
    assert len(sleeps) == slept and len(calls) == busy_opens + 1
    assert closed == [99] and waited == pytest.approx(0.25 * slept)


def test_the_wait_for_device_files_is_bounded_and_leaves_other_errors():
    import errno
    from ray_tpu.accelerators.tpu import wait_for_free_chips
    now = [0.0]

    def sleep(s):
        now[0] += s

    def always_busy(path, flags):
        raise OSError(errno.EBUSY, "busy", path)

    waited = wait_for_free_chips(
        timeout_s=60.0, glob=lambda p: ["/dev/vfio/0", "/dev/vfio/1"]
        if "vfio" in p else [], env={}, opener=always_busy, sleep=sleep,
        clock=lambda: now[0])
    assert 60.0 <= waited < 61.0

    def no_permission(path, flags):
        raise OSError(errno.EACCES, "denied", path)

    slept = []
    assert wait_for_free_chips(
        glob=lambda p: ["/dev/accel0"] if "accel" in p else [], env={},
        opener=no_permission, sleep=slept.append,
        clock=lambda: 0.0) == 0.0 and not slept
    # no device file at all (a CPU box): nothing to open, nothing waited
    assert wait_for_free_chips(glob=lambda p: [], env={},
                               sleep=slept.append) < 1.0
    assert not slept


@pytest.mark.parametrize("visible, files", [
    (None, ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2", "/dev/vfio/3"]),
    ("1,3", ["/dev/vfio/1", "/dev/vfio/3"]),
    # numbers that name none of the files (IOMMU groups, not chip
    # indices): none is positively this worker's, none is probed
    ("8,9", []),
    # as many chips as the host has files: the whole host's, whatever
    # the files are numbered
    ("4,5,6,7", ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2",
                 "/dev/vfio/3"])])
def test_only_the_files_the_lease_names_are_probed(visible, files):
    from ray_tpu.accelerators.tpu import (chip_device_files,
                                          wait_for_free_chips)
    listing = lambda p: [f"/dev/vfio/{n}" for n in range(4)] \
        if "vfio" in p else []                                   # noqa: E731
    env = {} if visible is None else {"TPU_VISIBLE_CHIPS": visible}
    assert chip_device_files(listing, env) == files
    opened = []

    def opener(path, flags):
        opened.append(path)
        return 7

    assert wait_for_free_chips(glob=listing, env=env, opener=opener,
                               closer=lambda fd: None,
                               clock=lambda: 0.0) == 0.0
    assert opened == files


def test_a_worker_that_waits_says_so_once(caplog):
    import logging
    from ray_tpu.accelerators.tpu import wait_for_free_chips
    opener, _ = _busy_then_free(3)
    now = [0.0]

    def sleep(s):
        now[0] += s

    with caplog.at_level(logging.WARNING, logger="ray_tpu.accelerators.tpu"):
        wait_for_free_chips(
            glob=lambda p: ["/dev/vfio/0"] if "vfio" in p else [], env={},
            opener=opener, closer=lambda fd: None, sleep=sleep,
            clock=lambda: now[0])
    said = [r for r in caplog.records if "held by another process" in
            r.getMessage()]
    assert len(said) == 1 and "/dev/vfio/0" in said[0].getMessage()
