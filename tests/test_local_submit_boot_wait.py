"""A worker's nested submission on a node whose other workers are still
booting: the daemon waits for a sibling on a thread of its own, so the
submitter's reader goes on serving, and leases the child to the sibling
once it connects; with no sibling booting, or after the wait, the
submitter itself takes it as before."""

import threading
import time

import cloudpickle
import pytest

from ray_tpu._private.ids import JobID
from ray_tpu._private.runtime import node_daemon as nd


class _Alive:
    def poll(self):
        return None


def _slot(num, connected):
    s = nd._WorkerSlot(num)
    s.proc = _Alive()
    s.conn = object() if connected else None
    return s


def _daemon(*slots):
    d = object.__new__(nd.NodeDaemon)
    d._slots = {s.num: s for s in slots}
    d._lock = threading.Lock()
    d._slots_changed = threading.Condition(d._lock)
    d._resview = {"accept": True, "cap": 16,
                  "job": JobID.from_int(7).binary()}
    d._resview_lock = threading.Lock()
    d._local_tids = set()
    d._local_dispatched = 0
    d._local_leases = {}
    d.sent = []
    d._send_head = lambda msg: d.sent.append(("head", msg))
    d._to_worker = lambda slot, msg: d.sent.append((slot.num, msg))
    return d


ARGS = (cloudpickle.dumps({
    "name": "child", "resources": {}, "has_refs": False, "arg_refs": [],
    "func_blob": b"fn", "args_blob": b"args", "num_returns": 1,
    "max_retries": 2}),)


def _task_target(d, timeout=5.0):
    deadline = time.monotonic() + timeout
    while True:
        for who, msg in list(d.sent):
            if who != "head" and msg[0] == "task":
                return who
        if time.monotonic() >= deadline:
            return None
        time.sleep(0.01)


def test_the_child_waits_for_a_booting_sibling_off_the_reader():
    submitter, sibling = _slot(0, True), _slot(1, False)
    d = _daemon(submitter, sibling)
    t0 = time.monotonic()
    assert d._maybe_local_submit(submitter, 11, ARGS) is None
    assert time.monotonic() - t0 < 1.0      # the reader is not held
    time.sleep(0.2)
    assert _task_target(d, timeout=0.0) is None
    with d._slots_changed:
        sibling.conn = object()
        d._slots_changed.notify_all()
    assert _task_target(d) == 1
    replies = [m for who, m in d.sent if who == 0 and m[0] == "reply"]
    assert [m[:3] for m in replies] == [("reply", 11, True)]


def test_with_no_sibling_booting_the_submitter_takes_it_at_once():
    submitter = _slot(0, True)
    d = _daemon(submitter)
    assert d._maybe_local_submit(submitter, 12, ARGS) is None
    assert _task_target(d, timeout=0.0) == 0


def test_after_the_wait_the_submitter_takes_it(monkeypatch):
    monkeypatch.setattr(nd, "_SIBLING_BOOT_WAIT_S", 0.2)
    submitter, sibling = _slot(0, True), _slot(1, False)
    d = _daemon(submitter, sibling)
    assert d._maybe_local_submit(submitter, 13, ARGS) is None
    assert _task_target(d) == 0
    assert sibling.conn is None


@pytest.mark.parametrize("connected", [True, False])
def test_a_connected_sibling_is_taken_without_a_wait(connected):
    submitter, sibling = _slot(0, True), _slot(1, True)
    booting = _slot(2, connected)
    d = _daemon(submitter, sibling, booting)
    assert d._maybe_local_submit(submitter, 14, ARGS) is None
    assert _task_target(d, timeout=0.0) in (1, 2)
