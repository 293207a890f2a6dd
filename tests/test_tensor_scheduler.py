"""TensorScheduler: semantics parity vs the EventScheduler oracle,
kernel unit tests, and determinism (same graph in -> same decisions out).

Mirrors the reference's scheduler test pattern
(ray: src/ray/raylet/scheduling/cluster_task_manager_test.cc — drive the
scheduler with synthetic task specs and fake cluster resource views)."""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.scheduler import kernels
from ray_tpu._private.scheduler.kernels import DONE, RUNNING, WAITING


# ----------------------------------------------------------------------
# End-to-end semantics through the public API (oracle parity)
# ----------------------------------------------------------------------

class TestTensorSchedulerE2E:
    def test_fanout(self, ray_start_tensor_sched):
        @ray_tpu.remote
        def f(i):
            return i * 2

        refs = [f.remote(i) for i in range(200)]
        assert ray_tpu.get(refs) == [i * 2 for i in range(200)]

    def test_map_reduce_deps(self, ray_start_tensor_sched):
        @ray_tpu.remote
        def m(i):
            return i

        @ray_tpu.remote
        def r(*xs):
            return sum(xs)

        maps = [m.remote(i) for i in range(50)]
        out = r.remote(*maps)
        assert ray_tpu.get(out) == sum(range(50))

    def test_chain_deps(self, ray_start_tensor_sched):
        @ray_tpu.remote
        def inc(x):
            return x + 1

        ref = ray_tpu.put(0)
        for _ in range(30):
            ref = inc.remote(ref)
        assert ray_tpu.get(ref) == 30

    def test_error_propagation(self, ray_start_tensor_sched):
        @ray_tpu.remote
        def boom():
            raise ValueError("boom")

        @ray_tpu.remote
        def use(x):
            return x

        with pytest.raises(ValueError):
            ray_tpu.get(use.remote(boom.remote()))

    def test_resource_capacity_respected(self, ray_start_tensor_sched):
        running = []
        lock = threading.Lock()
        peak = [0]

        @ray_tpu.remote(num_cpus=2)
        def heavy():
            with lock:
                running.append(1)
                peak[0] = max(peak[0], len(running))
            time.sleep(0.02)
            with lock:
                running.pop()
            return 1

        # 4 worker threads / 4 CPUs -> at most 2 concurrent 2-CPU tasks
        refs = [heavy.remote() for _ in range(8)]
        assert sum(ray_tpu.get(refs)) == 8
        assert peak[0] <= 2

    def test_actors_on_tensor_sched(self, ray_start_tensor_sched):
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.x = 0

            def incr(self, n=1):
                self.x += n
                return self.x

        c = Counter.remote()
        refs = [c.incr.remote() for _ in range(20)]
        assert ray_tpu.get(refs) == list(range(1, 21))

    def test_retry_releases_slot(self, ray_start_tensor_sched):
        """A retried failure must not leak the original RUNNING slot
        (the finished-notification goes out under the execution's id
        BEFORE the retry is resubmitted under a fresh id)."""
        attempts = []

        @ray_tpu.remote(max_retries=2, retry_exceptions=True)
        def flaky():
            attempts.append(1)
            if len(attempts) < 3:
                raise ValueError("transient")
            return "ok"

        assert ray_tpu.get(flaky.remote(), timeout=10) == "ok"
        assert len(attempts) == 3
        sched = ray_tpu._private.worker.global_worker.scheduler
        deadline = time.time() + 5
        while time.time() < deadline:
            s = sched.stats()
            if s["running"] == 0 and s["ready_queue"] == 0:
                break
            time.sleep(0.01)
        s = sched.stats()
        assert s["running"] == 0, s
        assert s["ready_queue"] == 0, s

    def test_cancel_queued(self, ray_start_tensor_sched):
        import ray_tpu.exceptions as rex

        ev = threading.Event()

        @ray_tpu.remote
        def gate():
            ev.wait(2)
            return 1

        @ray_tpu.remote
        def after(x):
            return x

        g = gate.remote()
        dep = after.remote(g)
        ray_tpu.cancel(dep)
        ev.set()
        with pytest.raises(rex.TaskCancelledError):
            ray_tpu.get(dep, timeout=5)


# ----------------------------------------------------------------------
# Kernel unit tests (numpy backend)
# ----------------------------------------------------------------------

class TestAssignKernelNp:
    def _demands(self, *rows):
        return np.asarray(rows, dtype=np.float32)

    def test_fills_local_then_spills(self):
        demands = self._demands([1, 0, 0, 0])
        cap = np.asarray([[4, 0, 0, 0], [4, 0, 0, 0]], dtype=np.float32)
        avail = cap.copy()
        ready = np.arange(6)
        cls = np.zeros(8, dtype=np.int32)
        node_of, new_avail = kernels.assign_np(
            ready, cls, demands, avail, cap, threshold=0.5)
        # all 6 assigned; capacity respected on both nodes
        assert (node_of >= 0).all()
        assert (new_avail >= 0).all()
        counts = np.bincount(node_of, minlength=2)
        assert counts.sum() == 6
        assert (counts <= 4).all()
        # hybrid: node0 takes up to threshold (2 of 4 cpus) first
        assert counts[0] >= 2

    def test_oversubscription_defers(self):
        demands = self._demands([1, 0, 0, 0])
        cap = np.asarray([[3, 0, 0, 0]], dtype=np.float32)
        avail = cap.copy()
        node_of, new_avail = kernels.assign_np(
            np.arange(10), np.zeros(16, np.int32), demands, avail, cap, 0.5)
        assert (node_of >= 0).sum() == 3
        assert new_avail[0, 0] == 0

    def test_infeasible_never_assigned(self):
        demands = self._demands([8, 0, 0, 0])
        cap = np.asarray([[4, 0, 0, 0]], dtype=np.float32)
        node_of, _ = kernels.assign_np(
            np.arange(2), np.zeros(4, np.int32), demands, cap.copy(), cap, 0.5)
        assert (node_of == -1).all()

    def test_zero_demand_tasks_all_run(self):
        demands = self._demands([0, 0, 0, 0])
        cap = np.asarray([[1, 0, 0, 0]], dtype=np.float32)
        node_of, _ = kernels.assign_np(
            np.arange(100), np.zeros(128, np.int32), demands, cap.copy(),
            cap, 0.5)
        assert (node_of >= 0).all()

    def test_multi_class(self):
        demands = self._demands([1, 0, 0, 0], [0, 1, 0, 0])
        cap = np.asarray([[2, 1, 0, 0]], dtype=np.float32)
        cls = np.asarray([0, 0, 1, 1], dtype=np.int32)
        node_of, new_avail = kernels.assign_np(
            np.arange(4), cls, demands, cap.copy(), cap, 1.1)
        # 2 cpu tasks fit; 1 tpu task fits
        assert (node_of[:2] >= 0).all()
        assert (node_of[2:] >= 0).sum() == 1
        assert new_avail[0, 0] == 0 and new_avail[0, 1] == 0

    def test_determinism(self):
        rng = np.random.default_rng(0)
        demands = self._demands([1, 0, 0, 0], [2, 0, 0, 0])
        cap = rng.integers(1, 8, size=(4, 1)).astype(np.float32)
        cap = np.concatenate([cap, np.zeros((4, 3), np.float32)], axis=1)
        cls = rng.integers(0, 2, size=64).astype(np.int32)
        a1 = kernels.assign_np(np.arange(64), cls, demands, cap.copy(), cap, 0.5)
        a2 = kernels.assign_np(np.arange(64), cls, demands, cap.copy(), cap, 0.5)
        assert (a1[0] == a2[0]).all()
        assert np.allclose(a1[1], a2[1])


class TestEdgeFireNp:
    def test_fire_decrements_once(self):
        src = np.asarray([0, 0, 1], dtype=np.int32)
        dst = np.asarray([2, 3, 3], dtype=np.int32)
        consumed = np.zeros(3, dtype=bool)
        indeg = np.asarray([0, 0, 1, 2], dtype=np.int32)
        done = np.asarray([True, False, False, False])
        indeg, consumed = kernels.fire_edges_np(done, src, dst, consumed, indeg)
        assert indeg.tolist() == [0, 0, 0, 1]
        # firing again with same done mask is a no-op (consumed)
        indeg, consumed = kernels.fire_edges_np(done, src, dst, consumed, indeg)
        assert indeg.tolist() == [0, 0, 0, 1]
        done = np.asarray([True, True, False, False])
        indeg, consumed = kernels.fire_edges_np(done, src, dst, consumed, indeg)
        assert indeg.tolist() == [0, 0, 0, 0]


# ----------------------------------------------------------------------
# numpy vs jax kernel parity on whole-graph simulation
# ----------------------------------------------------------------------

class TestJaxTickParity:
    def _simulate_np(self, indeg, cls, demands, cap, src, dst, max_ticks=64):
        """Instant-completion simulation with the numpy kernels."""
        C = len(indeg)
        state = np.full(C, WAITING, dtype=np.int8)
        avail = cap.copy()
        consumed = np.zeros(len(src), dtype=bool)
        order = []
        for _ in range(max_ticks):
            ready = np.flatnonzero((state == WAITING) & (indeg <= 0))
            if len(ready) == 0:
                if (state == WAITING).any():
                    continue
                break
            node_of, avail = kernels.assign_np(
                ready, cls, demands, avail, cap, 0.5)
            assigned = ready[node_of >= 0]
            state[assigned] = DONE
            order.append(set(assigned.tolist()))
            # instant completion: release
            for s in assigned:
                avail[node_of[np.where(ready == s)[0][0]]] += demands[cls[s]]
            avail = np.minimum(avail, cap)
            indeg, consumed = kernels.fire_edges_np(
                state == DONE, src, dst, consumed, indeg)
        return state, order

    def test_diamond_graph_completes_in_waves(self):
        # 0 -> {1, 2} -> 3
        src = np.asarray([0, 0, 1, 2], dtype=np.int32)
        dst = np.asarray([1, 2, 3, 3], dtype=np.int32)
        indeg = np.asarray([0, 1, 1, 2], dtype=np.int32)
        cls = np.zeros(4, dtype=np.int32)
        demands = np.asarray([[1, 0, 0, 0]], dtype=np.float32)
        cap = np.asarray([[8, 0, 0, 0]], dtype=np.float32)
        state, order = self._simulate_np(indeg.copy(), cls, demands, cap,
                                         src, dst)
        assert (state == DONE).all()
        assert order == [{0}, {1, 2}, {3}]

    def test_jax_matches_numpy_on_random_dags(self):
        import jax  # noqa: F401 — provided by conftest CPU mesh env

        rng = np.random.default_rng(42)
        C, E = 256, 512
        src = rng.integers(0, C - 1, size=E).astype(np.int32)
        dst = (src + rng.integers(1, 16, size=E).clip(max=C - 1)).clip(
            max=C - 1).astype(np.int32)
        keep = src < dst
        src, dst = src[keep], dst[keep]
        order = np.argsort(dst, kind="stable")  # kernel requires sorted dst
        src, dst = src[order], dst[order]
        indeg = np.zeros(C, dtype=np.int32)
        np.add.at(indeg, dst, 1)
        cls = rng.integers(0, 2, size=C).astype(np.int32)
        demands = np.asarray([[1, 0, 0, 0], [2, 0, 0, 0]], dtype=np.float32)
        cap = np.asarray([[64, 0, 0, 0], [32, 0, 0, 0]], dtype=np.float32)

        state_np, _ = self._simulate_np(indeg.copy(), cls, demands, cap,
                                        src, dst, max_ticks=C)
        assert (state_np == DONE).all()

        # jax instant-completion simulation of the same DAG
        state = np.full(C, WAITING, dtype=np.int8)
        ind = indeg.copy()
        avail = cap.copy()
        consumed = np.zeros(len(src), dtype=bool)
        pin = np.full(C, -1, dtype=np.int32)
        for _ in range(C):
            state, ind, avail_j, node_of, consumed = kernels.jax_tick(
                state, ind, cls, pin, demands, avail, cap, src, dst, consumed,
                num_classes=2, threshold=0.5, instant_completion=True)
            state = np.asarray(state)
            ind = np.asarray(ind)
            avail = np.asarray(avail_j)
            consumed = np.asarray(consumed)
            if (state == DONE).all():
                break
        assert (state == DONE).all()
        assert np.allclose(avail, cap)
        assert (ind <= 0).all()


# ----------------------------------------------------------------------
# Virtual multi-node behavior through the scheduler directly
# ----------------------------------------------------------------------

class TestTensorSchedulerMultiNode:
    def _mk(self, caps):
        from ray_tpu._private.scheduler.local import NodeState
        from ray_tpu._private.scheduler.tensor import TensorScheduler

        dispatched = []
        lock = threading.Lock()

        def dispatcher(task):
            with lock:
                dispatched.append(task)

        sched = TensorScheduler([NodeState(c) for c in caps], dispatcher)
        return sched, dispatched, lock

    def _spec(self, i, cpus=1.0):
        from ray_tpu._private.ids import JobID, TaskID
        from ray_tpu._private.task_spec import TaskSpec

        job = JobID.from_int(1)
        return TaskSpec(task_id=TaskID.of(job, seq=i), name=f"t{i}",
                        func=None, func_descriptor="f",
                        args=(), kwargs={}, resources={"CPU": cpus})

    def test_spillback_to_second_node(self):
        from ray_tpu._private.scheduler.base import PendingTask

        sched, dispatched, lock = self._mk(
            [(2.0, 0, 1e18, 1e18), (2.0, 0, 1e18, 1e18)])
        try:
            for i in range(4):
                sched.submit(PendingTask(spec=self._spec(i), deps=[],
                                         execute=lambda t, n: None))
            deadline = time.time() + 5
            while time.time() < deadline:
                with lock:
                    if len(dispatched) == 4:
                        break
                time.sleep(0.005)
            with lock:
                nodes = sorted(t.node_index for t in dispatched)
            assert len(nodes) == 4
            assert set(nodes) == {0, 1}  # spilled beyond node 0
        finally:
            sched.shutdown()

    def test_queued_until_node_added(self):
        from ray_tpu._private.scheduler.base import PendingTask
        from ray_tpu._private.scheduler.local import NodeState

        sched, dispatched, lock = self._mk([(1.0, 0, 1e18, 1e18)])
        try:
            sched.submit(PendingTask(spec=self._spec(0, cpus=4.0), deps=[],
                                     execute=lambda t, n: None))
            time.sleep(0.1)
            with lock:
                assert len(dispatched) == 0
            sched.add_node(NodeState((8.0, 0, 1e18, 1e18)))
            deadline = time.time() + 5
            while time.time() < deadline:
                with lock:
                    if dispatched:
                        break
                time.sleep(0.005)
            with lock:
                assert len(dispatched) == 1
                assert dispatched[0].node_index == 1
        finally:
            sched.shutdown()


class TestTicksByBackend:
    """stats() says which backend served the assignment passes, so a
    caller can tell a device run from a numpy one (chip_smoke.py holds
    phase A to device ticks > 0 and numpy ticks == 0)."""

    @pytest.mark.parametrize("backend,other", [("jax", "numpy"),
                                               ("numpy", "jax")])
    def test_public_api_ticks_counted_under_the_chosen_backend(
            self, backend, other):
        ray_tpu.shutdown()
        ray_tpu.init(num_workers=2, scheduler="tensor",
                     _system_config={"sched_backend": backend})
        try:
            @ray_tpu.remote
            def f(i):
                return i + 1

            assert ray_tpu.get(f.map_remote([(i,) for i in range(64)]),
                               timeout=120) == list(range(1, 65))
            from ray_tpu._private.worker import global_worker
            stats = global_worker.scheduler.stats()
        finally:
            ray_tpu.shutdown()
        assert stats["ticks_by_backend"][backend] > 0
        assert stats["ticks_by_backend"][other] == 0
        assert stats["assign_failures"] == 0
        assert stats["ticks"] >= stats["ticks_by_backend"][backend]

    def test_failing_device_tick_raises_and_is_counted(self, monkeypatch):
        """Under the device backend a failing tick is NOT served by
        numpy: the pass raises (the tick loop logs it and counts it)
        and the task stays queued."""
        from ray_tpu._private.config import GLOBAL_CONFIG
        from ray_tpu._private.scheduler.base import PendingTask

        def boom(*a, **kw):
            raise RuntimeError("device tick failed")

        monkeypatch.setattr(kernels, "jax_assign", boom)
        GLOBAL_CONFIG.unfreeze()
        GLOBAL_CONFIG.apply_system_config({"sched_backend": "jax"})
        mk = TestTensorSchedulerMultiNode()
        sched, dispatched, lock = mk._mk([(2.0, 0, 1e18, 1e18)])
        try:
            sched.submit(PendingTask(spec=mk._spec(0), deps=[],
                                     execute=lambda t, n: None))
            deadline = time.time() + 5
            while time.time() < deadline:
                if sched.stats()["assign_failures"]:
                    break
                time.sleep(0.005)
            stats = sched.stats()
        finally:
            sched.shutdown()
            GLOBAL_CONFIG.reset()
        assert stats["assign_failures"] >= 1
        assert stats["ticks_by_backend"] == {"jax": 0, "numpy": 0}
        assert stats["ready_queue"] == 1
        with lock:
            assert dispatched == []


class TestManyClasses:
    """The class axis is scanned (class as data), so large class counts
    must run the jax path without per-class recompiles and must match the
    numpy oracle decision-for-decision in totals."""

    def test_64_classes_jax_matches_numpy(self):
        rng = np.random.default_rng(7)
        K, C, N = 64, 512, 8
        demands = np.zeros((K, 4), dtype=np.float32)
        demands[:, 0] = rng.integers(1, 4, size=K)
        cls = rng.integers(0, K, size=C).astype(np.int32)
        cap = np.zeros((N, 4), dtype=np.float32)
        cap[:, 0] = rng.integers(16, 64, size=N)
        ready_idx = np.arange(C)

        node_np, avail_np = kernels.assign_np(
            ready_idx, cls, demands, cap.copy(), cap, 0.5)
        node_jx, avail_jx = kernels.jax_assign(
            cls, demands, cap.copy(), cap, 0.5)

        # identical assignment decisions per task, not just totals
        assert (node_np == node_jx).all()
        assert np.allclose(avail_np, avail_jx, atol=1e-4)

    def test_spread_round_robin_parity(self):
        """SPREAD: the jax water-filling path must land the same per-node
        COUNTS as the numpy true round-robin (task interleaving may
        differ; tasks of one class are interchangeable)."""
        rng = np.random.default_rng(11)
        for trial in range(6):
            N = int(rng.integers(2, 9))
            C = int(rng.integers(1, 64))
            demands = np.asarray([[1, 0, 0, 0]], dtype=np.float32)
            cls = np.zeros(C, dtype=np.int32)
            cap = np.zeros((N, 4), dtype=np.float32)
            cap[:, 0] = rng.integers(1, 32, size=N)
            avail = cap.copy()
            # uneven starting load so argsort order is non-trivial
            avail[:, 0] -= rng.integers(0, 2, size=N)
            avail[:, 0] = np.maximum(avail[:, 0], 0)
            spread = np.ones(1, dtype=bool)

            node_np, avail_np = kernels.assign_np(
                np.arange(C), cls, demands, avail.copy(), cap, 0.5,
                class_spread=spread)
            node_jx, avail_jx = kernels.jax_assign(
                cls, demands, avail.copy(), cap, 0.5,
                class_spread=spread)

            counts_np = np.bincount(node_np[node_np >= 0], minlength=N)
            counts_jx = np.bincount(node_jx[node_jx >= 0], minlength=N)
            assert (counts_np == counts_jx).all(), (
                trial, counts_np, counts_jx)
            assert np.allclose(avail_np, avail_jx, atol=1e-4)

    def test_class_bucket_no_recompile(self):
        """Growing the class count within a power-of-two bucket reuses the
        same compiled program (jax_assign pads the class axis)."""
        import jax

        cap = np.asarray([[64, 0, 0, 0]], dtype=np.float32)

        def run(k):
            demands = np.zeros((k, 4), dtype=np.float32)
            demands[:, 0] = 1
            cls = np.arange(k, dtype=np.int32)
            kernels.jax_assign(cls, demands, cap.copy(), cap, 0.5)

        run(33)  # lands in the 64-class bucket
        fn = kernels._jit_assign(0.5)
        sizes_before = fn._cache_size()
        run(48)  # same bucket: no new compile
        assert fn._cache_size() == sizes_before
        run(65)  # next bucket: exactly one new compile is allowed
        assert fn._cache_size() == sizes_before + 1


class TestDispatchWindow:
    """The raylet-dispatch-queue analog: simple CPU tasks lease beyond
    live capacity, queueing at the pool; window leases hold no node
    resources, so accounting must balance exactly."""

    def test_window_accounting_balances(self):
        import ray_tpu
        from ray_tpu._private import worker as wm

        ray_tpu.shutdown()
        ray_tpu.init(num_workers=2, scheduler="tensor",
                     _system_config={"worker_mode": "process",
                                     "worker_pipeline_depth": 4})
        try:
            @ray_tpu.remote
            def f(x):
                return x + 1

            refs = [f.remote(i) for i in range(200)]
            assert ray_tpu.get(refs, timeout=120) == \
                [i + 1 for i in range(200)]
            sched = wm.global_worker.scheduler
            import numpy as np
            import time
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline \
                    and sched._outstanding.sum() != 0:
                time.sleep(0.05)
            # every lease returned; nothing over- or under-released
            assert sched._outstanding.sum() == 0
            assert (sched._avail >= -1e-6).all()
            assert np.allclose(sched._avail[0], sched._cap[0])
            assert not sched._windowed.any()
        finally:
            ray_tpu.shutdown()

    def test_window_excludes_constrained_classes(self):
        """Named-resource and >1-CPU classes must NOT over-dispatch:
        their concurrency bound is the resource, not a worker pipe."""
        import ray_tpu
        from ray_tpu._private import worker as wm

        ray_tpu.shutdown()
        ray_tpu.init(num_workers=4, scheduler="tensor",
                     resources={"gadget": 1.0},
                     _system_config={"worker_mode": "process",
                                     "worker_pipeline_depth": 8})
        try:
            @ray_tpu.remote(resources={"gadget": 1.0})
            def exclusive(i):
                # CLOCK_MONOTONIC is system-wide on Linux, so the
                # (start, end) intervals are comparable across the
                # worker processes
                import time as _t
                t0 = _t.monotonic()
                _t.sleep(0.05)
                return (i, t0, _t.monotonic())

            # gadget has capacity 1: windowing it would run 2+ at once
            # worker-side; correctness here = all complete AND no two
            # execution intervals overlap
            refs = [exclusive.remote(i) for i in range(6)]
            rows = ray_tpu.get(refs, timeout=120)
            assert sorted(r[0] for r in rows) == list(range(6))
            spans = sorted((t0, t1) for _, t0, t1 in rows)
            for (_, prev_end), (next_start, _) in zip(spans, spans[1:]):
                assert next_start >= prev_end, \
                    f"gadget tasks overlapped: {spans}"
            sched = wm.global_worker.scheduler
            # class 0 may be windowable; the gadget class must not be
            gadget_cls = [i for i, ok in
                          enumerate(sched._class_window_ok) if not ok]
            assert gadget_cls, "named-resource class missing"
        finally:
            ray_tpu.shutdown()
