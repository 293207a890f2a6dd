"""spawn_env — the one subprocess environment builder, and the rule it
serves: a chip belongs to one process at a time.

The process that first initializes a JAX backend on the chip holds it;
a second one that asks fails or hangs in backend init. So children are
pinned to CPU jax unless meant to own the chip, and a child is only let
onto the chip when nobody holds it — refused with a message instead of
left to hang. These tests pin that contract without touching a
device."""

import os
import sys
import types

import pytest

from ray_tpu._private import spawn_env
from ray_tpu._private.config import GLOBAL_CONFIG


class TestStripAccelerator:
    @pytest.mark.parametrize("before,after", [
        (None, "cpu"),            # unset
        ("", "cpu"),
        ("cpu", "cpu"),
        ("tpu", "cpu"),           # the parent owns the chip
        ("TPU", "cpu"),
        ("tpu,cpu", "cpu"),
        (" cpu , tpu ", "cpu"),
        ("cuda", "cuda"),         # another explicit platform is kept
    ])
    def test_platform_pin(self, before, after):
        env = {} if before is None else {"JAX_PLATFORMS": before}
        assert spawn_env.strip_accelerator(env)["JAX_PLATFORMS"] == after

    def test_mutates_in_place_and_touches_nothing_else(self):
        env = {"KEEP": "me", "TPU_LOG_DIR": "disabled",
               "JAX_COMPILATION_CACHE_DIR": "/somewhere"}
        out = spawn_env.strip_accelerator(env)
        assert out is env
        assert env == {"KEEP": "me", "TPU_LOG_DIR": "disabled",
                       "JAX_COMPILATION_CACHE_DIR": "/somewhere",
                       "JAX_PLATFORMS": "cpu"}


class TestChildEnv:
    def test_defaults_pin_and_keep_base(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv("SOME_VAR", "v")
        env = spawn_env.child_env()
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["SOME_VAR"] == "v"
        assert os.environ["JAX_PLATFORMS"] == "tpu"  # parent untouched

    def test_use_accelerator_inherits_untouched(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert spawn_env.child_env(
            use_accelerator=True)["JAX_PLATFORMS"] == "tpu"
        monkeypatch.delenv("JAX_PLATFORMS")
        assert "JAX_PLATFORMS" not in spawn_env.child_env(
            use_accelerator=True)

    def test_pythonpath_layers(self):
        env = spawn_env.child_env(base={"PYTHONPATH": "prior"},
                                  repo_path="/repo",
                                  inherit_sys_path=True)
        parts = env["PYTHONPATH"].split(os.pathsep)
        assert parts[0] == "/repo"
        assert parts[-1] == "prior"
        assert any(p in parts for p in sys.path if p)

    def test_extra_wins_last(self):
        env = spawn_env.child_env(base={}, extra={"JAX_PLATFORMS": "tpu",
                                                  "N": 3})
        assert env["JAX_PLATFORMS"] == "tpu"  # caller override wins
        assert env["N"] == "3"  # stringified


class TestCpuPinned:
    def _fake_jax(self, platforms):
        return types.SimpleNamespace(
            config=types.SimpleNamespace(jax_platforms=platforms))

    @pytest.mark.parametrize("env,config,pinned", [
        ("cpu", None, True),      # the environment says so
        (" CPU ", "tpu", True),
        (None, "cpu", True),      # jax.config says so (tests/conftest)
        (None, None, False),      # nobody asked: jax will take the chip
        ("tpu", None, False),
        ("tpu,cpu", "tpu,cpu", False),
    ])
    def test_env_or_config(self, monkeypatch, env, config, pinned):
        if env is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", env)
        monkeypatch.setitem(sys.modules, "jax", self._fake_jax(config))
        assert spawn_env.cpu_pinned() is pinned

    def test_detect_is_zero_when_pinned_and_never_asks_jax(
            self, monkeypatch):
        from ray_tpu._private import worker as worker_mod

        def no_devices():
            raise AssertionError("a CPU-pinned process asked for devices")

        fake = self._fake_jax("cpu")
        fake.devices = no_devices
        monkeypatch.setitem(sys.modules, "jax", fake)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert worker_mod._detect_tpu_count() == 0.0

    def test_detect_lets_a_backend_error_through(self, monkeypatch):
        """Not pinned means the process wants the chip: a backend that
        cannot start is an error, not "0 TPUs"."""
        from ray_tpu._private import worker as worker_mod

        def broken():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        fake = self._fake_jax(None)
        fake.devices = broken
        monkeypatch.setitem(sys.modules, "jax", fake)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            worker_mod._detect_tpu_count()

    def test_detect_counts_accelerator_devices_only(self, monkeypatch):
        from ray_tpu._private import worker as worker_mod

        fake = self._fake_jax(None)
        fake.devices = lambda: [types.SimpleNamespace(platform=p)
                                for p in ("tpu", "tpu", "cpu")]
        monkeypatch.setitem(sys.modules, "jax", fake)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        assert worker_mod._detect_tpu_count() == 2.0


class TestOneProcessPerChip:
    def test_free_chip_passes(self):
        spawn_env.check_chip_free("a worker", parent_tpus=0.0)
        spawn_env.check_chip_free("a worker", 0.0, other_owners=0)

    def test_parent_holding_the_chip_refuses(self):
        with pytest.raises(RuntimeError) as e:
            spawn_env.check_chip_free("job j1", parent_tpus=1.0)
        msg = str(e.value)
        assert "job j1" in msg and "one process at a time" in msg
        assert "JAX_PLATFORMS=cpu" in msg  # says what to do about it

    def test_second_owner_refuses(self):
        with pytest.raises(RuntimeError, match="3 other child"):
            spawn_env.check_chip_free("a worker", 0.0, other_owners=3)

    @pytest.fixture
    def tpu_access(self):
        GLOBAL_CONFIG.unfreeze()
        GLOBAL_CONFIG.apply_system_config({"worker_tpu_access": True})
        yield
        GLOBAL_CONFIG.reset()

    @pytest.mark.parametrize("head_tpus,children,workers,match", [
        (1.0, 0, 1, "already holds it"),   # the head is on the chip
        (0.0, 0, 2, "1 other child"),      # two workers, one chip
        (0.0, 1, 1, "1 other child"),      # a second pool
    ])
    def test_pool_asked_onto_a_taken_chip_fails_before_spawning(
            self, tpu_access, head_tpus, children, workers, match):
        """worker_tpu_access=True hands every worker of the pool the
        chip. The constructor refuses before any thread, socket or
        child exists — it used to hang in the children's backend
        init."""
        from ray_tpu._private.runtime.process_pool import ProcessWorkerPool

        head = types.SimpleNamespace(tpu_count=head_tpus,
                                     chip_children=children)
        with pytest.raises(RuntimeError, match=match):
            ProcessWorkerPool(head, workers, shm_store=None)
        assert head.chip_children == children  # nothing was granted

    def test_job_asked_onto_the_held_chip_fails(self, monkeypatch,
                                                tmp_path):
        from ray_tpu import job_submission
        from ray_tpu._private import worker as worker_mod

        monkeypatch.setattr(worker_mod, "global_worker",
                            types.SimpleNamespace(tpu_count=1.0))
        client = job_submission.JobSubmissionClient(str(tmp_path))
        with pytest.raises(RuntimeError, match="already holds it"):
            client.submit_job(entrypoint="true",
                              env_vars={"JAX_PLATFORMS": "tpu"})
        assert client.list_jobs() == []
        assert os.listdir(tmp_path) == []  # not even a log file

    def test_cpu_job_under_a_chip_holding_head_runs(self, monkeypatch,
                                                    tmp_path):
        import time

        from ray_tpu import job_submission
        from ray_tpu._private import worker as worker_mod

        monkeypatch.setattr(worker_mod, "global_worker",
                            types.SimpleNamespace(tpu_count=1.0))
        client = job_submission.JobSubmissionClient(str(tmp_path))
        job = client.submit_job(
            entrypoint=f"{sys.executable} -c "
            "\"import os; print(os.environ['JAX_PLATFORMS'])\"")
        deadline = time.time() + 30
        while time.time() < deadline and client.get_job_status(job) in (
                job_submission.JobStatus.PENDING,
                job_submission.JobStatus.RUNNING):
            time.sleep(0.05)
        assert client.get_job_status(job) == \
            job_submission.JobStatus.SUCCEEDED
        assert client.get_job_logs(job).strip() == "cpu"


class TestWireProto:
    """The proto3 handshake envelope (wire.proto / wire_pb2) the peer
    plane speaks; legacy tuple hellos must still parse."""

    def test_proto_hello_roundtrips_every_role(self):
        """Every hello shape the runtime sends must reconstruct the
        exact legacy field tuple its acceptor destructures."""
        from ray_tpu._private import protocol

        cases = [
            (("peer",), ("peer",)),
            (("worker", 3, "task"), (3, "task")),
            (("worker", 7, "ctrl"), (7, "ctrl")),
            (("client", "abc123"), ("client", "abc123")),
            (("join", 42, "arena0", {"num_cpus": 2.0},
              ("127.0.0.1", 9000)),
             ("join", 42, "arena0", {"num_cpus": 2.0},
              ("127.0.0.1", 9000))),
            (("rejoin", 42, "arena0", {"n": 1},
              ("127.0.0.1", 9000), {0: {"pid": 5}}),
             ("rejoin", 42, "arena0", {"n": 1},
              ("127.0.0.1", 9000), {0: {"pid": 5}})),
            (("tok123", 42, "arena0", ("h", 1)),
             ("tok123", 42, "arena0", ("h", 1))),
        ]
        for args, want in cases:
            blob = protocol.make_wire_hello(*args)
            assert isinstance(blob, bytes)
            ver, got = protocol.split_any_hello(blob)
            assert ver == protocol.PROTOCOL_VERSION, args
            assert got == want, (args, got)

    def test_legacy_tuple_still_parses(self):
        from ray_tpu._private import protocol

        ver, fields = protocol.split_any_hello(
            protocol.make_hello("peer"))
        assert ver == protocol.PROTOCOL_VERSION
        assert fields == ("peer",)

    def test_garbage_bytes_rejected_not_crashed(self):
        from ray_tpu._private import protocol

        # Hello{} parses from b"" with role="" -> malformed, and true
        # garbage must also yield the unversioned verdict
        assert protocol.split_any_hello(b"")[0] is None
        ver, _f = protocol.split_any_hello(b"\xff\xfe\x00garbage")
        assert ver is None or ver != protocol.PROTOCOL_VERSION

    def test_reject_roundtrip(self):
        from ray_tpu._private import protocol, wire_pb2

        r = wire_pb2.Reject()
        r.ParseFromString(protocol.proto_reject("skew"))
        assert r.reason == "skew"
        assert r.speaker_version == protocol.PROTOCOL_VERSION
