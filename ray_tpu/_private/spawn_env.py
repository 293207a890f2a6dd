"""The one environment builder for every subprocess this framework spawns.

A chip belongs to one process at a time: the process that first
initializes a JAX backend on it holds it until it exits, and a second
process that asks for the same chip fails or hangs in backend init. The
head (or whatever process the user drives JAX from) is that one process,
so every child the framework starts — worker processes, node daemons,
job drivers, test heads, bench children — is pinned to CPU JAX unless
it is explicitly meant to own the chip, and a child is only ever meant
to own it when its parent does not (``check_chip_free``).

Reference analog: upstream ray sanitises ``CUDA_VISIBLE_DEVICES`` for
worker processes (ray: python/ray/_private/utils.py
set_cuda_visible_devices); this is the same idea for the TPU.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Mapping, Optional


def strip_accelerator(env: Dict[str, str]) -> Dict[str, str]:
    """Pin a child's JAX to the CPU. Mutates and returns *env*.

    An unset or empty ``JAX_PLATFORMS`` becomes ``cpu``; so does one
    that names the TPU (``tpu``, ``tpu,cpu``): the parent owns the chip.
    Another explicit platform (e.g. ``cuda``) is the caller's choice and
    is kept.
    """
    tokens = [t.strip().lower()
              for t in env.get("JAX_PLATFORMS", "").split(",")]
    if not any(tokens) or "tpu" in tokens:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def cpu_pinned() -> bool:
    """True when THIS process was told to keep JAX on the CPU, by the
    environment or (once jax is imported) by ``jax.config``. Such a
    process holds no chip and is never asked to look for one."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return True
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    return str(jax.config.jax_platforms or "").strip().lower() == "cpu"


def check_chip_free(what: str, parent_tpus: float,
                    other_owners: int = 0) -> None:
    """Refuse to start *what* as a chip-owning child when the chip is
    taken: by this process (``parent_tpus`` accelerator devices it
    already initialized) or by ``other_owners`` children given the
    same access. Raising here replaces a hang in the child's backend
    init."""
    if parent_tpus > 0:
        raise RuntimeError(
            f"{what} was asked onto the accelerator, but this process "
            f"already holds it ({parent_tpus:g} TPU device(s) "
            "initialized) and a chip belongs to one process at a time. "
            "Run the JAX work in this process (worker_mode='thread' "
            "does), or start this process with JAX_PLATFORMS=cpu so "
            "that exactly one child can own the chip.")
    if other_owners > 0:
        raise RuntimeError(
            f"{what} was asked onto the accelerator, but would share "
            f"it with {other_owners} other child process(es) of this "
            "runtime given the same access, and a chip belongs to one "
            "process at a time: worker_tpu_access=True is only valid "
            "for a single process worker.")


def claim_chip(head, what: str, workers: int) -> None:
    """Let ONE group of ``workers`` children of *head* (a worker pool,
    a remote node) onto the chip, or raise: *head* must not hold it
    (``head.tpu_count``), no group may have been let on before
    (``head.chip_children``), and the group must be a single process."""
    check_chip_free(what, head.tpu_count,
                    head.chip_children + workers - 1)
    head.chip_children += 1


def child_env(base: Optional[Mapping[str, str]] = None, *,
              use_accelerator: bool = False,
              inherit_sys_path: bool = False,
              repo_path: Optional[str] = None,
              extra: Optional[Mapping[str, str]] = None) -> Dict[str, str]:
    """Build the environment for a subprocess.

    - ``use_accelerator=False`` (default): the child is CPU-only jax
      (``strip_accelerator``). This is right for worker processes (the
      head owns the chip), node daemons, test heads, and bench children.
    - ``use_accelerator=True``: inherit the platform choice untouched
      (the child is meant to own the chip; see ``check_chip_free``).
    - ``inherit_sys_path``: prepend the parent's ``sys.path`` to
      PYTHONPATH (worker processes import the driver's modules).
    - ``repo_path``: prepend one directory to PYTHONPATH (tests).
    - ``extra``: final overrides, applied last so callers win.
    """
    env = dict(os.environ if base is None else base)
    if not use_accelerator:
        strip_accelerator(env)
    paths = []
    if inherit_sys_path:
        paths.extend(p for p in sys.path if p)
    if repo_path:
        paths.insert(0, repo_path)
    if paths:
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = os.pathsep.join(paths) + (
            os.pathsep + prev if prev else "")
    if extra:
        for key, value in extra.items():
            env[key] = str(value)
    return env
