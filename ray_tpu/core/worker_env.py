"""The environment a worker process is born with — the one place that
decides which processes may open the accelerator and where compiled
programs are cached.

One process per chip: a TPU belongs to one OS process at a time, and a
second process that initialises jax on it fails or hangs. So only a
worker whose lease holds ``TPU`` is started with an environment that
can reach the chip (a *chip worker*, dedicated from birth through the
``tpu:`` prefix of its ``env_hash`` — the runtime_env dedication
mechanism of ``Node._pop_idle``). Every other worker (HTTP proxy, serve
controller, rollout workers, data tasks, feed pumps) is pinned to the
CPU platform, so importing jax there cannot take the chip.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, MutableMapping, Optional

from .resources import ResourceSet

CHIP_PREFIX = "tpu:"
_CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed, in-checkout, git-ignored: the path is part of the cache key, so
# a directory built from a temp name, pid or time would never hit
_DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache(env: Optional[MutableMapping[str, str]] = None) -> str:
    """Make ``env`` (default: this process's) name a persistent compile
    cache: the directory set from outside if there is one, else the
    fixed in-checkout path. jax reads the variable itself; no code sets
    another directory. Returns the directory."""
    env = os.environ if env is None else env
    return env.setdefault(_CACHE_VAR, _DEFAULT_CACHE)


def lease_env_hash(demand: ResourceSet, env_hash: str) -> str:
    """Dedication key of a lease: the runtime_env hash, prefixed for a
    lease that holds ``TPU`` so it only ever lands on a chip worker."""
    return CHIP_PREFIX + env_hash if demand.get("TPU", 0) > 0 else env_hash


def is_chip(env_hash: Optional[str]) -> bool:
    return bool(env_hash) and env_hash.startswith(CHIP_PREFIX)


def worker_env(chip: bool, authkey_hex: str) -> Dict[str, str]:
    """Environment for a new worker process. A chip worker inherits the
    parent's platform choice (under ``JAX_PLATFORMS=cpu``, as in the
    tests, it stays on the CPU) and gets the compile cache; any other
    worker cannot select the TPU platform."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
    # auth token travels via env (RTPU_AUTHKEY), never argv — argv is
    # world-readable through /proc/<pid>/cmdline
    env["RTPU_AUTHKEY"] = authkey_hex
    if chip:
        use_compile_cache(env)
    else:
        env["JAX_PLATFORMS"] = "cpu"
    return env
