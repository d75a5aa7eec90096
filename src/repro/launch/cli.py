"""Shared CLI plumbing for the launch drivers.

``kernel_train`` and ``kernel_serve`` both advertise the live solver/plan
registries in ``--help``; the formatting lives here once so the two can
never drift (a newly registered solver or plan shows up in both drivers
without touching either file). Both, and ``chip_smoke.py``, place JAX's
persistent compile cache through :func:`enable_compile_cache`.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.api import available_plans, available_solvers

#: Where compiled programs are kept when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset. A fixed path: the directory is part of every entry's key, so a
#: per-run name would never hit.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

#: The kinds of backend the ``--backend`` flags accept.
BACKENDS = ("jnp", "pallas")


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compile cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache goes to ``REPO_CACHE_DIR``.
    ``JAX_ENABLE_COMPILATION_CACHE=false`` (the test suite sets it) leaves
    the cache off. Every program is kept, however quick its compile: a
    chip run pays for each one it has to compile again.
    """
    if not jax.config.jax_enable_compilation_cache:
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def registry_epilog() -> str:
    """The ``--help`` epilog enumerating the live registries."""
    return (f"registered solvers: {', '.join(available_solvers())} | "
            f"registered plans: {', '.join(available_plans())} "
            f"(see repro.api.registry; docs/paper_map.md maps each to "
            f"the paper)")


def plan_choices() -> list:
    """Live plan names, for ``choices=`` on a ``--plan`` argument."""
    return available_plans()


def solver_choices() -> list:
    """Live solver names, for ``choices=`` on a ``--solver`` argument."""
    return available_solvers()
