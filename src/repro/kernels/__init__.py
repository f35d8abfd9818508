"""Optional accelerator-kernel layer: Pallas TPU kernels with jnp
reference implementations (``ref.py``) and dispatch wrappers (``ops.py``).

Everything here is exported *lazily*: importing ``repro.kernels`` never
touches jax, and each attribute resolves its module on first access —
so a host with no jax at all can still import the package and probe
:data:`PALLAS_AVAILABLE`, and only fails, with a clear message, when it
actually asks for a kernel. The planner's fused admission kernel
(``batch_cell_best``) re-exports from ``repro.core.scheduler.grid_pallas``
so kernel consumers have one import surface.
"""
from __future__ import annotations

import importlib
from typing import List

# Only names that do NOT collide with a submodule: once a submodule is
# imported Python pins it as a package attribute, which would shadow the
# lazy resolver — the dispatch wrappers therefore stay importable from
# ``repro.kernels.ops`` only.
_LAZY = {
    "flash_attention_kernel": "repro.kernels.flash_attention",
    "ssd_scan_kernel": "repro.kernels.ssd_scan",
    "batch_cell_best": "repro.core.scheduler.grid_pallas",
}

_probe_cache = None                    # None = not probed yet


def pallas_available() -> bool:
    """True when jax is installed (probed once; interpret-mode execution
    still counts — availability is about the API, not about having a
    TPU). Only a missing jax reads False: any other import error of the
    Pallas API propagates."""
    global _probe_cache
    if _probe_cache is None:
        try:
            importlib.import_module("jax")
        except ImportError:            # pragma: no cover - env without jax
            _probe_cache = False
        else:
            importlib.import_module("jax.experimental.pallas")
            _probe_cache = True
    return _probe_cache


def __getattr__(name: str):
    if name == "PALLAS_AVAILABLE":
        return pallas_available()
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    if not pallas_available():
        raise ImportError(
            f"repro.kernels.{name} needs jax; this host has none — plan "
            f"with CarbonPlanner(batch_backend='numpy'), the oracle")
    return getattr(importlib.import_module(mod), name)


def __dir__() -> List[str]:
    return sorted(list(globals()) + list(_LAZY) + ["PALLAS_AVAILABLE"])
