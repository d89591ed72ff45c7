"""Production mesh construction (thin re-export).

The real implementation lives in :mod:`repro.parallel.meshes`; this
module keeps the historical
``repro.launch.mesh`` import path working for launchers and scripts.
"""
from __future__ import annotations

from repro.parallel.meshes import (  # noqa: F401
    make_abstract_mesh,
    make_host_mesh,
    make_mesh,
    make_production_mesh,
)
