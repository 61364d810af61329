"""Launchers: production mesh, multi-pod dry-run, training driver.

NOTE: do not import .dryrun from library code — it pins
XLA_FLAGS=--xla_force_host_platform_device_count=512 at import time.
"""
from .mesh import (make_local_mesh, make_mesh, make_production_mesh,
                   mesh_from_flag)
from .steps import make_prefill_step, make_serve_step, make_train_step

__all__ = ["make_local_mesh", "make_mesh", "make_production_mesh",
           "mesh_from_flag", "make_prefill_step", "make_serve_step",
           "make_train_step"]
