"""Launcher plumbing: Auto-axis meshes, the compilation-cache location, and
which contraction path (fused kernels or emulation) the trainer's step
traces."""
import json
import os
import subprocess
import sys
import textwrap

import jax
from jax.sharding import AxisType

from repro.core import use_fused_gemms
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_mesh, mesh_from_flag
from repro.launch.train import build_trainer, parse_args

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def test_every_mesh_has_auto_axes():
    for mesh in (make_mesh((1,), ("pod",)), make_local_mesh(),
                 mesh_from_flag("1,1"), mesh_from_flag("1,1,1")):
        assert mesh.axis_types == (AxisType.Auto,) * len(mesh.axis_names)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev   # nothing set


def test_compile_cache_defaults_into_the_checkout(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _trainer_args(*extra):
    return parse_args(["--arch", "olmo-paper", "--precision", "mxfp8_e4m3",
                       "--batch", "2", "--seq", "64", "--steps", "1",
                       "--log-every", "1", *extra])


def test_lower_step_shows_the_kernels_and_runs_nothing(monkeypatch,
                                                      tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with use_fused_gemms(True):
        tr = build_trainer(_trainer_args())
        text = tr.lower_step().as_text(debug_info=True)
        assert "pallas_call" in text
        assert tr.step == 0 and not tr.history
        hist = tr.run(1)
    assert len(hist) == 1 and tr.events.of_kind("run_start")[0][
        "fused_gemms"]


_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    from repro.core import use_fused_gemms
    from repro.launch.train import build_trainer, parse_args

    args = parse_args(["--arch", "olmo-paper", "--precision", "mxfp8_e4m3",
                       "--batch", "2", "--seq", "64", "--steps", "1",
                       "--log-every", "1", "--mesh", "2,1"])
    with use_fused_gemms(True):
        tr = build_trainer(args)
        text = tr.lower_step().as_text(debug_info=True)
        tr.run(1)
    print(json.dumps({
        "pallas": "pallas_call" in text,
        "fused_gemms": tr.events.of_kind("run_start")[0]["fused_gemms"],
        "loss": tr.history[0]["loss"]}))
""")


def test_sharded_step_traces_the_emulation(tmp_path):
    """GSPMD cannot partition a Mosaic kernel: a step on a mesh of more
    than one device must trace the emulation even when kernels are forced,
    and say so in run_start."""
    env = dict(os.environ, PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert not res["pallas"] and not res["fused_gemms"]
    assert res["loss"] > 0
