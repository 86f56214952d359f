"""Run a test's script in a child process with several host CPU devices.

JAX fixes the device count when its backend starts, so a test that needs
more devices than the test process has runs its body in a fresh child.
"""
import os
import pathlib
import subprocess
import sys

_SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_on_host_devices(tmp_path, source: str, n_devices: int) -> str:
    """Run ``source`` with ``n_devices`` CPU devices; return its stdout,
    failing the test with the child's stderr if it exits non-zero."""
    script = tmp_path / "child.py"
    script.write_text(source)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [_SRC] + [p for p in [os.environ.get("PYTHONPATH")] if p]),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout
