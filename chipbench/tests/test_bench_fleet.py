"""Cells that span four chips build their server from the cell file alone,
on four host CPU devices at toy sizes: ``replicas: 4`` a FleetServer with
one replica per device, ``mesh: [1, 4]`` one LLMServer over a serve mesh
with the weights made straight into its layout. JAX fixes the device
count when its backend starts, so each run happens in a child process."""
import json
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent

CHILD = """
import json, pathlib, sys
sys.path[:0] = {paths!r}
import _tiny, check, manifest, metrics_run, run
wl = _tiny.workload("agent", max_logit_gap=0.02, **{layout!r})
got = run.serve(wl, seed=11, seconds=2.0, trace=False, require_tpu=False,
                out_dir=pathlib.Path({out!r}))
ctx = metrics_run.Run(records=got["records"], t0=got["t0"], seconds=2.0,
                      setup_s=got["setup_s"], spec=wl["spec"], chips=4,
                      stats=got["stats"], kind="cpu", ref=got["ref"])
compared, _ = check.compare(got["records"], got["params"], wl["spec"],
                            wl["reference"], wl["cell"], 11)
end = got["stats"]["end"]
print(json.dumps({{
    "replicas": end.get("fleet_replicas"),
    "mesh_devices": end.get("mesh_devices"),
    "param_devices": len(next(iter(got["params"]["embed"].values()))
                         .sharding.device_set),
    "decode_tokens": [p["decode_tokens"]
                      for p in end.get("per_replica", [end])],
    "affinity_share": manifest.reader("affinity_share").read(ctx),
    "slots_busy": manifest.reader("slots_busy_per_step").read(ctx),
    "correct": check.holds(compared),
    "unfinished": sum(1 for r in got["records"] if r.measured and not r.ok),
    "measured": sum(r.measured for r in got["records"])}}))
"""


def _child(tmp_path, layout):
    paths = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]
    script = tmp_path / "child.py"
    script.write_text(CHILD.format(paths=paths, out=str(tmp_path),
                                   layout=layout))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()
             if x.startswith("{")]
    window = [x for x in lines if x.get("phase") == "window"][0]
    assert window["compiles_in_window"] == 0    # every program was warmed
    got = lines[-1]
    assert got["measured"] > 0 and got["unfinished"] == 0
    assert got["correct"]
    assert got["slots_busy"] > 0
    return got


def test_fleet_cell_from_file(tmp_path):
    got = _child(tmp_path, {"replicas": 4})
    assert got["replicas"] == 4
    assert sum(1 for n in got["decode_tokens"] if n > 0) >= 2
    assert 0.0 <= got["affinity_share"] <= 100.0


def test_mesh_cell_from_file(tmp_path):
    got = _child(tmp_path, {"mesh": [1, 4]})
    assert got["mesh_devices"] == 4 and got["param_devices"] == 4
    assert got["affinity_share"] is None        # no router on one server
    assert got["decode_tokens"][0] > 0
