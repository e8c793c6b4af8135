"""The mesh cell's check on a 4-device CPU mesh at a size a test run holds,
with the Pallas kernels in interpret mode: a sound run passes, the control
fails, and runs with the ring gather's exchange left out or with the eval
scoring the wrong rows fail.

JAX fixes its device count when it starts, so the runs go in one child
process that asks the CPU for four devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "spambase-4m.extreme.mesh"


def mesh_cell() -> dict:
    """The mesh cell from its files: its configuration, mix and limits,
    with the end-to-end metrics of a cell without queries (``BENCHMARK.json``
    lists it once it has been proved on four chips)."""
    from benchmarks.chip import run

    here = ROOT / "benchmarks" / "chip"
    read = lambda *p: json.loads(here.joinpath(*p).read_text())
    cell = run.load_cell("spambase-1m.sparse")
    cell.update(name=CELL, chips=4,
                config=read("configs", "spambase-f32-4m-mesh.json"),
                traffic=read("traffic", "extreme.json"),
                limits=read("limits", f"{CELL}.json"))
    return cell


def small_mesh_readings() -> dict:
    """In the child: the mesh cell at 4096 nodes and 200 test rows, as it
    is, with the control, and with each fault planted."""
    import jax

    from benchmarks.chip import faults, run

    cell = mesh_cell()
    cell["config"].update(n_nodes=4096, n_test=200)
    kind = jax.devices()[0].device_kind
    cell["peaks"]["devices"][kind] = cell["peaks"]["devices"]["TPU v5 lite"]

    def one(fault=None, control=False):
        undo = faults.plant(fault) if fault else (lambda: None)
        try:
            out = run.run_cell(cell, 2**33 + 5, 1.0, False,
                               devs=jax.devices()[:4], interpret=True,
                               control=control)
        finally:
            undo()
        return dict(correct=out["correct"], control=out.get("control"),
                    checks={k: v["value"] for k, v in out["checks"].items()},
                    metrics=sorted(out["metrics"]),
                    count=out["device"]["count"])

    return dict(sound=one(control=True), ring=one("ring"), eval=one("eval"),
                limits=cell["limits"])


@pytest.fixture(scope="module")
def readings():
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(
        flags + ["--xla_force_host_platform_device_count=4"]))
    code = ("import json, sys; sys.path[0:0] = [sys.argv[1], sys.argv[2]]; "
            "from benchmarks.chip.test_chipbench_mesh import "
            "small_mesh_readings; print(json.dumps(small_mesh_readings()))")
    p = subprocess.run([sys.executable, "-c", code, str(ROOT),
                        str(ROOT / "src")], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_sound_run_is_correct_on_four_devices(readings):
    r = readings["sound"]
    assert r["correct"], r["checks"]
    assert r["count"] == 4
    assert r["metrics"] == ["node_cycles_per_s", "peak_hbm_gb", "setup_s"]


def test_mesh_control_is_not_correct(readings):
    from benchmarks.chip import check
    assert not check.verdict(readings["sound"]["control"],
                             readings["limits"])


@pytest.mark.parametrize("fault", ["ring", "eval"])
def test_mesh_planted_fault_is_not_correct(readings, fault):
    assert not readings[fault]["correct"], readings[fault]["checks"]
