"""chip_smoke.py's phases at a tiny size on the CPU backend, and its
refusal to run without a TPU.

The chip run itself happens on the chip machine (``python chip_smoke.py``);
these cases keep its control flow and its comparisons exercised in every
tier-1 run.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _phase(name, fn, *args):
    meter = chip_smoke.CompileMeter()
    t0, c0, s0, m0 = (chip_smoke.time.perf_counter(), chip_smoke.counters(),
                      chip_smoke.spans(), meter.snap())
    result = fn(*args)
    rep = chip_smoke.phase_report(name, t0, c0, s0, m0, meter)
    chip_smoke.check_report(rep)
    return result, rep


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and '"ok"' not in out


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, capture_output=True,
        text=True, timeout=60, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode != 0 and '"ok"' not in p.stdout, p.stderr


def test_served_phase_matches_host(tmp_path):
    result, rep = _phase(
        "served", chip_smoke.phase_served, 3, 2, 400, 2, 2, 2, str(tmp_path)
    )
    assert result["equal"] and result["client_changes"] == 2 * 2 * 2
    assert rep["kernel_launches"].get("per_doc", 0) > 0


def test_fanin_phase_matches_host():
    result, rep = _phase("fanin", chip_smoke.phase_fanin, 5, 600, 12, 4)
    assert result["equal"] and result["ops"] > 600


@pytest.mark.parametrize("transport", ["dict", "packed"])
def test_mesh_phase_matches_one_device_and_host(transport, monkeypatch):
    # on a chip the one-device merge takes the packed transport, whose
    # conflicts output is a flag, not the count the mesh returns
    monkeypatch.setenv("AUTOMERGE_TPU_TRANSPORT", transport)
    result, rep = _phase("mesh", chip_smoke.phase_mesh, 1, 300, 3, 4)
    assert result["equal"] and rep["kernel_launches"].get("sharded") == 1


def test_smoke_check_refuses_a_degrade_counter():
    rep = {"phase": "x", "kernel_launches": {"per_doc": 1},
           "degrade": {"device.batched_error": 1}, "host_stages": {}}
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_report(rep)
