"""CPU rehearsal of ``chip_smoke.py``: both phases at a tiny size with the
Pallas kernels in interpret mode, and the script's refusal to run without
a TPU."""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_host_replay_linearizes_a_batch():
    """The reference's own contract: removals before adds, lowest lane
    wins, a vertex removal takes its edges."""
    h = chip_smoke.HostGraph(4, np.ones(4, bool), np.array([0 * 4 + 1]))
    ok = h.apply([chip_smoke.ADD_EDGE, chip_smoke.ADD_EDGE,
                  chip_smoke.REM_VERTEX, chip_smoke.REM_EDGE,
                  chip_smoke.ADD_VERTEX, chip_smoke.ADD_EDGE],
                 [2, 2, 0, 0, 0, 0], [3, 3, 0, 1, 0, 2])
    assert ok.tolist() == [True, False, True, False, True, True]
    assert h.keys.tolist() == [0 * 4 + 2, 2 * 4 + 3]
    assert h.labels().tolist() == [0, 1, 2, 3]


def test_chip_smoke_phases_rehearse_on_cpu():
    a = chip_smoke.phase_a(nv=256, cap=2048, n_edges=1024, chunk=64,
                           rounds=1, query_bucket=16, n_pair=16, n_reach=8,
                           sparse_impl="pallas_interpret")
    assert a["tiers"] == {"dense": 0, "compact": 1, "full": 1, "skipped": 2}
    assert a["impls"]["frontier_expand@vcap"] == "pallas_interpret"
    b = chip_smoke.phase_b(tenants=4, nv=64, cap=256, waves=2,
                           sparse_impl="pallas_interpret")
    assert b["ops"] == 4 * 3 * 64


@pytest.mark.parametrize("isolated", [False, True])
def test_chip_smoke_refuses_without_tpu(tmp_path, isolated):
    """No TPU: a non-zero exit and no result line, from the checkout and
    from a directory that holds the script alone."""
    script = ROOT / "chip_smoke.py"
    if isolated:
        (tmp_path / "chip_smoke.py").write_bytes(script.read_bytes())
        script = tmp_path / "chip_smoke.py"
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=300, cwd=tmp_path, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU found" in r.stderr
