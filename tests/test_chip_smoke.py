"""chip_smoke.py, as far as a machine without the chip can show.

The real run needs a TPU (`python chip_smoke.py`, see docs/guide/11). Here:
the CPU dry run drives every phase's control flow and checks at a small
size, and the two refusals of the contract hold — no accelerator, and a
directory that holds the script and nothing else of the repo.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def run(args, cwd=REPO, **env):
    e = dict(os.environ, **env)
    e.pop("FLEET_TRANSFER_GUARD", None)
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=600, env=e)


def test_cpu_dry_run_passes_every_phase():
    out = run(["--cpu-dry-run"])
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()]
    phases = {d["phase"]: d for d in lines if "phase" in d}
    assert list(phases) == ["device", "cold", "churn", "admit", "pod",
                            "summary"]
    assert phases["device"]["platform"] == "cpu"       # said out loud
    assert phases["device"]["dry_run"] is True
    assert phases["churn"]["steady"]["compile_events"] == 0
    assert phases["churn"]["steady"]["reuse_delta"] == 8
    assert phases["churn"]["steady"]["subsolve_localized"] >= 1
    assert set(phases["admit"]["census"]) == {"placed", "departed"}
    assert phases["pod"]["ran"] and all(
        b > 0 for b in phases["pod"]["per_device_bytes"].values())
    # the contract's last line, with the device as JAX reports it
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 8}}


def test_no_accelerator_exits_nonzero_with_no_result():
    out = run([])
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = run(["--cpu-dry-run"], cwd=tmp_path, PYTHONPATH="")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
