"""Tier-1 gate for the chaos campaign: the curated smoke subset of
``tools/chaos_drill.py`` runs as a real subprocess sweep (nine driver
children one after another, every cell read below) so a
robustness-invariant regression — a fault mode that starts crashing with
a stack trace, a kill that stops resuming bit-exact, a corrupt shard
that kills ingest instead of quarantining — fails loudly in CI.

The full point × mode matrix is the same script without ``--smoke``
(a few minutes); run it when touching the fault/retry/quarantine layers.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DRILL = os.path.join(_REPO, "tools", "chaos_drill.py")


def test_chaos_smoke_campaign(tmp_path):
    report_path = str(tmp_path / "chaos_report.json")
    env = dict(os.environ)
    env.pop("PHOTON_FAULTS", None)
    env.pop("PHOTON_FAULTS_STATE_DIR", None)
    proc = subprocess.run(
        [sys.executable, _DRILL, "--smoke",
         "--workdir", str(tmp_path / "work"),
         "--report", report_path],
        cwd=_REPO, env=env, text=True, capture_output=True, timeout=420)
    assert proc.returncode == 0, \
        (f"chaos smoke campaign failed rc={proc.returncode}\n"
         f"{proc.stdout}\n{proc.stderr[-3000:]}")
    assert "CHAOS_OK" in proc.stdout

    with open(report_path) as fh:
        report = json.load(fh)
    assert report["cells_failed"] == 0
    cells = {c["cell"]: c for c in report["cells"]
             if c["outcome"] != "skipped"}
    # the smoke subset runs the cells read here and no other (each is a
    # driver child of its own: tier-1 pays for every one):
    assert sorted(cells) == [
        "cd.update=kill", "cd.update=signal@per_update",
        "io.avro_read=corrupt", "io.index_map=io_error",
        "obs.export=io_error", "obs.flush=io_error",
        "scenario.corrupt_shard"]
    # and it must keep covering each invariant class:
    assert cells["io.avro_read=corrupt"]["outcome"].startswith("degraded")
    assert cells["scenario.corrupt_shard"]["passed"]  # ISSUE acceptance
    assert cells["cd.update=kill"]["outcome"] == "killed+resumed"
    # graceful-stop cell: SIGTERM mid-update must exit 75 with a
    # PHOTON_PREEMPTED line and resume bit-exact from its safe point
    assert cells["cd.update=signal@per_update"]["outcome"] == \
        "preempted+resumed"
    assert cells["io.index_map=io_error"]["outcome"] == "clean_abort"
    assert cells["obs.flush=io_error"]["outcome"] == "ok"
    # live-plane cell: telemetry I/O hard down leaves training exit-0
    # with a bit-exact result and counted drops as the only evidence
    assert cells["obs.export=io_error"]["outcome"].startswith(
        "ok+dropped(")
