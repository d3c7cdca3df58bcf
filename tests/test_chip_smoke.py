"""CPU rehearsal of ``chip_smoke.py``'s control flow.

The smoke itself runs on the chip or not at all. What can be shown here
is that its phases are wired right: the same phase functions, at tiny
sizes, on the CPU — the fused kernel through the Pallas interpreter, the
three children of phase 2 as real subprocesses, the ``--chips 4`` phase
on four virtual CPU devices — and that the script, run with the CPU
forced, refuses. Nothing here is a statement about the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(users=50, items=30, d_global=8)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _one_cpu_device_env() -> dict:
    """The children of phase 2 each see ONE device, as on one chip (the
    harness's 8 virtual devices would make the driver build a mesh)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def test_smoke_shapes_are_the_compile_tests_shapes(smoke):
    """tests/test_tpu_compile.py compiles for the chip what the smoke runs
    on it; the two files state the shapes independently."""
    import test_tpu_compile as compiled

    assert (smoke.GLM_ROWS, smoke.GLM_DIM) == compiled.GLM_SHAPE
    assert smoke.GLMIX_FULL_ROWS == compiled.GLMIX_ROWS
    assert smoke.GLMIX["d_global"] + 1 == compiled.GLMIX_FIXED_DIM
    assert smoke.MESH_ROWS == compiled.MESH_ROWS
    assert smoke.GLMIX_MIN_ROWS * smoke.GLMIX["d_global"] >= 2 * (1 << 21)


def test_phase_glm_rehearsal(smoke, monkeypatch):
    """Phase 1 with the kernel in interpret mode. The one check a CPU
    cannot pass is the one that proves the chip: no Mosaic call."""
    from photon_ml_tpu.ops import pallas_kernels

    real = pallas_kernels.fused_value_gradient_sums
    monkeypatch.setattr(pallas_kernels, "pallas_supported",
                        lambda *a, **k: True)
    monkeypatch.setattr(
        pallas_kernels, "fused_value_gradient_sums",
        lambda loss, interpret, *args: real(loss, True, *args))
    report = smoke.phase_glm(2048, 128, (10.0, 1.0), seed=3)
    assert report["failures"] == [
        "the fused kernel is not in the compiled objective"]
    assert not report["mosaic_call"]
    assert max(report["sums_rel_dev"].values()) <= smoke.SUMS_REL_BOUND
    assert max(report["coef_rel_dev"]) <= smoke.COEF_REL_BOUND
    assert len(report["iterations"]) == 2 and min(report["iterations"]) > 0
    assert report["compile_secs"] > 0


def test_phase_game_rehearsal(smoke, tmp_path, monkeypatch):
    """Phase 2: train -> score -> serve as three children on the CPU, each
    checked against its own record, the scores against the reference."""
    monkeypatch.setitem(smoke.GLMIX, "buckets", 2)
    # this harness holds a (CPU) backend, which is exactly what the smoke's
    # parent must never do on the chip: the guard says so, then steps aside
    import jax

    jax.devices()
    with pytest.raises(smoke.SmokeFailure, match="initialized a JAX backend"):
        smoke.parent_off_chip()
    monkeypatch.setattr(smoke, "parent_off_chip", lambda: None)
    work = str(tmp_path)
    env = _one_cpu_device_env()
    env["PHOTON_NATIVE_LIB"] = smoke.build_native(work)
    fx = smoke.build_fixture(work, 3000, 256, seed=1, workers=0, **TINY)
    compiles = smoke.phase_game(work, fx, env, "cpu")
    assert compiles["train"] > 0
    with open(os.path.join(work, "train_trace", "run_manifest.json")) as fh:
        assert json.load(fh)["device_count"] == 1
    # a phase made to fail fails the run: the service's scores no longer
    # match a model whose fixed effect was tampered with
    model = smoke.read_model(os.path.join(work, "train_out", "best"))
    model["fixed"]["g0"] += 1.0
    ref = smoke.reference_scores(model, fx["heldout"])
    got = smoke.read_scores(os.path.join(work, "score_out"))
    dev = smoke.max_abs_dev([got[f"h0_{i}"] for i in range(256)], ref)
    assert dev > smoke.SCORE_ABS_BOUND


def test_check_training_refuses_a_warm_compile(smoke, tmp_path):
    """The training record's checks, on a hand-made record: a compile
    inside the warm sweep, a rising objective and an undonated fit each
    fail the phase."""
    out = tmp_path / "out"
    out.mkdir()

    def record(objectives):
        states = [{"iteration": i, "coordinate": "fixed", "objective": o}
                  for i, o in enumerate(objectives)]
        (out / "metrics.json").write_text(
            json.dumps({"grid": [{"states": states}]}))

    def span(name, ts, dur=1.0, **labels):
        return {"name": name, "ts_us": ts, "dur_us": dur, "labels": labels}

    sweeps = [span("cd.sweep", 0.0, 100.0, sweep=0),
              span("cd.sweep", 200.0, 100.0, sweep=1)]
    cold_fit = span("xla.compile", 50.0, site="re.fit_blocks",
                    alias_bytes=4096)
    record([10.0, 9.0])
    ok = smoke.check_training(str(out), sweeps + [cold_fit], 2, True, ())
    assert ok["objective_by_sweep"] == [10.0, 9.0]
    warm = span("xla.retrace", 250.0, site="re.fit_blocks")
    with pytest.raises(smoke.SmokeFailure, match="warm sweep compiled"):
        smoke.check_training(str(out), sweeps + [cold_fit, warm], 2, True, ())
    undonated = span("xla.compile", 50.0, site="re.fit_blocks",
                     alias_bytes=0)
    with pytest.raises(smoke.SmokeFailure, match="donating"):
        smoke.check_training(str(out), sweeps + [undonated], 2, True, ())
    record([10.0, 11.0])
    with pytest.raises(smoke.SmokeFailure, match="did not fall"):
        smoke.check_training(str(out), sweeps + [cold_fit], 2, True, ())


def test_phase_mesh_rehearsal(smoke, tmp_path):
    """``--chips 4``'s phase on four virtual CPU devices, in a process of
    its own (this harness has eight, and the driver meshes every device
    it finds)."""
    work = str(tmp_path)
    env = _one_cpu_device_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PHOTON_NATIVE_LIB"] = smoke.build_native(work)
    code = (
        "import json, sys; sys.path.insert(0, %r); import chip_smoke as s; "
        "s.GLMIX['buckets'] = 2; "
        "fx = s.build_fixture(%r, 3000, 0, 1, 0, users=50, items=30, "
        "d_global=8); r = s.phase_mesh(%r, fx, 4); "
        "print('REPORT ' + json.dumps(r))" % (REPO, work, work))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=work,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("REPORT ")][-1]
    report = json.loads(line[len("REPORT "):])
    assert report["failures"] == []
    assert report["mesh"] == {"data": 2, "entity": 2}
    assert report["objective_rel_dev"] <= smoke.MESH_OBJECTIVE_REL_BOUND
    # the CPU backend reports no memory statistics; the chip run must
    assert report["peak_bytes"] == [None] * 4


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_script_refuses_without_a_tpu(argv, tmp_path):
    """The script itself, CPU forced: non-zero, names the platform it
    found, prints no ok line."""
    env = _one_cpu_device_env()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "cpu" in proc.stdout
    assert "FAILED" in proc.stdout.splitlines()[-1]
