"""chip_smoke.py's contract, rehearsed on the CPU at its tiny size: every
phase and every check runs, and the run fails for ONE stated reason — no
child reported platform `tpu`. There is no switch that turns that check
off; the chip is where it passes (through the chip tool, one process at a
time)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tiny_cpu_rehearsal_fails_for_the_platform_alone(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"))
    env.pop("XLA_FLAGS", None)  # one CPU device, like a one-chip machine
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--tiny"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 1, (p.stdout[-2000:], p.stderr[-2000:])
    # no result on stdout: progress lines only, nothing a driver could
    # parse as the success object
    for line in p.stdout.splitlines():
        assert line.startswith("[chip_smoke] "), line
    summary = json.loads(p.stderr.strip().splitlines()[-1])
    assert summary["ok"] is False and summary["claim"] is None
    assert list(summary)[-1] == "claim"
    # the platform is the ONLY failed check, once per chip-holding child
    assert summary["failed"], summary
    assert all(f.startswith("platform:") for f in summary["failed"]), \
        summary["failed"]
    assert {f.split(":")[1] for f in summary["failed"]} == {
        "prewarm[0]", "prewarm[1]", "serve"}
    assert summary["checks_passed"] >= 40
    # every phase ran and said where: both prewarms, serve, the CPU
    # reference, and the mesh phase's stated skip
    phases = summary["phases"]
    assert len(phases["prewarm"]) == 2
    assert all(pw["programs"] > 0 for pw in phases["prewarm"])
    for name in ("serve", "reference"):
        ph = phases[name]
        assert ph["device"]["platform"] == "cpu"
        assert ph["exit_code"] == 0  # SIGTERM -> graceful stop
        assert ph["final_health"] == "ok"
        assert all(v == 0 for v in ph["containment"].values())
        assert set(ph["family_launches"]) >= {
            "pair", "band", "bivariate", "hpa"}
        assert ph["lstm_train_spans"] >= 2  # train-on-miss over two cycles
        assert ph["anomalies_convicted"] == ph["anomalies_injected"] > 0
    # the serve child replayed programs the prewarm children compiled
    assert phases["serve"]["compile"]["cache_hits"] > 0
    assert "skipped" in phases["mesh"]
    assert summary["verdicts_compared"] == phases["serve"]["submitted"] > 0
    assert summary["verdicts_differing"] == 0
    assert summary["parser"].startswith("foremast_native-")
    assert summary["compile_cache_dir"] == env["JAX_COMPILATION_CACHE_DIR"]


def test_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    """The driver also runs the script with nothing else of the repo
    beside it: it must exit non-zero and print no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_full_size_without_an_accelerator_stops_after_the_first_child(
        tmp_path):
    """What the driver sees in a sandbox with no chip: the full-size run
    fails as soon as its first child says where it ran, prints no result,
    and does not spend the budget on phases a CPU cannot finish."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        cwd=str(tmp_path), capture_output=True, text=True, timeout=600)
    assert p.returncode == 1
    assert '"ok"' not in p.stdout
    summary = json.loads(p.stderr.strip().splitlines()[-1])
    assert summary["ok"] is False and summary["claim"] is None
    assert [f.split(":")[:2] for f in summary["failed"]] == [
        ["platform", "prewarm[0]"]]
    assert "serve" not in summary["phases"]
