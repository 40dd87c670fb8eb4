"""The mesh-reduction and long-window bench legs are driver-run product
surface (bench.py children); pin their record shapes on tiny inputs."""
import os
import sys


# bench.py lives at the repo root (driver contract), not in the package;
# make the import work under bare `pytest` from any CWD
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_mesh_reduction_leg_record_shape(monkeypatch):
    from foremast_tpu import bench_mesh

    monkeypatch.delenv("BENCH_DEVICE_SCORE_S", raising=False)
    rec = bench_mesh.run(B_total=256, T=32, n_runs=3)
    assert rec["n_devices"] == 8  # conftest's virtual mesh
    assert rec["pairs"] == 256
    assert rec["with_reduction_s"] > 0 and rec["score_only_s"] > 0
    assert 0.0 <= rec["reduction_share_cpu_mesh"] < 1.0
    # overhead is max(with-without, 0): never negative
    assert rec["value"] >= 0.0
    # no device time was measured in this run, so none is assumed: the
    # re-rated share is null until bench.py exports its own device leg's
    assert rec["device_score_s_measured"] is None
    assert rec["share_vs_device_scoring_est"] is None
    monkeypatch.setenv("BENCH_DEVICE_SCORE_S", "0.05")
    rec = bench_mesh.run(B_total=256, T=32, n_runs=3)
    assert rec["device_score_s_measured"] == 0.05
    assert 0.0 <= rec["share_vs_device_scoring_est"] < 1.0


def test_long_window_leg_record_shape(monkeypatch):
    import bench as bench_mod

    monkeypatch.setenv("BENCH_LONG_WINDOW", "512")
    monkeypatch.setenv("BENCH_LONG_BATCH", "16")
    monkeypatch.setenv("BENCH_LONG_RUNS", "3")
    rec = bench_mod._long_window_fields()
    assert rec["long_window"] == 512 and rec["long_batch"] == 16
    assert rec["long_band_p99_s"] >= rec["long_band_p50_s"] > 0
    assert rec["long_ses_assoc_speedup"] > 0
    assert rec["long_hw_fit_p50_s"] > 0 and rec["long_hw_batch"] == 2


def test_bench_without_device_number_exits_nonzero(monkeypatch, capsys):
    """A device leg that produced nothing is not a result: bench.py prints
    no JSON line a reader could take for a measurement and exits non-zero
    (it used to print `device_skipped` with exit code 0)."""
    import pytest

    import bench
    calls = []

    def failing_child(cmd, timeout_s, env=None, cwd=None):
        calls.append(cmd)
        return None, "RuntimeError: no accelerator"

    monkeypatch.setattr(bench, "_run_json_child", failing_child)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr()
    assert out.out.strip() == ""  # nothing on stdout
    assert "no accelerator" in out.err
    # it stopped at the device leg: no host-path children were spent on
    # a run that can no longer produce its headline
    assert len(calls) == 1 and "--device-only" in calls[0]


def test_bench_long_leg_failure_reaches_exit_code(monkeypatch, capsys):
    """The headline in hand still prints, but a failed long-window leg
    fails the run instead of hiding in a `long_window_error` field."""
    import json as _json

    import pytest

    import bench

    def child(cmd, timeout_s, env=None, cwd=None):
        if "--device-only" in cmd:
            return {"value": 1.0, "p50_s_at_100k": 0.1,
                    "readback_rtt_floor_s": 0.0, "backend": "cpu"}, None
        if "--long-only" in cmd:
            return None, "TimeoutExpired: long leg"
        return None, "skipped in test"

    monkeypatch.setattr(bench, "_run_json_child", child)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    rec = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["value"] == 1.0
    assert rec["long_window_error"] == "TimeoutExpired: long leg"


def test_bench_parent_never_initialises_a_jax_backend():
    """Exactly one process holds the accelerator: the parent's module
    scope and its orchestration path import no jax at all."""
    import subprocess

    code = (
        "import sys, importlib.util;"
        "spec = importlib.util.spec_from_file_location('b', 'bench.py');"
        "m = importlib.util.module_from_spec(spec);"
        "spec.loader.exec_module(m);"
        "assert 'jax' not in sys.modules, 'bench.py imports jax at load'")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], cwd=root, check=True,
                   timeout=60)
