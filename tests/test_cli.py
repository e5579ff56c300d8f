"""CLI subprocess tests for ``python -m repro`` (ISSUE 4 satellite).

Exit codes, artifact JSON schemas, geometry threading, and the sweep
cache-hit behaviour on a second invocation -- all through real
subprocesses, so argument parsing and artifact writing are exercised the
way CI's bench-smoke job runs them.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).parent.parent / "src")


def run_cli(*args, artifact_dir=None, cwd=None):
    env = {"PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin")}
    if artifact_dir is not None:
        env["REPRO_BENCH_ARTIFACT_DIR"] = str(artifact_dir)
    return subprocess.run([sys.executable, "-m", "repro", *args],
                          capture_output=True, text=True, env=env,
                          cwd=cwd)


def test_list_exits_zero_and_names_everything():
    proc = run_cli("list")
    assert proc.returncode == 0
    for needle in ("mk/vector_add", "aes", "arch/tinyllama_1_1b",
                   "# backends", "analytic", "planner"):
        assert needle in proc.stdout, needle


def test_characterize_quick_writes_schema_valid_artifact(tmp_path):
    proc = run_cli("characterize", "--quick", "mk/vector_add", "aes",
                   artifact_dir=tmp_path)
    assert proc.returncode == 0, proc.stderr
    env = json.loads((tmp_path / "characterize.json").read_text())
    assert env["artifact"] == "characterize"
    assert env["schema_version"] == 1
    art = env["payload"]
    assert set(art) == {"mk/vector_add", "aes"}
    for summaries in art.values():
        assert set(summaries) >= {"analytic", "planner", "executor"}
        for s in summaries.values():
            assert isinstance(s.get("bp_cycles"), int)
            assert isinstance(s.get("bs_cycles"), int)


def test_characterize_geometry_changes_reported_cycles():
    base = run_cli("characterize", "mk/multu", "--backends", "analytic")
    small = run_cli("characterize", "mk/multu", "--backends", "analytic",
                    "--geometry", "128x512x4")
    assert base.returncode == 0 and small.returncode == 0
    assert base.stdout != small.stdout
    assert "bp_cycles=210" in base.stdout
    assert "bp_cycles=336" in small.stdout


def test_characterize_bad_geometry_exits_nonzero():
    proc = run_cli("characterize", "mk/multu", "--geometry", "banana")
    assert proc.returncode != 0
    assert "bad --geometry" in proc.stderr


def test_characterize_unknown_workload_fails():
    proc = run_cli("characterize", "no/such_workload")
    assert proc.returncode != 0


@pytest.fixture(scope="module")
def sweep_runs(tmp_path_factory):
    """Two identical sweep invocations against one artifact dir (small
    spec to keep the subprocess cheap)."""
    art = tmp_path_factory.mktemp("artifacts")
    args = ("sweep", "mk/vector_add", "mk/multu",
            "--widths", "4,8", "--geometries", "3", "--no-hybrid")
    first = run_cli(*args, artifact_dir=art)
    second = run_cli(*args, artifact_dir=art)
    return art, first, second


def test_sweep_exit_codes_and_artifacts(sweep_runs):
    art, first, second = sweep_runs
    assert first.returncode == 0, first.stderr
    assert second.returncode == 0, second.stderr
    for name in ("sweep.json", "guidelines.json"):
        assert (art / name).exists(), name


def test_sweep_artifact_schema(sweep_runs):
    art, _, _ = sweep_runs
    sweep = json.loads((art / "sweep.json").read_text())
    assert set(sweep) >= {"spec", "summary", "cache", "cache_stats",
                          "elapsed_s"}
    assert sweep["spec"]["workloads"] == ["mk/vector_add", "mk/multu"]
    assert sweep["spec"]["widths"] == [4, 8]
    assert sweep["summary"]["grid_points"] == 2 * 2 * 2 * 3
    assert sweep["cache_stats"]["entries"] >= 1

    g = json.loads((art / "guidelines.json").read_text())
    assert set(g) >= {"spec", "crossover", "hybrid_recommended", "rules",
                      "geometry_profile", "sweep_summary"}
    assert set(g["crossover"]) == {"mk/vector_add", "mk/multu"}
    for c in g["crossover"].values():
        assert {"crossover_width", "bs_win_widths", "tie_widths",
                "prefix", "bs_feasible_widths"} <= set(c)
    assert g["hybrid_recommended"] == []  # --no-hybrid


def test_sweep_second_invocation_hits_cache(sweep_runs):
    art, first, second = sweep_runs
    assert "cache: miss" in first.stdout
    assert "cache: hit" in second.stdout
    assert json.loads((art / "sweep.json").read_text())["cache"]["hit"]


def test_guidelines_prints_rules(tmp_path):
    proc = run_cli("guidelines", "--no-cache", artifact_dir=tmp_path)
    assert proc.returncode == 0, proc.stderr
    assert "# derived rules" in proc.stdout
    assert "hybrid_recommended" in proc.stdout
    g = json.loads((tmp_path / "guidelines.json").read_text())
    assert g["rules"]


def test_characterize_bad_bandwidth_suffix_exits_cleanly():
    proc = run_cli("characterize", "mk/multu", "--geometry",
                   "128x512x64@abc")
    assert proc.returncode != 0
    assert "bad --geometry" in proc.stderr
    assert "Traceback" not in proc.stderr


# ---------------------------------------------------------------------------
# --arrays threading + machine-bench (ISSUE 8)
# ---------------------------------------------------------------------------

def test_characterize_arrays_override_changes_cycles():
    base = run_cli("characterize", "mk/multu", "--backends", "analytic")
    scaled = run_cli("characterize", "mk/multu", "--backends", "analytic",
                     "--arrays", "4")
    assert base.returncode == 0 and scaled.returncode == 0
    assert base.stdout != scaled.stdout


def test_characterize_bad_arrays_exits_cleanly():
    proc = run_cli("characterize", "mk/multu", "--arrays", "-1")
    assert proc.returncode != 0
    assert "--arrays" in proc.stderr
    assert "Traceback" not in proc.stderr


def test_plan_arrays_override_threads_geometry(tmp_path):
    base = run_cli("plan", "vgg16")
    scaled = run_cli("plan", "vgg16", "--arrays", "16", "--geometry",
                     "128x512x512")
    assert base.returncode == 0 and scaled.returncode == 0
    assert base.stdout != scaled.stdout  # fewer arrays -> more batches


def test_machine_bench_writes_schema_valid_artifact(tmp_path):
    proc = run_cli("machine-bench", "--workload", "vgg16",
                   "--geometries", "2", "--no-execute", "--no-diff",
                   artifact_dir=tmp_path)
    assert proc.returncode == 0, proc.stderr
    env = json.loads((tmp_path / "machine.json").read_text())
    assert env["artifact"] == "machine"
    assert env["schema_version"] == 1
    art = env["payload"]
    assert art["workload"] == "vgg16"
    assert art["gate_failures"] == []
    assert len(art["curve"]) == 2
    for pt in art["curve"]:
        if "error" in pt:
            continue
        assert pt["explained"] is True
        assert pt["total_cycles"] == (pt["compute_cycles"]
                                      + pt["movement_cycles"]
                                      + pt["transpose_cycles"])


def test_machine_bench_unknown_workload_fails():
    proc = run_cli("machine-bench", "--workload", "no/such_app",
                   "--no-execute", "--no-diff")
    assert proc.returncode != 0


def test_pallas_bench_writes_artifact_and_gates(tmp_path):
    """pallas-bench (ISSUE 9): envelope-valid artifact, per-case rows,
    and the regression gate's two verdicts -- pass against itself,
    exit 3 against a doctored too-fast baseline."""
    proc = run_cli("pallas-bench", "--quick", "--reps", "1",
                   "--shape", "vgg_fc_out", artifact_dir=tmp_path)
    assert proc.returncode == 0, proc.stderr
    env = json.loads((tmp_path / "BENCH_pallas.json").read_text())
    assert env["artifact"] == "pallas"
    assert env["schema_version"] == 1
    cases = env["payload"]["cases"]
    # quick widths {4,8,16} x paths {bp, bs_fused, bs_unfused}
    assert {c["name"] for c in cases} == {
        f"vgg_fc_out/w{b}/{p}" for b in (4, 8, 16)
        for p in ("bp", "bs_fused", "bs_unfused")}
    for c in cases:
        assert c["shape"] == [1, 512, 10]
        assert c["us"] > 0
        assert c["padded"][0] >= 1 and c["padded"][2] >= 10

    # a fresh run against its own artifact passes the gate (a generous
    # threshold keeps single-rep jitter from flaking the test; the
    # regression verdict itself is pinned below and in test_kernels)
    proc = run_cli("pallas-bench", "--quick", "--reps", "1",
                   "--shape", "vgg_fc_out", "--regress-threshold", "20",
                   "--baseline", str(tmp_path / "BENCH_pallas.json"),
                   artifact_dir=tmp_path)
    assert proc.returncode == 0, proc.stderr
    assert "regression gate" in proc.stdout

    # doctor every baseline median to ~0 and drop the noise floor: every
    # case is now a regression -> exit 3 (the CI failure mode)
    for c in env["payload"]["cases"]:
        c["us"] = 0.001
    slow = tmp_path / "baseline_doctored.json"
    slow.write_text(json.dumps(env))
    proc = run_cli("pallas-bench", "--quick", "--reps", "1",
                   "--shape", "vgg_fc_out", "--baseline", str(slow),
                   "--regress-floor-us", "0", artifact_dir=tmp_path)
    assert proc.returncode == 3
    assert "regression(s)" in proc.stdout


def test_pallas_bench_unknown_shape_fails():
    proc = run_cli("pallas-bench", "--shape", "nope")
    assert proc.returncode == 2
    assert "unknown shape" in proc.stderr


def test_plan_pallas_flag_times_kernel_schedule(tmp_path):
    """`plan <app> --pallas` lowers the compiled LayoutPlan to the Pallas
    kernel schedule and prints a measured median per step."""
    proc = run_cli("plan", "gemv", "--quick", "--pallas", "--reps", "1",
                   artifact_dir=tmp_path)
    assert proc.returncode == 0, proc.stderr
    assert "pallas" in proc.stdout and "median_us=" in proc.stdout
    env = json.loads((tmp_path / "plans.json").read_text())
    pallas = env["payload"]["gemv"]["pallas"]
    assert pallas["steps"] and all(r["dims"] for r in pallas["steps"])


def test_chip_smoke_refuses_the_cpu():
    """The chip smoke run never carries on without a TPU: non-zero exit
    and no ``"ok": true`` result line."""
    env = {"PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin")}
    root = Path(__file__).parent.parent
    proc = subprocess.run([sys.executable, str(root / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          cwd=root, timeout=300)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert not lines or '"ok": true' not in lines[-1]
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir(tmp_path, from_env):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says, and otherwise to the fixed in-checkout ``.jax-cache``."""
    env = {"PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin")}
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax; from repro.util import use_compile_cache; "
            "print(use_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr
    returned, configured = proc.stdout.split()
    want = (str(tmp_path) if from_env
            else str(Path(__file__).resolve().parent.parent / ".jax-cache"))
    assert returned == configured == want
