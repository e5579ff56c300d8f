"""Cells are data: a configuration, a traffic mix, a metric and a
``workloads`` entry added as files to a copy of the benchmark are found
by name, with no edit to the harness."""
import json
import shutil
import subprocess
import sys
import textwrap

from bench.tests.conftest import ROOT, TINY

DRIVE = textwrap.dedent("""
    import json, sys, time
    from pathlib import Path
    t0 = time.perf_counter()
    root = Path(sys.argv[1])
    sys.path[:0] = [sys.argv[2], str(root)]
    from bench.harness import run_cell
    from bench.spec import load_cell
    cell = load_cell(root, "tiny_lm.tiny")
    chip = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for trace in (False, True):
        try:
            r = run_cell(cell, seed=5, seconds=0.2, trace=trace,
                         device=chip, t0=t0)
        except RuntimeError as e:   # no device plane in a CPU trace
            r = {"error": str(e)}
        print(json.dumps(r))
""")


def test_new_cell_is_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax-cache",
                                                  "__pycache__"))
    b = tmp_path / "bench"
    cfg = dict(json.loads((b / "configs" / "stablelm_1_6b.json")
                          .read_text()), name="tiny_lm", **TINY)
    (b / "configs" / "tiny_lm.json").write_text(json.dumps(cfg))
    (b / "configs" / "tiny_lm.py").write_text(
        "from bench.configs.stablelm_1_6b import build, layers  # noqa\n")
    (b / "traffic" / "tiny.json").write_text(
        json.dumps({"phase": "decode", "tokens": 8, "weight_bits": 4}))
    (b / "metrics" / "calls_per_window.py").write_text(
        "def read(run):\n    return len(run.step_s)\n")
    (b / "metrics" / "trace_only.py").write_text(
        "def read(run):\n    return None\n")

    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny_lm", "source": "test", "file":
        "bench/configs/tiny_lm.json", "reduced": [], "why": "test"})
    spec["workloads"].append({
        "name": "tiny_lm.tiny", "config": "tiny_lm", "traffic": "tiny",
        "chips": 1, "why": "test"})
    spec["end_to_end"].append({
        "name": "calls_per_window", "unit": "calls", "better": "higher",
        "bound": 0.25, "source": "host_clock",
        "workloads": ["tiny_lm.tiny"]})
    spec["per_layer"].append({
        "name": "trace_only", "unit": "x", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "step_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    proc = subprocess.run(
        [sys.executable, "-c", DRIVE, str(tmp_path), str(ROOT / "src")],
        capture_output=True, text=True, timeout=600, cwd=tmp_path,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode == 0, proc.stderr[-3000:]
    plain, traced = [json.loads(l) for l in proc.stdout.splitlines()[-2:]]
    assert plain["correct"]
    assert plain["metrics"]["calls_per_window"]["value"] == \
        plain["attempted"]
    assert set(plain["metrics"]) == {"step_ms", "setup_s",
                                     "calls_per_window"}
    # the CPU has no device plane to trace: the harness says so
    assert "no device operation" in traced["error"]
