"""The command refuses anything but a TPU: non-zero exit, one line, no
result."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    proc = _run("--workload", "gpt2-350m-train.seq1024", "--seed",
                str(2 ** 31 + 5), "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    lines = [l for l in proc.stderr.splitlines()
             if l.startswith("benchmark:")]
    assert lines == ["benchmark: needs a TPU, jax found platform 'cpu'"]


def test_unknown_workload_is_an_error():
    proc = _run("--workload", "no-such.cell", "--seed", "1", "--seconds",
                "1", "--trace", "0")
    assert proc.returncode != 0 and "{" not in proc.stdout
    assert "no workload 'no-such.cell'" in proc.stderr
