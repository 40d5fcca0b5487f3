"""``bench/run.py`` refuses to run without an accelerator, without the
program beside it, and on a configuration the program cannot serve,
printing no result in each case."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, widths
from bench import run as run_cli

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen3b-chat-3tier", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("refusal", [
    harness.UnsupportedConfig("configuration 'x': the program cannot honour "
                              "rope_scaling", ["rope_scaling"]),
    harness.MappingMismatch("configuration 'x': the program maps rope_theta "
                            "to 10000.0, bench/configmap.py to 1000000.0"),
    widths.LayoutMismatch("configuration 'x': layers: served 26, the file "
                          "states 27"),
], ids=lambda e: type(e).__name__)
def test_a_configuration_it_cannot_serve_exits_1_with_one_line(
        refusal, monkeypatch, capsys):
    def run_cell(*_a, **_k):
        raise refusal

    monkeypatch.setattr(harness, "run_cell", run_cell)
    assert run_cli.main(ARGS) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert err == f"bench: {refusal}\n"
