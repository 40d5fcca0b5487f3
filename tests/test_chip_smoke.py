"""chip_smoke.py refuses to run off a TPU, and its phases pass at toy size.

The script itself needs the chip; here its phases run on a two-layer bf16
qwen2.5-3b variant with the Pallas kernels in interpret mode.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.configs import get_config, smoke_variant

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_phases_at_toy_size():
    smoke = _load()
    cfg = smoke_variant(get_config("qwen2.5-3b")).replace(
        dtype_name="bfloat16")
    recs = {r["phase"]: r for r in smoke.run_smoke(
        cfg, jax.devices()[0], decode_pallas="interpret")}
    assert list(recs) == ["init", "float", "int8", "update"]
    for phase in ("float", "int8"):
        r = recs[phase]
        assert r["tokens"] == sum(n for _, _, n in smoke.REQUESTS)
        assert r["rows_compared"] >= len(smoke.REQUESTS)
        assert r["logit_rel_l2"] <= smoke.LOGIT_RTOL
    up = recs["update"]
    assert up["flips"] == 1 and up["parts_applied"] >= 2
