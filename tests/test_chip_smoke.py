"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size through
the Pallas interpreter, and its refusal of a machine without a TPU."""
import json
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402


def test_phases_run_end_to_end_on_cpu(capsys):
    """(a) served path, (b) plain-solver reference, (c) fused + top-k
    kernel route, at C=4, N=8, M=2 with the kernels interpreted."""
    out = chip_smoke.run_phases(4, 8, 2, 0, jax.devices()[:1],
                                chip_smoke.CompileClock())
    assert out["sum_R_c"] <= 1.01 * out["sum_R_a"]
    assert len(out["reference"]) == chip_smoke.REF_CELLS
    for _, want, got in out["reference"]:
        assert got == pytest.approx(want, rel=1e-4)
    assert out["kernels"] == []          # interpreted: no Mosaic kernels
    lines = capsys.readouterr().out.splitlines()
    for tag in ("a/bootstrap", "a/tick", "a/ticks", "b/reference",
                "c/kernels"):
        assert any(f"] {tag} {chip_smoke.SMOKE}" in ln for ln in lines), tag


def test_main_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    for line in out.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok")


def test_sharded_parity_on_four_virtual_devices():
    """The ``--chips 4`` phase on four forced host devices (tiny fleet):
    the sharded search lands on all four and matches one device."""
    import os
    import subprocess

    code = """
import jax, chip_smoke
devs = jax.devices()
assert len(devs) == 4, devs
out = chip_smoke.run_sharded_parity(8, 8, 2, 0, devs,
                                    chip_smoke.CompileClock())
assert out["mismatched"] == 0
print("FOUR-DEVICE-PARITY-OK")
"""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=root)
    assert "FOUR-DEVICE-PARITY-OK" in out.stdout, out.stderr[-2000:]
