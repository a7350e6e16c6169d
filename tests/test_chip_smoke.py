"""The no-fallback rule as a test: `chip_smoke.py`, rehearsed on the CPU at
SF0.01, completes every phase and then FAILS, because the platform is not
`tpu`. A smoke test that passes without a chip proves nothing on one."""

import os
import subprocess
import sys

from nds_tpu.engine.aotcache import compile_cache_root

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("gen_data", "load", "gen_query_stream", "power_one", "reference",
          "probe")


def test_cpu_rehearsal_runs_every_phase_then_fails(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--scale", "0.01", "--work_dir", str(tmp_path / "work")],
        env={**env, "JAX_PLATFORMS": "cpu"}, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600,
    )
    out = p.stdout.strip().splitlines()
    assert p.returncode != 0, p.stdout[-3000:]
    assert '"ok": true' not in out[-1]
    assert out[-1].startswith("chip_smoke: FAILED"), out[-1]
    # both caches where engine/aotcache.compile_cache_root puts them
    assert out[0].startswith(f"compile cache: {compile_cache_root()} "), out[0]
    for name in PHASES:
        assert any(
            line.startswith(f"phase {name}: ") and line.endswith("rc=0")
            for line in out
        ), (name, p.stdout[-3000:], p.stderr[-2000:])
    # six Completed queries whose answers agree with sqlite's ...
    assert sum("status=['Completed'] backend=cpu" in line for line in out) == 6
    assert any(
        line.startswith("reference: ") and '"unmatched": []' in line
        for line in out
    )
    # ... and it fails all the same, for want of the chip and nothing else
    fails = [line for line in out if line.startswith("FAIL: ")]
    assert any("jax found no accelerator" in line for line in fails)
    assert all(
        "ran on cpu" in line or "not the device" in line
        or "no accelerator" in line
        for line in fails
    ), fails
