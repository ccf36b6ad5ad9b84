"""``chip_smoke.py`` on the CPU: its phases at the smoke size (kernels in
interpret mode, oracles where ``auto`` resolves), and its refusal to run
without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_smoke_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(cs, capsys):
    assert cs.main([]) == 2
    assert "no TPU" in capsys.readouterr().err


def test_fails_without_the_repo(tmp_path):
    """Alone in a directory, the script cannot import the program and
    prints no result line."""
    shutil.copy(SCRIPT, tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_one_chip_phases_at_smoke_size(cs, capsys):
    clock = cs.CompileClock()
    gate, ds = cs.plant_gate()
    cfg = get_smoke_config(cs.ARCH)
    params = cs.init_model(cfg, 0)
    cs.phase_gate(gate, ds, expect_backend="jnp")
    cb = cs.phase_serve(cfg, params, gate, ds, "cpu", expect_attn="jnp",
                        clock=clock, seed=0)
    cs.phase_kernel_vs_oracle(cfg, params, cb.kv_pages, seed=0)
    out = capsys.readouterr().out
    assert "0 mismatches" in out
    assert f"all {cs.N_REQUESTS} requests terminal" in out
    assert "[kernel]" in out


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("cs", {SCRIPT!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_smoke_config
        gate, ds = cs.plant_gate()
        cfg = get_smoke_config(cs.ARCH)
        cs.phase_router(cfg, cs.init_model(cfg, 0), gate, ds, "cpu",
                        cs.CompileClock(), seed=0)
        print("ROUTER-OK")
    """)
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "ROUTER-OK" in r.stdout
    assert r.stdout.count("streams identical to one batcher") == 4
