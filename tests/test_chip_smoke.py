"""``chip_smoke.py`` on the CPU: its phases at SMOKE_CONFIG widths, and
its refusal to report a result where there is no TPU.

On the CPU the controller phase's kernels run in the Pallas interpreter;
the four-chip phase runs on four virtual CPU devices in a child process
(the device count is fixed when JAX starts).
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("PYTHONPATH", None)        # the script finds src/ itself
    return env


def test_serve_phase(smoke):
    out = smoke.phase_serve(0, smoke=True, n_requests=4, prompt_lens=(5, 24),
                            new_tokens=5)
    assert out["logit_err"] >= 0.0


def test_controller_phase(smoke):
    out = smoke.phase_controller(0, rows=300, d=256, n=200)
    assert sorted(out) == ["bulk_read", "bulk_write", "gather",
                           "scatter_add", "scatter_set"]


def test_simulator_phase(smoke):
    smoke.phase_simulator()


def test_simulator_phase_fails_on_a_changed_record(smoke, monkeypatch):
    smoke.phase_simulator()                    # puts golden_cases on path
    golden_cases = sys.modules["golden_cases"]
    real = golden_cases.golden_record

    def drifted(name):
        rec = real(name)
        rec["n_requests"] += 1
        return rec

    monkeypatch.setattr(golden_cases, "golden_record", drifted)
    with pytest.raises(smoke.SmokeFailure, match="n_requests"):
        smoke.phase_simulator()


def test_four_chip_phase_on_virtual_devices():
    prog = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location("cs", {SCRIPT!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        import sys
        sys.path.insert(0, {os.path.join(REPO, "src")!r})
        out = cs.phase_train_sharded(0, smoke=True, steps=3, batch=8,
                                     seq=32)
        print("RESULT:" + json.dumps(out["history"]))
    """)
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")]
    hist = json.loads(line[-1][len("RESULT:"):])
    assert len(hist) == 3 and hist[-1] < hist[0]


def test_exits_nonzero_without_tpu():
    out = subprocess.run([sys.executable, SCRIPT], env=_cpu_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_exits_nonzero_outside_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = subprocess.run([sys.executable, str(lone)], env=_cpu_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
