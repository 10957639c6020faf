"""chip_smoke.py's phases on the CPU backend at tiny sizes (kernels
interpreted), and its refusal to report success without a TPU."""
import json
import subprocess
import sys
import textwrap

import pytest

from conftest import ROOT, jax_subprocess_env, load_chip_smoke


@pytest.fixture(scope="module")
def smoke():
    return load_chip_smoke()


def test_train_phase_resumes_from_a_replica(smoke, tmp_path):
    from repro.configs import get_config
    out = smoke.phase_train(get_config("smollm-135m").smoke(), batch=2,
                            seq=32, workdir=str(tmp_path), platform="cpu")
    assert out["restored_from"] == "POD1"
    assert out["replicated"] == ["ckpts/step-000003", "ckpts/step-000006"]
    assert len(out["losses"]) == 6


def test_verify_phase_matches_numpy(smoke):
    out = smoke.phase_verify(n_words=1 << 18, tail_words=(1 << 18) - 70001,
                             platform="cpu", interpret=True)
    assert out["bytes"] == 1 << 20 and out["hash"] != out["tail_hash"]


def test_ensemble_phase_matches_numpy(smoke):
    out = smoke.phase_ensemble(lanes=4, n_datasets=8, scale=0.01,
                               platform="cpu")
    assert (out["engine"], out["backend"]) == ("lanes", "jax")


def test_phase_refuses_arrays_off_the_expected_platform(smoke):
    with pytest.raises(smoke.SmokeFailure, match="not tpu"):
        smoke.phase_verify(n_words=1 << 18, tail_words=1000,
                           platform="tpu", interpret=True)


def test_main_without_a_tpu_fails_and_reports_nothing(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_relay_phase_on_four_cpu_devices():
    code = textwrap.dedent("""
        import importlib.util, json, sys
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      "chip_smoke.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        print(json.dumps(mod.phase_relay(nbytes=1 << 16, platform="cpu")))
    """)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=jax_subprocess_env(devices=4),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["bytes"] == 1 << 16


def test_simulator_imports_do_not_load_jax():
    """Host-only simulator workers (e.g. the sweep's spawn pool) must not
    load jax: on a chip host a second jax process fights for the chip."""
    code = ("import sys; sys.path.insert(0, 'src'); "
            "import repro.scenarios.sweep, repro.scenarios.run, "
            "repro.ensemble.run; "
            "print('jax' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split() == ["False"]
