"""``chip_smoke.py`` is the command that says whether the system still
starts on the chip — so its own verdict logic is tested here, where
there is no chip: the rehearsal passes end to end, every quiet way off
the device makes it fail, and nothing but a TPU makes it a pass.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def run_smoke(*args, cwd=ROOT, script=SMOKE, timeout=600, **env):
    full_env = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "TFIDF_DEVICE_NEMESIS")}
    full_env.update(env)
    p = subprocess.run([sys.executable, script, *args], cwd=cwd,
                       env=full_env, capture_output=True, text=True,
                       timeout=timeout)
    lines = p.stdout.strip().splitlines()
    if not lines:
        return p, None
    # the last line is the verdict and holds exactly this; the line
    # before it is the record
    verdict, out = json.loads(lines[-1]), json.loads(lines[-2])
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert isinstance(verdict["ok"], bool)
    assert isinstance(verdict["device"]["platform"], str)
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    assert (verdict["ok"], verdict["device"]) == (out["ok"], out["device"])
    return p, out


def test_rehearsal_passes_end_to_end_and_is_never_a_pass():
    p, out = run_smoke("--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["rehearsal"] is True and out["rehearsal_passed"] is True
    # stamped: nothing that reads the verdict (run_smoke checks it says
    # what the record says) can take a CPU run in interpret mode for
    # the chip
    assert out["ok"] is False
    assert out["device"]["platform"] == "cpu"
    assert out["failures"] == []
    assert list(out)[-1] == "claim" and out["claim"] is None
    for stage in ("served", "engine"):
        assert out[stage]["parity_ok"] > 0
        assert out[stage]["kernel_blocks"] >= 1
        assert out[stage]["kernel_interpret"] is True
    assert out["served"]["compiles_in_window"] == 0
    assert out["engine"]["compiles_in_window"] == 0
    assert out["engine"]["kernel_parity_ok"] is True


def test_degraded_worker_fails_the_rehearsal():
    """The no-fallback checks are themselves checked: a worker whose
    every ELL dispatch OOMs answers correctly from the host mirror
    (bit-exact, HTTP 200) — exactly the run that must not pass."""
    p, out = run_smoke("--rehearse", "--stages", "served",
                       TFIDF_DEVICE_NEMESIS="score_ell:oom:1.0")
    assert p.returncode != 0
    assert out["rehearsal_passed"] is False and out["ok"] is False
    said = " ".join(out["failures"])
    assert "compute_fallback_served" in said and "oom" in said, said


@pytest.mark.skipif(os.path.exists("/dev/accel0"),
                    reason="this box has a TPU: the plain command "
                           "would run the whole smoke")
def test_without_a_chip_the_plain_command_fails_and_says_why():
    p, out = run_smoke(timeout=120)
    assert p.returncode != 0
    assert out is None, "no result line without an accelerator"
    assert "no accelerator" in p.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p, out = run_smoke("--rehearse", cwd=str(tmp_path),
                       script=str(tmp_path / "chip_smoke.py"),
                       timeout=120)
    assert p.returncode != 0 and out is None
    assert "tfidf_tpu" in p.stderr


def test_the_parent_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "assert 'jax' not in sys.modules, 'jax imported'" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
